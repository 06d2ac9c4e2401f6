"""The dense decoder's parameters, operations and attention calls (Mistral
family: grouped-query attention on every layer, full causal mask, a gated MLP
of three matrices, an untied head).

Functions of the configuration file and the traffic file alone, each with its
derivation on one line. No JAX: the run's parent loads this file.
"""

from __future__ import annotations

import counts


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]


def matmul_params(cfg: dict) -> int:
    """Weights that multiply every token: per layer wq [d,q] + wk, wv [d,kv]
    + wo [q,d] + gate, up, down [d,f]; plus the head [d,V]. The embedding
    table is a lookup and is left out."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * head_dim(cfg)
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    layer = 2 * d * q + 2 * d * kv + 3 * d * f
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    """matmul weights + embedding [V,d] + two norm scales a layer + final."""
    d = cfg["hidden_size"]
    return (matmul_params(cfg) + cfg["vocab_size"] * d
            + (2 * cfg["num_hidden_layers"] + 1) * d)


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward = 2 per weight per token + causal attention (QK^T and PV, 2
    flops a multiply-add, half the square): 2*seq*q per layer. Backward is
    twice the forward. No recomputation, no embedding lookup."""
    q = cfg["num_attention_heads"] * head_dim(cfg)
    fwd = 2 * matmul_params(cfg) + cfg["num_hidden_layers"] * 2 * seq * q
    return 3.0 * fwd


def flash_shard_shape(cfg: dict, traffic: dict) -> tuple:
    return counts.flash_shard_shape(
        traffic, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        head_dim(cfg))


def flash_calls(cfg: dict, traffic: dict) -> list:
    """Every layer the same call under the full causal triangle."""
    return [(flash_shard_shape(cfg, traffic), {"window": None},
             cfg["num_hidden_layers"])]
