"""Granite 4.0-H's parameters, operations, attention calls and state-space
scans (every layer a mixer, Mamba-2 or attention without position embedding,
then a dense SwiGLU MLP; a tied head), for ONE CHIP'S SHARE of a deployment:
the vocabulary rows the configuration file says are held here.

Functions of the configuration file and the traffic file alone, each with its
derivation on one line. No JAX: the run's parent loads this file.
"""

from __future__ import annotations

import os

import arch
import counts

_mamba = arch.load(os.path.join(arch.HERE, "architectures", "nemotron_h"),
                   "counts")


def kinds(cfg: dict, kind: str) -> int:
    """Layers whose mixer is ``mamba`` or ``attention``."""
    return cfg["layer_types"].count(kind)


def as_nemotron(cfg: dict) -> dict:
    """The mixer's sizes under the names ``nemotron_h``'s files read: the
    same Mamba-2 mixer, its counts and its reference are theirs."""
    return {"hidden_size": cfg["hidden_size"],
            "mamba_num_heads": cfg["mamba_n_heads"],
            "mamba_head_dim": cfg["mamba_d_head"],
            "n_groups": cfg["mamba_n_groups"],
            "ssm_state_size": cfg["mamba_d_state"],
            "conv_kernel": cfg["mamba_d_conv"],
            "chunk_size": cfg["mamba_chunk_size"],
            "layer_norm_epsilon": cfg["rms_norm_eps"]}


def head_dim(cfg: dict) -> int:
    """The config states none: hidden / q heads (2048 / 32 = 64)."""
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def mamba_params(cfg: dict) -> int:
    """``nemotron_h``'s Mamba layer: the projections, conv taps and bias,
    A_log, D, dt_bias, the gated norm's scale and the layer's norm [d]."""
    return _mamba.mamba_params(as_nemotron(cfg))


def attention_matmul_params(cfg: dict) -> int:
    """wq [d, q] + wo [q, d] with q = heads * head_dim, wk, wv [d, kv]."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    return 2 * d * cfg["num_attention_heads"] * hd \
        + 2 * d * cfg["num_key_value_heads"] * hd


def mlp_params(cfg: dict) -> int:
    """gate, up [d, f] + down [f, d], f the shared MLP's width."""
    return 3 * cfg["hidden_size"] * cfg["shared_intermediate_size"]


def total_params(cfg: dict) -> int:
    """Every layer: its mixer with the mixer's norm [d], the MLP with its
    norm [d]; the tied table [V, d], V the rows held; the final norm."""
    d = cfg["hidden_size"]
    return (kinds(cfg, "mamba") * mamba_params(cfg)
            + kinds(cfg, "attention") * (attention_matmul_params(cfg) + d)
            + cfg["num_hidden_layers"] * (mlp_params(cfg) + d)
            + cfg["vocab_size"] * d + d)


def scan_flops_a_token(cfg: dict) -> dict:
    """``nemotron_h``'s: the chunked scan's products a token and Mamba
    layer, at one group and chunks of 256."""
    return _mamba.scan_flops_a_token(as_nemotron(cfg))


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward = 2 per weight that multiplies the token (a Mamba layer's
    projections and conv taps, attention's projections, every layer's MLP,
    the tied head's rows held) + attention's QK^T and PV over the causal
    triangle (2 matmuls * 2 flops * q width * pairs / seq a token) + the
    scan's forward products. Backward is twice the forward. No
    recomputation, no embedding lookup, no multiplier."""
    d, mixer = cfg["hidden_size"], as_nemotron(cfg)
    h, p, g, n, _ = _mamba.mamba_sizes(mixer)
    weights = kinds(cfg, "mamba") * (
        _mamba.mamba_matmul_params(mixer)
        + cfg["mamba_d_conv"] * (h * p + 2 * g * n)) \
        + kinds(cfg, "attention") * attention_matmul_params(cfg) \
        + cfg["num_hidden_layers"] * mlp_params(cfg) + d * cfg["vocab_size"]
    attention = kinds(cfg, "attention") * 2 * 2 \
        * cfg["num_attention_heads"] * head_dim(cfg) \
        * counts.causal_pairs(seq) / seq
    scan = kinds(cfg, "mamba") * scan_flops_a_token(cfg)["fwd"]
    return 3.0 * (2 * weights + attention + scan)


def flash_calls(cfg: dict, traffic: dict) -> list:
    """One kind of call: full causal, 32 q heads over 8 kv heads of 64."""
    return [(counts.flash_shard_shape(
                traffic, cfg["num_attention_heads"],
                cfg["num_key_value_heads"], head_dim(cfg)),
             {"window": None}, kinds(cfg, "attention"))]


def ssd_needs(cfg: dict, traffic: dict) -> dict:
    """``nemotron_h``'s: what one scan call needs at one group and chunks of
    256 (what the mathematics needs: a head block's own share of dB and dC,
    which the kernel writes where a group is several blocks, is not
    counted)."""
    return _mamba.ssd_needs(as_nemotron(cfg), traffic)


ssd_call_min_seconds = _mamba.ssd_call_min_seconds
