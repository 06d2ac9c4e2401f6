"""The dense decoder in plain float32 (Mistral family, as the configuration's
source publishes it): its leaves and its loss, for ``reference.follow``.

Token embedding, then per layer ``h = x + Wo·attn(rope(Wq·n), rope(Wk·n),
Wv·n)`` with ``n = rmsnorm(x)`` and grouped-query causal softmax attention,
``out = h + Wdown·(silu(Wgate·m) * Wup·m)`` with ``m = rmsnorm(h)``, a final
rmsnorm, an untied head, and the mean next-token cross entropy. One RoPE on
every layer, the full causal mask. Imports nothing of the program.
"""

from __future__ import annotations

import jax

from reference import (attention, by_position_blocks, mean_over_rows,
                       next_token_nll_sum, rmsnorm, rope)


def leaf_specs(cfg: dict) -> list:
    """``[(path, shape, std)]`` for every parameter leaf, in the sorted order
    of the program's parameter tree (dict keys sort the same way). ``std`` is
    None for a norm scale (ones). Kernels are [in, out]."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    f = cfg["intermediate_size"]
    specs = [(("embedding",), (v, d), cfg.get("initializer_range", 0.02)),
             (("final_norm", "scale"), (d,), None)]
    for i in range(cfg["num_hidden_layers"]):
        layer = f"layer_{i}"
        specs += [
            ((layer, "attn", "wq", "kernel"), (d, q), d ** -0.5),
            ((layer, "attn", "wk", "kernel"), (d, kv), d ** -0.5),
            ((layer, "attn", "wv", "kernel"), (d, kv), d ** -0.5),
            ((layer, "attn", "wo", "kernel"), (q, d), q ** -0.5),
            ((layer, "attn_norm", "scale"), (d,), None),
            ((layer, "mlp", "gate", "kernel"), (d, f), d ** -0.5),
            ((layer, "mlp", "up", "kernel"), (d, f), d ** -0.5),
            ((layer, "mlp", "down", "kernel"), (f, d), f ** -0.5),
            ((layer, "mlp_norm", "scale"), (d,), None),
        ]
    specs.append((("lm_head", "kernel"), (d, v), d ** -0.5))
    return sorted(specs)


def _layer(cfg, p, x):
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]
    s = x.shape[0]
    n = rmsnorm(x, p["attn_norm"]["scale"], cfg["rms_norm_eps"])
    q = (n @ p["attn"]["wq"]["kernel"]).reshape(s, -1, hd)
    k = (n @ p["attn"]["wk"]["kernel"]).reshape(s, -1, hd)
    v = (n @ p["attn"]["wv"]["kernel"]).reshape(s, -1, hd)
    o = attention(rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"]), v)
    h = x + o.reshape(s, -1) @ p["attn"]["wo"]["kernel"]
    m = rmsnorm(h, p["mlp_norm"]["scale"], cfg["rms_norm_eps"])

    def mlp(mb):
        gate = mb @ p["mlp"]["gate"]["kernel"]
        up = mb @ p["mlp"]["up"]["kernel"]
        return (jax.nn.silu(gate) * up) @ p["mlp"]["down"]["kernel"]

    return h + by_position_blocks(mlp, m)


def hidden(cfg, params, tokens):
    """The final-norm hidden states [S, D] of one row of ids [S]."""
    x = params["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda p, y: _layer(cfg, p, y))(
            params[f"layer_{i}"], x)
    return rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def loss_fn(cfg: dict, params: dict, tokens: jax.Array) -> jax.Array:
    """Mean next-token cross entropy of a batch of ids [B, S]."""
    return mean_over_rows(
        lambda row: next_token_nll_sum(
            hidden(cfg, params, row), params["lm_head"]["kernel"], row),
        tokens)
