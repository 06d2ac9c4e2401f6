"""Granite 4.0-H in plain float32 (as the configuration's source publishes it,
each inference listed under ``assumed`` in the configuration file): its
leaves and its loss, for ``reference.follow``. One chip's share: the
vocabulary rows the configuration says are held here.

The input is ``x = embedding_multiplier · E[ids]``. Every layer is two
parts, each ``x ← x + residual_multiplier · part(rmsnorm(x))``:

- the mixer, by ``layer_types``: ``mamba``, the Mamba-2 mixer (``z, u, dt =
  n·W_z, n·W_xbc, n·W_dt`` with ``u = [x | B | C]``; ``u ← silu(b_c + Σ_k w_k
  ⊙ u_{t−K+1+k})``, depthwise, causal, zeros before the row's start; ``Δ =
  softplus(dt + dt_bias)`` a head, ``A = −exp(A_log)``; ONE group of B and C
  for every head, the recurrence ``S_t = exp(Δ_t A) S_{t−1} + Δ_t B_t
  x_tᵀ``, ``y_t = S_tᵀ C_t + D x_t`` token by token; ``y ← W_norm ⊙ rms(y
  ⊙ silu(z))`` over all of the mixer's columns; ``part = y·W_o``: the same
  mixer as ``nemotron_h``'s, whose ``mamba`` this file calls at one group),
  or ``attention``: grouped-query causal softmax of ``q kᵀ ·
  attention_multiplier`` (the blocks' ``attention`` at ``head_dim^-½`` with
  q scaled by ``attention_multiplier · head_dim^½``, the same number), NO
  position embedding;
- the MLP, ``W_down·(silu(W_gate·m) ⊙ W_up·m)`` at
  ``shared_intermediate_size``.

A final rmsnorm, the head tied to the table, logits ``x · Eᵀ /
logits_scaling``, the mean next-token cross entropy. Imports nothing of the
program.
"""

from __future__ import annotations

import os

import jax

import arch
from reference import (attention, by_position_blocks, mean_over_rows,
                       next_token_nll_sum, rmsnorm)

_nemotron = arch.load(os.path.join(arch.HERE, "architectures", "nemotron_h"),
                      "reference")
_counts = arch.load(os.path.dirname(os.path.abspath(__file__)), "counts")

# Random weights that behave as a trained model's (the configuration file's
# ``assumed.weights`` has the reasons). Table rows of std 0.1, so that the
# input 12 · E[ids] has rows of about unit RMS; q and k of std 4 a column, so
# that scores at the published 1/64 spread by about 2 (at 1/sqrt(fan-in) they
# would spread by 1/8 and every query would attend nearly alike); A_log of std
# 0.5 and dt_bias of std 3 (the generator draws normal(0, std) or ones, so
# the published uniform ranges cannot be drawn): a few heads a layer keep a
# memory longer than a chunk and none holds over 0.29 of the one group's norm
# (over 48 seeds on the CPU; at nemotron_h's std 3 for A_log one head holds
# nearly all of it).
EMBEDDING_STD = 0.1
QK_SPREAD = 4.0
A_LOG_STD = 0.5
DT_BIAS_STD = 3.0


def leaf_specs(cfg: dict) -> list:
    """``[(path, shape, std)]`` for every parameter leaf, in the sorted order
    of the program's parameter tree. ``std`` is None for a leaf of ones (a
    norm's scale, the mixer's ``D``). Kernels are [in, out]."""
    d, v, f = cfg["hidden_size"], cfg["vocab_size"], \
        cfg["shared_intermediate_size"]
    q = cfg["num_attention_heads"] * _counts.head_dim(cfg)
    kv = cfg["num_key_value_heads"] * _counts.head_dim(cfg)
    m = _nemotron.sizes(_counts.as_nemotron(cfg))
    specs = [(("embedding",), (v, d), EMBEDDING_STD),
             (("final_norm", "scale"), (d,), None)]
    for i, kind in enumerate(cfg["layer_types"]):
        layer = f"layer_{i}"
        if kind == "mamba":
            specs += [
                ((layer, "ssm", "A_log"), (m["h"],), A_LOG_STD),
                ((layer, "ssm", "D"), (m["h"],), None),
                ((layer, "ssm", "conv_bias"), (m["conv_dim"],),
                 m["taps"] ** -0.5),
                ((layer, "ssm", "conv_kernel"), (m["taps"], m["conv_dim"]),
                 m["taps"] ** -0.5),
                ((layer, "ssm", "dt_bias"), (m["h"],), DT_BIAS_STD),
                ((layer, "ssm", "norm"), (m["inner"],), None),
                ((layer, "ssm", "wdt"), (d, m["h"]), d ** -0.5),
                ((layer, "ssm", "wo", "kernel"), (m["inner"], d),
                 m["inner"] ** -0.5),
                ((layer, "ssm", "wxbc", "kernel"), (d, m["conv_dim"]),
                 d ** -0.5),
                ((layer, "ssm", "wz", "kernel"), (d, m["inner"]), d ** -0.5),
                ((layer, "ssm_norm", "scale"), (d,), None)]
        elif kind == "attention":
            specs += [
                ((layer, "attn", "wq", "kernel"), (d, q),
                 QK_SPREAD * d ** -0.5),
                ((layer, "attn", "wk", "kernel"), (d, kv),
                 QK_SPREAD * d ** -0.5),
                ((layer, "attn", "wv", "kernel"), (d, kv), d ** -0.5),
                ((layer, "attn", "wo", "kernel"), (q, d), q ** -0.5),
                ((layer, "attn_norm", "scale"), (d,), None)]
        else:
            raise ValueError(f"layer {i}'s type {kind!r} is neither mamba "
                             f"nor attention")
        specs += [
            ((layer, "mlp", "gate", "kernel"), (d, f), d ** -0.5),
            ((layer, "mlp", "up", "kernel"), (d, f), d ** -0.5),
            ((layer, "mlp", "down", "kernel"), (f, d), f ** -0.5),
            ((layer, "mlp_norm", "scale"), (d,), None)]
    return sorted(specs)


def attend(cfg, p, n):
    s, hd = n.shape[0], _counts.head_dim(cfg)
    q, k, v = ((n @ p[w]["kernel"]).reshape(s, -1, hd)
               for w in ("wq", "wk", "wv"))
    q = q * (cfg["attention_multiplier"] * hd ** 0.5)
    return attention(q, k, v).reshape(s, -1) @ p["wo"]["kernel"]


def _layer(cfg, p, x, i):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    if cfg["layer_types"][i] == "mamba":
        x = x + r * _nemotron.mamba(_counts.as_nemotron(cfg), p["ssm"],
                                    rmsnorm(x, p["ssm_norm"]["scale"], eps))
    else:
        x = x + r * attend(cfg, p["attn"], rmsnorm(x, p["attn_norm"]["scale"],
                                                   eps))
    mlp = p["mlp"]

    def swiglu(mb):
        return (jax.nn.silu(mb @ mlp["gate"]["kernel"])
                * (mb @ mlp["up"]["kernel"])) @ mlp["down"]["kernel"]

    return x + r * by_position_blocks(
        swiglu, rmsnorm(x, p["mlp_norm"]["scale"], eps))


def hidden(cfg, params, tokens):
    """The final-norm hidden states [S, D] of one row of ids [S]."""
    x = cfg["embedding_multiplier"] * params["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda p, y, i=i: _layer(cfg, p, y, i))(
            params[f"layer_{i}"], x)
    return rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def loss_fn(cfg: dict, params: dict, tokens: jax.Array) -> jax.Array:
    """Mean next-token cross entropy of a batch of ids [B, S], the head the
    table's transpose over ``logits_scaling``."""
    head = params["embedding"].T / cfg["logits_scaling"]
    return mean_over_rows(
        lambda row: next_token_nll_sum(hidden(cfg, params, row), head, row),
        tokens)
