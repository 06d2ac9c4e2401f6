"""Nemotron-H in plain float32 (as the configuration's source publishes it,
each inference listed under ``assumed`` in the configuration file): its
leaves and its loss, for ``reference.follow``. One chip's share: the experts
and vocabulary rows the configuration says are held here.

Every layer is ONE part, ``out = x + part(rmsnorm(x))``, the part by the
layer's letter in ``hybrid_override_pattern``:

- ``M``, the Mamba-2 mixer. ``z, u, dt = n·W_z, n·W_xbc, n·W_dt`` with ``u =
  [x | B | C]``; ``u ← silu(b_c + Σ_k w_k ⊙ u_{t−K+1+k})`` (depthwise, causal,
  zeros before the row's start); ``Δ = softplus(dt + dt_bias)`` a head,
  ``a = exp(Δ·A)``, ``A = −exp(A_log)``; per head ``h`` of group ``g``:
  ``S_t = a_t S_{t−1} + Δ_t B_{g,t} x_tᵀ`` from ``S = 0``, ``y_t = S_tᵀ
  C_{g,t} + D_h x_t``, **the recurrence itself, token by token** (a
  ``lax.scan`` over the row, recomputed in blocks of tokens in the backward
  pass; no chunked algebra); ``y ← W_norm ⊙ grouprms(y ⊙ silu(z))``, the RMS
  over each group's columns; ``part = y·W_o``.
- ``*``, attention: ``q, k, v = n·W_q, n·W_k, n·W_v``, grouped-query causal
  softmax at ``1/√head_dim``, NO position embedding, ``part = concat_h(a_h)
  ·W_o``.
- ``E``, the sparse feed-forward: ``s = sigmoid(n·W_r)`` over ALL of the
  model's experts, the k largest chosen (the correction bias is zero), ``w =
  routed_scaling_factor · s_chosen / (Σ s_chosen + 1e-20)``; ``part = S(n) +
  Σ w_e·E_e(n)`` over the chosen experts THAT ARE HELD HERE, as a dense
  masked sum, ``E_e(n) = W_down,e·relu(W_up,e·n)²`` and ``S`` the same
  function at the shared width, unweighted.

A final rmsnorm, an untied head, the mean next-token cross entropy. No
auxiliary loss. Imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import (POS_BLOCK, attention, blocks, by_position_blocks,
                       mean_over_rows, next_token_nll_sum, rmsnorm)

# Random weights that behave as a trained model's (the configuration file's
# ``assumed.weights`` has the reasons): unit-variance embedding rows and
# router logits with a spread of 3, as the sparse architectures before this
# one found; and a mixer whose heads differ in how long they remember. The
# generator draws normal(0, std) or ones and nothing else, so the published
# ranges (A uniform in [1, 16], Δ log-uniform in [0.001, 0.1]) cannot be
# drawn; with A_log and dt_bias of std 3, |A| spans e^±3 and a head's usual
# step softplus(±3), so that a_t spreads over (0, 1) and a fifth of the heads
# keep a^128 > 0.1: a memory that outlasts a chunk of the scan. (Narrower
# draws, std 2.5 with the conv's taps at 1/√12, were tried on the chip and
# left: PERF.md section 2.)
ROUTER_SPREAD = 3.0
EMBEDDING_STD = 1.0
A_LOG_STD = 3.0
DT_BIAS_STD = 3.0
SCAN_BLOCK = 64         # tokens of the recurrence recomputed at a time


def out_scale(cfg: dict) -> float:
    """The factor on every part's output projection: the published
    ``rescale_prenorm_residual`` divides a residual branch's by the root of
    the model's depth, so that 52 parts added to the stream leave it of the
    embedding's order."""
    return cfg["published"]["num_hidden_layers"] ** -0.5 \
        if cfg.get("rescale_prenorm_residual") else 1.0


def sizes(cfg: dict) -> dict:
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {"h": h, "p": p, "g": g, "n": n, "inner": h * p,
            "conv_dim": h * p + 2 * g * n, "taps": cfg["conv_kernel"]}


def leaf_specs(cfg: dict) -> list:
    """``[(path, shape, std)]`` for every parameter leaf, in the sorted order
    of the program's parameter tree. ``std`` is None for a leaf of ones (a
    norm's scale, the mixer's ``D``). Kernels are [in, out]; routed experts
    are stacked [held, in, out]."""
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    experts = cfg["published"]["n_routed_experts"]
    m, out = sizes(cfg), out_scale(cfg)
    specs = [(("embedding",), (v, d),
              cfg.get("initializer_range", EMBEDDING_STD)),
             (("final_norm", "scale"), (d,), None),
             (("lm_head", "kernel"), (d, v), d ** -0.5)]
    for i, letter in enumerate(cfg["hybrid_override_pattern"]):
        layer = f"layer_{i}"
        if letter == "M":
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
                 out * m["inner"] ** -0.5),
                ((layer, "ssm", "wxbc", "kernel"), (d, m["conv_dim"]),
                 d ** -0.5),
                ((layer, "ssm", "wz", "kernel"), (d, m["inner"]), d ** -0.5),
                ((layer, "ssm_norm", "scale"), (d,), None)]
        elif letter == "*":
            specs += [
                ((layer, "attn", "wq", "kernel"), (d, q), d ** -0.5),
                ((layer, "attn", "wk", "kernel"), (d, kv), d ** -0.5),
                ((layer, "attn", "wv", "kernel"), (d, kv), d ** -0.5),
                ((layer, "attn", "wo", "kernel"), (q, d), out * q ** -0.5),
                ((layer, "attn_norm", "scale"), (d,), None)]
        elif letter == "E":
            specs += [
                ((layer, "mlp_norm", "scale"), (d,), None),
                ((layer, "moe", "router"), (d, experts),
                 ROUTER_SPREAD * d ** -0.5),
                ((layer, "moe", "up"), (held, d, f), d ** -0.5),
                ((layer, "moe", "down"), (held, f, d), out * f ** -0.5),
                ((layer, "moe", "shared", "up", "kernel"), (d, fs),
                 d ** -0.5),
                ((layer, "moe", "shared", "down", "kernel"), (fs, d),
                 out * fs ** -0.5)]
        else:
            raise ValueError(f"layer {i}'s letter {letter!r} is none of "
                             f"M, *, E")
    return sorted(specs)


def recurrence(x, dt, a, b, c, d):
    """``y [S, H, P]`` of ``S_t = a_t S_{t−1} + Δ_t B_t x_tᵀ``, ``y_t = S_tᵀ
    C_t + D x_t`` for one row: ``x [S, H, P]``, ``dt [S, H]``, ``a [H]``,
    ``b``, ``c [S, G, N]``, ``d [H]``; token by token."""
    s, h, p = x.shape
    per = h // b.shape[1]

    def token(state, at):
        x_t, dt_t, b_t, c_t = at
        b_t, c_t = (jnp.repeat(m, per, axis=0) for m in (b_t, c_t))  # [H, N]
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * b_t)[:, :, None] * x_t[:, None, :]
        return state, jnp.sum(state * c_t[:, :, None], axis=1) \
            + d[:, None] * x_t

    @jax.checkpoint
    def block(state, rows):
        return jax.lax.scan(token, state, rows)

    n = blocks(s, SCAN_BLOCK)
    _, y = jax.lax.scan(
        block, jnp.zeros((h, b.shape[2], p), jnp.float32),
        tuple(m.reshape(s // n, n, *m.shape[1:]) for m in (x, dt, b, c)))
    return y.reshape(s, h, p)


def mamba(cfg, p, n):
    """The mixer's part for normed positions n [S, D]."""
    m = sizes(cfg)
    s = n.shape[0]
    z, u = n @ p["wz"]["kernel"], n @ p["wxbc"]["kernel"]
    padded = jnp.pad(u, ((m["taps"] - 1, 0), (0, 0)))
    u = jax.nn.silu(p["conv_bias"] + sum(
        padded[k:k + s] * p["conv_kernel"][k] for k in range(m["taps"])))
    x, b, c = jnp.split(u, (m["inner"], m["inner"] + m["g"] * m["n"]), axis=1)
    dt = jax.nn.softplus(n @ p["wdt"] + p["dt_bias"])
    y = recurrence(x.reshape(s, m["h"], m["p"]), dt, -jnp.exp(p["A_log"]),
                   b.reshape(s, m["g"], m["n"]), c.reshape(s, m["g"], m["n"]),
                   p["D"])
    gated = (y.reshape(s, m["inner"]) * jax.nn.silu(z)).reshape(
        s, m["g"], -1)
    var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
    y = (gated * jax.lax.rsqrt(var + cfg["layer_norm_epsilon"])).reshape(
        s, m["inner"]) * p["norm"]
    return y @ p["wo"]["kernel"]


def relu2_mlp(up, down, n):
    """``W_down·relu(W_up·n)²``: the shared expert, one routed expert."""
    return jnp.square(jax.nn.relu(n @ up)) @ down


def route(cfg, scores):
    """(the chosen experts [S, k], their weights) from sigmoid scores [S,
    E]: the k largest (``n_group = topk_group = 1`` is no grouping; the
    correction bias is zero), each weighted by its own score over the
    chosen's sum, times the factor."""
    _, idx = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, cfg["routed_scaling_factor"] * top / (
        jnp.sum(top, axis=-1, keepdims=True) + 1e-20)


def experts_held(cfg, p, scores, n):
    """The held routed experts' part for positions n [S, D] with sigmoid
    scores [S, E]: a dense masked sum."""
    first = cfg.get("share", {}).get("first_expert_held", 0)
    idx, weights = route(cfg, scores)

    def block(args):
        nb, ib, wb = args

        def one(acc, expert):
            up, down, e = expert
            chosen = jnp.sum(jnp.where(ib == e, wb, 0.0), axis=-1)
            return acc + chosen[:, None] * relu2_mlp(up, down, nb), None

        out, _ = jax.lax.scan(
            jax.checkpoint(one), jnp.zeros_like(nb),
            (p["up"], p["down"], first + jnp.arange(p["up"].shape[0])))
        return out

    s = n.shape[0]
    b = blocks(s, POS_BLOCK)
    out = jax.lax.map(jax.checkpoint(block), tuple(
        a.reshape(s // b, b, *a.shape[1:]) for a in (n, idx, weights)))
    return out.reshape(s, *out.shape[2:])


def sparse(cfg, p, n):
    shared = by_position_blocks(
        lambda nb: relu2_mlp(p["shared"]["up"]["kernel"],
                             p["shared"]["down"]["kernel"], nb), n)
    return shared + experts_held(cfg, p, jax.nn.sigmoid(n @ p["router"]), n)


def attend(cfg, p, n):
    s, hd = n.shape[0], cfg["head_dim"]
    q, k, v = ((n @ p[w]["kernel"]).reshape(s, -1, hd)
               for w in ("wq", "wk", "wv"))
    return attention(q, k, v).reshape(s, -1) @ p["wo"]["kernel"]


def _layer(cfg, p, x, i):
    eps = cfg["layer_norm_epsilon"]
    letter = cfg["hybrid_override_pattern"][i]
    if letter == "M":
        return x + mamba(cfg, p["ssm"], rmsnorm(x, p["ssm_norm"]["scale"],
                                                eps))
    if letter == "*":
        return x + attend(cfg, p["attn"], rmsnorm(x, p["attn_norm"]["scale"],
                                                  eps))
    return x + sparse(cfg, p["moe"], rmsnorm(x, p["mlp_norm"]["scale"], eps))


def hidden(cfg, params, tokens):
    """The final-norm hidden states [S, D] of one row of ids [S]."""
    x = params["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda p, y, i=i: _layer(cfg, p, y, i))(
            params[f"layer_{i}"], x)
    return rmsnorm(x, params["final_norm"]["scale"],
                   cfg["layer_norm_epsilon"])


def loss_fn(cfg: dict, params: dict, tokens: jax.Array) -> jax.Array:
    """Mean next-token cross entropy of a batch of ids [B, S]."""
    return mean_over_rows(
        lambda row: next_token_nll_sum(
            hidden(cfg, params, row), params["lm_head"]["kernel"], row),
        tokens)
