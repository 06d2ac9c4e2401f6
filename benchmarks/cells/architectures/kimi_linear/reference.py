"""Kimi Linear in plain float32 (as the configuration's source publishes it,
each inference listed under ``assumed`` in the configuration file): its
leaves and its loss, for ``reference.follow``. One chip's share: the experts
and vocabulary rows the configuration says are held here.

Every layer is ``h = x + mixer(rmsnorm x)``, ``x' = h + ff(rmsnorm h)``, the
mixer by ``linear_attn_config`` (1-based layer numbers):

- a KDA layer (``kda_layers``). ``q, k, v = silu(conv(n·W))`` each
  (depthwise, causal, no bias, zeros before the row's start); ``q ← q/‖q‖ ·
  K^−½``, ``k ← k/‖k‖`` a head; ``g = −exp(A_log_h) · softplus(n·W_fa·W_fb +
  dt_bias)`` a channel; ``β = sigmoid(n·W_b)``; per head ``S_t = (I − β_t
  k_t k_tᵀ) Diag(e^{g_t}) S_{t−1} + β_t k_t v_tᵀ`` from ``S = 0``, ``o_t =
  S_tᵀ q_t``: **the recurrence itself, token by token** (a ``lax.scan``
  over the row, each block of tokens recomputed in the backward pass from
  the state it entered with; no chunked algebra); ``o ← W_on ⊙ rms(o) ⊙
  sigmoid(n·W_ga·W_gb)`` a head; ``mixer = o·W_o``.
- an MLA layer (``full_attn_layers``), without RoPE (``mla_use_nope``):
  ``q = n·W_q`` ``[S, H, 128 + 64]``; ``[c | k_r] = n·W_kv_a``, ``c ←
  rmsnorm(c)``; ``[k_n | v] = c·W_kv_b`` a head; ``k = [k_n | k_r]``; causal
  softmax of ``q·kᵀ / √192`` over values 128 wide, a head and a block of
  queries at a time; ``mixer = concat_h(a_h)·W_o``.

The feed-forward: the dense SwiGLU in the first ``first_k_dense_replace``
layers; after them the sparse one, ``s = sigmoid(m·W_r)`` over ALL of the
model's experts, the k largest chosen (one expert group: plain top-k; the
correction bias is zero), ``w = routed_scaling_factor · s_chosen / (Σ
s_chosen + 1e-20)``, ``ff = S(m) + Σ w_e·E_e(m)`` over the chosen experts
THAT ARE HELD HERE, as a dense masked sum, ``S`` the shared expert,
unweighted. A final rmsnorm, an untied head, the mean next-token cross
entropy. No auxiliary loss, no position embedding. Imports nothing of the
program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import (ATTN_Q_BLOCK, POS_BLOCK, blocks, by_position_blocks,
                       mean_over_rows, next_token_nll_sum, rmsnorm)

# Random weights that behave as a trained model's (the configuration file's
# ``assumed.weights`` has the readings): unit-variance embedding rows and
# router logits with a spread of 3, as the sparse architectures before this
# one found; latent attention's q at twice a plain projection's std, so that
# its scores spread by about 2; and decays whose channels differ in how long
# they remember. The generator draws normal(0, std) or ones and nothing else,
# so the published ranges (A uniform in [1, 16], Δ log-uniform in [0.001,
# 0.1]) cannot be drawn.
ROUTER_SPREAD = 3.0
EMBEDDING_STD = 1.0
MLA_Q_SPREAD = 2.0
A_LOG_STD = 0.5
DT_BIAS_STD = 3.0
CONV_STD = 0.5
L2_EPS = 1e-6
SCAN_BLOCK = 64         # tokens of the recurrence recomputed at a time
HEAD_GROUP = 4          # KDA heads taken (and recomputed) at once


def layer_kinds(cfg: dict) -> list:
    """Per layer (0-based): ``"kda"`` or ``"mla"``, from the 1-based lists
    of ``linear_attn_config``."""
    lin = cfg["linear_attn_config"]
    kinds = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        if i in lin["kda_layers"]:
            kinds.append("kda")
        elif i in lin["full_attn_layers"]:
            kinds.append("mla")
        else:
            raise ValueError(f"layer {i} is in neither list of "
                             f"linear_attn_config")
    return kinds


def is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


def leaf_specs(cfg: dict) -> list:
    """``[(path, shape, std)]`` for every parameter leaf, in the sorted order
    of the program's parameter tree. ``std`` is None for a leaf of ones (a
    norm's scale). Kernels are [in, out]; routed experts are stacked [held,
    in, out]."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    lin = cfg["linear_attn_config"]
    h, kd = lin["num_heads"], lin["head_dim"]
    inner, taps = h * kd, lin["short_conv_kernel_size"]
    heads = cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    fs = cfg["num_shared_experts"] * f
    experts = cfg["published"]["num_experts"]
    specs = [(("embedding",), (v, d), EMBEDDING_STD),
             (("final_norm", "scale"), (d,), None),
             (("lm_head", "kernel"), (d, v), d ** -0.5)]

    def gated(prefix, width):
        return [((*prefix, "gate", "kernel"), (d, width), d ** -0.5),
                ((*prefix, "up", "kernel"), (d, width), d ** -0.5),
                ((*prefix, "down", "kernel"), (width, d), width ** -0.5)]

    for i, kind in enumerate(layer_kinds(cfg)):
        layer = f"layer_{i}"
        if kind == "kda":
            mix = (layer, "kda")
            specs += [
                ((layer, "kda_norm", "scale"), (d,), None),
                ((*mix, "A_log"), (h,), A_LOG_STD),
                ((*mix, "b"), (d, h), d ** -0.5),
                ((*mix, "dt_bias"), (inner,), DT_BIAS_STD),
                ((*mix, "f_a"), (d, kd), d ** -0.5),
                ((*mix, "f_b"), (kd, inner), kd ** -0.5),
                ((*mix, "g_a"), (d, kd), d ** -0.5),
                ((*mix, "g_b"), (kd, inner), kd ** -0.5),
                ((*mix, "o_norm"), (kd,), None),
                ((*mix, "wo", "kernel"), (inner, d), inner ** -0.5)]
            specs += [((*mix, w, "kernel"), (d, inner), d ** -0.5)
                      for w in ("wq", "wk", "wv")]
            specs += [((*mix, c), (taps, inner), CONV_STD)
                      for c in ("q_conv", "k_conv", "v_conv")]
        else:
            mix = (layer, "mla")
            specs += [
                ((layer, "attn_norm", "scale"), (d,), None),
                ((*mix, "kv_norm"), (rank,), None),
                ((*mix, "wq", "kernel"), (d, heads * qk),
                 MLA_Q_SPREAD * d ** -0.5),
                ((*mix, "wkv_a", "kernel"),
                 (d, rank + cfg["qk_rope_head_dim"]), d ** -0.5),
                ((*mix, "wkv_b", "kernel"),
                 (rank, heads * (cfg["qk_nope_head_dim"] + dv)),
                 rank ** -0.5),
                ((*mix, "wo", "kernel"), (heads * dv, d),
                 (heads * dv) ** -0.5)]
        specs.append(((layer, "mlp_norm", "scale"), (d,), None))
        if is_dense(cfg, i):
            specs += gated((layer, "mlp"), cfg["intermediate_size"])
            continue
        specs += [
            ((layer, "moe", "router"), (d, experts),
             ROUTER_SPREAD * d ** -0.5),
            ((layer, "moe", "gate"), (held, d, f), d ** -0.5),
            ((layer, "moe", "up"), (held, d, f), d ** -0.5),
            ((layer, "moe", "down"), (held, f, d), f ** -0.5),
        ] + gated((layer, "moe", "shared"), fs)
    return sorted(specs)


def recurrence(q, k, v, g, beta):
    """``o [S, H, V]`` of the gated delta rule for one row: ``q``, ``k``,
    ``g [S, H, K]``, ``v [S, H, V]``, ``beta [S, H]``; token by token, each
    block of ``SCAN_BLOCK`` tokens recomputed in the backward pass."""
    s, h, kd = q.shape

    def token(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[:, :, None] * state               # [H, K, V]
        k_s = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + (b_t[:, None] * k_t)[:, :, None] \
            * (v_t - k_s)[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def block(state, rows):
        return jax.lax.scan(token, state, rows)

    n = blocks(s, SCAN_BLOCK)
    _, o = jax.lax.scan(
        block, jnp.zeros((h, kd, v.shape[2]), jnp.float32),
        tuple(m.reshape(s // n, n, *m.shape[1:]) for m in (q, k, v, g, beta)))
    return o.reshape(s, h, v.shape[2])


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + L2_EPS)


def kda(cfg, p, n):
    """The KDA mixer's part for normed positions n [S, D]. Heads are
    independent from their columns of W_q, W_k, W_v, W_fb and W_gb to their
    rows of W_o, so the part is a sum over groups of ``HEAD_GROUP`` heads,
    each group recomputed in the backward pass: the same sums, a group's
    ``[S, G·K]`` tensors at a time."""
    lin = cfg["linear_attn_config"]
    h, kd, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    s, d = n.shape
    g = min(h, HEAD_GROUP)
    ng, cols = h // g, g * kd

    def by_group(w, axis):
        """w's columns (or entries) of each group, the group leading."""
        w = jnp.moveaxis(w, axis, 0)
        return jnp.moveaxis(w.reshape(ng, cols, *w.shape[1:]), 1, axis + 1)

    fa, ga = n @ p["f_a"], n @ p["g_a"]
    beta = jax.nn.sigmoid(n @ p["b"])                      # [S, H]
    groups = (by_group(p["wq"]["kernel"], 1), by_group(p["wk"]["kernel"], 1),
              by_group(p["wv"]["kernel"], 1), by_group(p["q_conv"], 1),
              by_group(p["k_conv"], 1), by_group(p["v_conv"], 1),
              by_group(p["f_b"], 1), by_group(p["dt_bias"], 0),
              by_group(p["g_b"], 1), by_group(p["wo"]["kernel"], 0),
              p["A_log"].reshape(ng, g), beta.reshape(s, ng, g).swapaxes(0, 1))

    def group(args):
        wq, wk, wv, cq, ck, cv, fb, dtb, gb, wo, a_log, bg = args

        def conv(w, taps_w):
            u = jnp.pad(n @ w, ((taps - 1, 0), (0, 0)))
            return jax.nn.silu(sum(u[t:t + s] * taps_w[t]
                                   for t in range(taps))).reshape(s, g, kd)

        q = _l2(conv(wq, cq)) * kd ** -0.5
        k = _l2(conv(wk, ck))
        v = conv(wv, cv)
        decay = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            fa @ fb + dtb).reshape(s, g, kd)
        o = recurrence(q, k, v, decay, bg)
        var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        o = o * jax.lax.rsqrt(var + cfg["rms_norm_eps"]) * p["o_norm"] \
            * jax.nn.sigmoid(ga @ gb).reshape(s, g, kd)
        return o.reshape(s, cols) @ wo

    out, _ = jax.lax.scan(
        lambda acc, args: (acc + jax.checkpoint(group)(args), None),
        jnp.zeros((s, d), jnp.float32), groups)
    return out


def attention(q, k, v):
    """Causal softmax attention at ``q``'s width: q, k [S, H, D], v [S, H,
    Dv] -> [S, H, Dv]; one head and one block of queries at a time."""
    s, h, d = q.shape
    bq = blocks(s, ATTN_Q_BLOCK)
    nq = s // bq
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def one(idx):
        head, blk = idx // nq, idx % nq
        qb = jax.lax.dynamic_slice(q, (blk * bq, head, 0), (bq, 1, d))[:, 0]
        kh = jax.lax.dynamic_slice(k, (0, head, 0), (s, 1, d))[:, 0]
        vh = jax.lax.dynamic_slice(v, (0, head, 0), (s, 1, v.shape[2]))[:, 0]
        scores = qb @ kh.T * d ** -0.5
        q_pos = blk * bq + jnp.arange(bq)
        scores = jnp.where(key_pos[None, :] <= q_pos[:, None], scores,
                           -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ vh            # [bq, Dv]

    out = jax.lax.map(one, jnp.arange(h * nq))       # [h*nq, bq, Dv]
    return out.reshape(h, nq, bq, -1).transpose(1, 2, 0, 3).reshape(
        s, h, v.shape[2])


def mla(cfg, p, n):
    """Latent attention's part (no RoPE) for normed positions n [S, D]."""
    s = n.shape[0]
    h, nope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"]
    rank = cfg["kv_lora_rank"]
    q = (n @ p["wq"]["kernel"]).reshape(s, h, -1)
    latent = n @ p["wkv_a"]["kernel"]
    c = rmsnorm(latent[:, :rank], p["kv_norm"], cfg["rms_norm_eps"])
    kv = (c @ p["wkv_b"]["kernel"]).reshape(s, h, -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        latent[:, None, rank:], (s, h, cfg["qk_rope_head_dim"]))], axis=-1)
    o = attention(q, k, kv[..., nope:])
    return o.reshape(s, -1) @ p["wo"]["kernel"]


def gated_mlp(p, m):
    """``Wdown·(silu(Wgate·m) * Wup·m)``: the dense MLP, the shared expert."""
    return (jax.nn.silu(m @ p["gate"]["kernel"]) * (m @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def route(cfg, scores):
    """(the chosen experts [S, k], their weights) from sigmoid scores [S,
    E]: the k largest, each weighted by its own score over the chosen's
    sum, times the factor."""
    _, idx = jax.lax.top_k(scores, cfg["num_experts_per_token"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, cfg["routed_scaling_factor"] * top / (
        jnp.sum(top, axis=-1, keepdims=True) + 1e-20)


def experts_held(cfg, p, scores, m):
    """The held routed experts' part for positions m [S, D] with sigmoid
    scores [S, E]: a dense masked sum."""
    first = cfg.get("share", {}).get("first_expert_held", 0)
    idx, weights = route(cfg, scores)

    def block(args):
        mb, ib, wb = args

        def one(acc, expert):
            gate, up, down, e = expert
            chosen = jnp.sum(jnp.where(ib == e, wb, 0.0), axis=-1)
            y = (jax.nn.silu(mb @ gate) * (mb @ up)) @ down
            return acc + chosen[:, None] * y, None

        out, _ = jax.lax.scan(
            jax.checkpoint(one), jnp.zeros_like(mb),
            (p["gate"], p["up"], p["down"],
             first + jnp.arange(p["gate"].shape[0])))
        return out

    s = m.shape[0]
    b = blocks(s, POS_BLOCK)
    out = jax.lax.map(jax.checkpoint(block), tuple(
        a.reshape(s // b, b, *a.shape[1:]) for a in (m, idx, weights)))
    return out.reshape(s, *out.shape[2:])


def sparse(cfg, p, m):
    shared = by_position_blocks(lambda mb: gated_mlp(p["shared"], mb), m)
    return shared + experts_held(cfg, p, jax.nn.sigmoid(m @ p["router"]), m)


def _layer(cfg, p, x, i):
    eps = cfg["rms_norm_eps"]
    if layer_kinds(cfg)[i] == "kda":
        h = x + kda(cfg, p["kda"], rmsnorm(x, p["kda_norm"]["scale"], eps))
    else:
        h = x + mla(cfg, p["mla"], rmsnorm(x, p["attn_norm"]["scale"], eps))
    m = rmsnorm(h, p["mlp_norm"]["scale"], eps)
    if is_dense(cfg, i):
        return h + by_position_blocks(lambda mb: gated_mlp(p["mlp"], mb), m)
    return h + sparse(cfg, p["moe"], m)


def hidden(cfg, params, tokens):
    """The final-norm hidden states [S, D] of one row of ids [S]."""
    x = params["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(lambda p, y, i=i: _layer(cfg, p, y, i))(
            params[f"layer_{i}"], x)
    return rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def loss_fn(cfg: dict, params: dict, tokens: jax.Array) -> jax.Array:
    """Mean next-token cross entropy of a batch of ids [B, S]."""
    return mean_over_rows(
        lambda row: next_token_nll_sum(
            hidden(cfg, params, row), params["lm_head"]["kernel"], row),
        tokens)
