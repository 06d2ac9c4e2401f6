"""Laguna in plain float32 (as the configuration's source publishes it, each
inference listed under ``assumed`` in the configuration file): its leaves and
its loss, for ``reference.follow``. One chip's share: the heads, experts and
vocabulary rows the configuration says are held here.

Per layer ``i`` of kind full or window, with ``n = rmsnorm(x)``: ``q = n·Wq``
at the layer's own count of heads, ``k = n·Wk``, ``v = n·Wv``, ``g = n·Wg``
(one scalar a head); RoPE by the layer's kind (window: θ 10,000 over the
whole head; full: the first half of a head's columns alone, YaRN's blended
frequencies, cos and sin times ``attention_factor``); grouped-query causal
softmax attention, a window layer's query i seeing keys i − w < j ≤ i;
``h = x + Wo·concat_h(sigmoid(g_h)·a_h)``; ``m = rmsnorm(h)``. Layer 0 is
dense: ``out = h + Wdown·(silu(Wgate·m) * Wup·m)``. The others are sparse: the
router's logits ``r = m·Wr`` over ALL of the model's experts, the k largest
chosen and weighted by ``moe_routed_scaling_factor`` times the softmax of
those k; ``out = h + S(m) + Σ w_e·E_e(m)`` over the chosen experts THAT ARE
HELD HERE, as a dense masked sum (every held expert over every position,
weight zero where it was not chosen), ``S`` the shared expert, unweighted.
A final rmsnorm, an untied head, the mean next-token cross entropy. No
auxiliary loss. Imports nothing of the program.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import (ATTN_Q_BLOCK, POS_BLOCK, blocks, by_position_blocks,
                       mean_over_rows, next_token_nll_sum, rmsnorm)

# The router's and the embedding's std, as the sparse architecture before
# this one found random weights need them (its reference.py has the
# readings): logits with a spread of 3 over unit-variance normed inputs, so
# that a last choice swapped by bf16 rounding weighs little; embedding rows
# of unit variance, so that random attention's near-uniform mean of values is
# a few hundredths of the stream and no router reads it as a bias.
ROUTER_SPREAD = 3.0
EMBEDDING_STD = 1.0


def leaf_specs(cfg: dict) -> list:
    """``[(path, shape, std)]`` for every parameter leaf, in the sorted order
    of the program's parameter tree. ``std`` is None for a norm scale (ones).
    Kernels are [in, out]; routed experts are stacked [held, in, out]."""
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * hd
    f, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    fs, fd = cfg["shared_expert_intermediate_size"], cfg["intermediate_size"]
    experts = cfg["published"]["num_experts"]
    specs = [(("embedding",), (v, d),
              cfg.get("initializer_range", EMBEDDING_STD)),
             (("final_norm", "scale"), (d,), None)]

    def gated(prefix, width):
        return [((*prefix, "gate", "kernel"), (d, width), d ** -0.5),
                ((*prefix, "up", "kernel"), (d, width), d ** -0.5),
                ((*prefix, "down", "kernel"), (width, d), width ** -0.5)]

    for i in range(cfg["num_hidden_layers"]):
        layer = f"layer_{i}"
        heads = cfg["num_attention_heads_per_layer"][i]
        q = heads * hd
        specs += [
            ((layer, "attn", "wq", "kernel"), (d, q), d ** -0.5),
            ((layer, "attn", "wk", "kernel"), (d, kv), d ** -0.5),
            ((layer, "attn", "wv", "kernel"), (d, kv), d ** -0.5),
            ((layer, "attn", "wg", "kernel"), (d, heads), d ** -0.5),
            ((layer, "attn", "wo", "kernel"), (q, d), q ** -0.5),
            ((layer, "attn_norm", "scale"), (d,), None),
            ((layer, "mlp_norm", "scale"), (d,), None),
        ]
        if cfg["mlp_layer_types"][i] == "dense":
            specs += gated((layer, "mlp"), fd)
            continue
        specs += [
            ((layer, "moe", "router"), (d, experts),
             ROUTER_SPREAD * d ** -0.5),
            ((layer, "moe", "gate"), (held, d, f), d ** -0.5),
            ((layer, "moe", "up"), (held, d, f), d ** -0.5),
            ((layer, "moe", "down"), (held, f, d), f ** -0.5),
        ] + gated((layer, "moe", "shared"), fs)
    specs.append((("lm_head", "kernel"), (d, v), d ** -0.5))
    return sorted(specs)


def rope_table(rope: dict, head_dim: int) -> tuple:
    """(inverse frequencies [rot/2] of the columns one kind of layer rotates,
    the factor on cos and sin) from its entry of ``rope_parameters``:
    ``rope_type`` default, or yarn as Hugging Face's
    ``_compute_yarn_parameters`` computes it."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1))
    base = rope["rope_theta"]
    inv_freq = 1.0 / base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope["rope_type"] == "default":
        return inv_freq.astype(np.float32), 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r} is not written "
                         f"down here")
    factor, original = rope["factor"], rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        # the pair whose frequency turns ``rotations`` times in the original
        # context
        return rot * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope.get("beta_fast") or 32)), 0)
    high = min(math.ceil(correction_dim(rope.get("beta_slow") or 1)), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    extrapolation = 1 - ramp      # 1: the original frequency is kept
    blended = inv_freq / factor * (1 - extrapolation) \
        + inv_freq * extrapolation
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return blended.astype(np.float32), float(scale)


def rope(x, inv_freq, scale):
    """x [S, H, D]; positions 0..S-1; half-split rotation within the first
    ``2 * len(inv_freq)`` columns, the others passed through."""
    s = x.shape[0]
    rot = 2 * inv_freq.shape[0]
    ang = jnp.arange(s, dtype=jnp.float32)[:, None, None] * inv_freq
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x1, x2 = jnp.split(x[..., :rot], 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rot:]], -1)


def attention(q, k, v, window=None):
    """Causal grouped-query attention, query i seeing keys i − w < j ≤ i
    under a window. q [S, H, D], k/v [S, Hk, D] -> [S, H, D]. One kv head and
    one block of queries at a time, against every key: the mask alone says
    what a query sees."""
    s, h, d = q.shape
    hk = k.shape[1]
    g = h // hk
    bq = blocks(s, ATTN_Q_BLOCK)
    nq = s // bq
    qg = q.reshape(s, hk, g, d)
    scale = d ** -0.5
    key_pos = jnp.arange(s)

    @jax.checkpoint
    def one(idx):
        head, blk = idx // nq, idx % nq
        qb = jax.lax.dynamic_slice(qg, (blk * bq, head, 0, 0),
                                   (bq, 1, g, d))[:, 0]        # [bq, g, d]
        kh = jax.lax.dynamic_slice(k, (0, head, 0), (s, 1, d))[:, 0]
        vh = jax.lax.dynamic_slice(v, (0, head, 0), (s, 1, d))[:, 0]
        scores = jnp.einsum("qgd,kd->gqk", qb, kh) * scale
        q_pos = blk * bq + jnp.arange(bq)
        mask = key_pos[None, :] <= q_pos[:, None]
        if window is not None:
            mask &= key_pos[None, :] > q_pos[:, None] - window
        scores = jnp.where(mask[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("gqk,kd->qgd", p, vh)                # [bq, g, d]

    out = jax.lax.map(one, jnp.arange(hk * nq))      # [hk*nq, bq, g, d]
    out = out.reshape(hk, nq, bq, g, d).transpose(1, 2, 0, 3, 4)
    return out.reshape(s, h, d)


def gated_mlp(p, m):
    """``Wdown·(silu(Wgate·m) * Wup·m)``: the dense MLP, the shared expert."""
    return (jax.nn.silu(m @ p["gate"]["kernel"]) * (m @ p["up"]["kernel"])) \
        @ p["down"]["kernel"]


def experts_held(cfg, p, logits, m):
    """The held routed experts' part of the layer for positions m [S, D] with
    router logits [S, E]: a dense masked sum."""
    first = cfg.get("share", {}).get("first_expert_held", 0)
    top, idx = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    weights = cfg["moe_routed_scaling_factor"] \
        * jax.nn.softmax(top, axis=-1)                          # [S, k]

    def block(args):
        mb, ib, wb = args

        def one(acc, expert):
            gate, up, down, e = expert
            chosen = jnp.sum(jnp.where(ib == e, wb, 0.0), axis=-1)
            y = (jax.nn.silu(mb @ gate) * (mb @ up)) @ down
            return acc + chosen[:, None] * y, None

        held = p["gate"].shape[0]
        out, _ = jax.lax.scan(
            jax.checkpoint(one), jnp.zeros_like(mb),
            (p["gate"], p["up"], p["down"], first + jnp.arange(held)))
        return out

    s = m.shape[0]
    b = blocks(s, POS_BLOCK)
    out = jax.lax.map(jax.checkpoint(block), tuple(
        a.reshape(s // b, b, *a.shape[1:]) for a in (m, idx, weights)))
    return out.reshape(s, *out.shape[2:])


def _layer(cfg, p, x, i):
    hd = cfg["head_dim"]
    s = x.shape[0]
    kind = cfg["layer_types"][i]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    inv_freq, scale = rope_table(cfg["rope_parameters"][kind], hd)
    n = rmsnorm(x, p["attn_norm"]["scale"], cfg["rms_norm_eps"])
    q = rope((n @ p["attn"]["wq"]["kernel"]).reshape(s, -1, hd), inv_freq,
             scale)
    k = rope((n @ p["attn"]["wk"]["kernel"]).reshape(s, -1, hd), inv_freq,
             scale)
    v = (n @ p["attn"]["wv"]["kernel"]).reshape(s, -1, hd)
    o = attention(q, k, v, window)                              # [S, H, D]
    if cfg["gating_types"][i] == "per_head":
        o = o * jax.nn.sigmoid(n @ p["attn"]["wg"]["kernel"])[:, :, None]
    h = x + o.reshape(s, -1) @ p["attn"]["wo"]["kernel"]
    m = rmsnorm(h, p["mlp_norm"]["scale"], cfg["rms_norm_eps"])
    if cfg["mlp_layer_types"][i] == "dense":
        return h + by_position_blocks(lambda mb: gated_mlp(p["mlp"], mb), m)
    shared = by_position_blocks(
        lambda mb: gated_mlp(p["moe"]["shared"], mb), m)
    return h + shared + experts_held(cfg, p["moe"], m @ p["moe"]["router"], m)


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
