"""SmallThinker in plain float32 (as the configuration's source publishes it,
each inference listed under ``assumed`` in the configuration file): its leaves
and its loss, for ``reference.follow``. One chip's share: the experts and
vocabulary rows the configuration says are held here.

Per layer, with ``n = rmsnorm(x)``: the router's logits ``r = n·Wr`` over ALL
of the model's experts, read before attention from what attention reads;
``h = x + Wo·attn(Wq·n, Wk·n, Wv·n)``, grouped-query causal softmax attention
whose layer either sees the full causal triangle with NO position embedding or
applies RoPE and lets query i see keys i − w < j ≤ i; ``m = rmsnorm(h)``; the k
largest logits are chosen and weighted by the softmax of those k; expert e is
``Wdown,e·(relu(Wgate,e·m) * Wup,e·m)``; ``out = h + Σ w_e·E_e(m)`` over the
chosen experts THAT ARE HELD HERE, as a dense masked sum (every held expert
over every position, weight zero where it was not chosen). A final rmsnorm, an
untied head, the mean next-token cross entropy. No auxiliary loss. Imports
nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import (ATTN_Q_BLOCK, POS_BLOCK, blocks, mean_over_rows,
                       next_token_nll_sum, rmsnorm, rope)

# The router's std, in units of 1/sqrt(hidden): its logits then have a spread
# of 3 over unit-variance normed inputs. Routing is discrete. The bf16 stream
# the program computes in moves a logit by a few thousandths of the spread
# whatever the spread is, so a few tokens in a hundred swap their LAST expert
# against this float32 reference at any scale; what the scale decides is how
# much such a token's swap weighs. At spread 1 the six chosen of 64 normal
# logits get nearly even weights (the 6th 0.11 against the 1st's 0.29) and
# the swaps alone put some 0.05 on the expert leaves' grad_sample_diff, the
# int8 control's own size. At spread 3 the weights are about 0.60, 0.18,
# 0.10, 0.05, 0.04, 0.03: the 6th is a twentieth of the 1st, a swap weighs a
# quarter of what it did, and the 2nd to 5th still carry two fifths of the
# result. A trained router's logits are no flatter than that.
ROUTER_SPREAD = 3.0

# The embedding's std where the configuration states no ``initializer_range``
# (the source's does not): rows of unit variance, which is what the d**-0.5
# kernels assume of what they read. Random attention is nearly uniform, so a
# layer's attention output is close to one mean of values for every position;
# beside rows of std 0.02 that shared vector is half of the stream, the next
# layers' routers read it as a bias, and a few experts take most tokens (the
# fullest 7 to 10 times the mean in layers 2 and 3, the held experts' share of
# the pairs 0.10 to 0.54 by seed: readings of these weights on the CPU,
# PR 28). The grouped matmuls skip the tiles no row came to, so the step's
# time was a property of the seed (5 % between seeds on the chip). A trained
# router is balanced; at unit variance the shared part is a few hundredths of
# the stream and the fullest expert has 1.1 to 1.8 times the mean.
EMBEDDING_STD = 1.0


def leaf_specs(cfg: dict) -> list:
    """``[(path, shape, std)]`` for every parameter leaf, in the sorted order
    of the program's parameter tree. ``std`` is None for a norm scale (ones).
    Kernels are [in, out]; experts are stacked [held, in, out]."""
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    f, held = cfg["moe_ffn_hidden_size"], cfg["moe_num_primary_experts"]
    experts = cfg["published"]["moe_num_primary_experts"]
    specs = [(("embedding",), (v, d),
              cfg.get("initializer_range", EMBEDDING_STD)),
             (("final_norm", "scale"), (d,), None)]
    for i in range(cfg["num_hidden_layers"]):
        layer = f"layer_{i}"
        specs += [
            ((layer, "attn", "wq", "kernel"), (d, q), d ** -0.5),
            ((layer, "attn", "wk", "kernel"), (d, kv), d ** -0.5),
            ((layer, "attn", "wv", "kernel"), (d, kv), d ** -0.5),
            ((layer, "attn", "wo", "kernel"), (q, d), q ** -0.5),
            ((layer, "attn_norm", "scale"), (d,), None),
            ((layer, "mlp_norm", "scale"), (d,), None),
            ((layer, "moe", "router"), (d, experts),
             ROUTER_SPREAD * d ** -0.5),
            ((layer, "moe", "gate"), (held, d, f), d ** -0.5),
            ((layer, "moe", "up"), (held, d, f), d ** -0.5),
            ((layer, "moe", "down"), (held, f, d), f ** -0.5),
        ]
    specs.append((("lm_head", "kernel"), (d, v), d ** -0.5))
    return sorted(specs)


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


def experts_held(cfg, p, logits, m):
    """The held experts' part of the layer for positions m [S, D] with router
    logits [S, E]: a dense masked sum."""
    k = cfg["moe_num_active_primary_experts"]
    first = cfg.get("share", {}).get("first_expert_held", 0)
    top, idx = jax.lax.top_k(logits, k)
    weights = jax.nn.softmax(top, axis=-1)                      # [S, k]

    def block(args):
        mb, ib, wb = args

        def one(acc, expert):
            gate, up, down, e = expert
            chosen = jnp.sum(jnp.where(ib == e, wb, 0.0), axis=-1)
            y = (jax.nn.relu(mb @ gate) * (mb @ up)) @ down
            return acc + chosen[:, None] * y, None

        held = p["gate"].shape[0]
        out, _ = jax.lax.scan(
            jax.checkpoint(one), jnp.zeros_like(mb),
            (p["gate"], p["up"], p["down"], first + jnp.arange(held)))
        return out

    return _by_blocks(block, m, idx, weights)


def _by_blocks(fn, m, idx, weights):
    """``by_position_blocks`` for a function of three arrays that share their
    leading axis."""
    s = m.shape[0]
    b = blocks(s, POS_BLOCK)
    out = jax.lax.map(jax.checkpoint(fn), tuple(
        a.reshape(s // b, b, *a.shape[1:]) for a in (m, idx, weights)))
    return out.reshape(s, *out.shape[2:])


def _layer(cfg, p, x, window, with_rope):
    hd = cfg["head_dim"]
    s = x.shape[0]
    n = rmsnorm(x, p["attn_norm"]["scale"], cfg["rms_norm_eps"])
    logits = n @ p["moe"]["router"]          # before attention, from n
    q = (n @ p["attn"]["wq"]["kernel"]).reshape(s, -1, hd)
    k = (n @ p["attn"]["wk"]["kernel"]).reshape(s, -1, hd)
    v = (n @ p["attn"]["wv"]["kernel"]).reshape(s, -1, hd)
    if with_rope:
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    o = attention(q, k, v, window)
    h = x + o.reshape(s, -1) @ p["attn"]["wo"]["kernel"]
    m = rmsnorm(h, p["mlp_norm"]["scale"], cfg["rms_norm_eps"])
    return h + experts_held(cfg, p["moe"], logits, m)


def hidden(cfg, params, tokens):
    """The final-norm hidden states [S, D] of one row of ids [S]."""
    x = params["embedding"][tokens]
    for i in range(cfg["num_hidden_layers"]):
        window = cfg["sliding_window_size"] \
            if cfg["sliding_window_layout"][i] else None
        with_rope = bool(cfg["rope_layout"][i])
        x = jax.checkpoint(
            lambda p, y, w=window, r=with_rope: _layer(cfg, p, y, w, r))(
                params[f"layer_{i}"], x)
    return rmsnorm(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def loss_fn(cfg: dict, params: dict, tokens: jax.Array) -> jax.Array:
    """Mean next-token cross entropy of a batch of ids [B, S]."""
    return mean_over_rows(
        lambda row: next_token_nll_sum(
            hidden(cfg, params, row), params["lm_head"]["kernel"], row),
        tokens)
