"""The chunked state-space scan (Mamba-2's SSD) as Pallas TPU kernels.

A head ``h`` of width ``P`` in group ``g`` carries a state ``S ∈ R^{N×P}``
along the sequence::

    S_t = a_t · S_{t−1} + Δ_t · B_{g,t} x_tᵀ        a_t = exp(Δ_t · A_h)
    y_t = S_tᵀ C_{g,t} + D_h · x_t                   S_0 = 0 at a row's start

Token by token that is ``S`` sequential steps of rank-one updates. The
chunked algorithm does the same sums a chunk of ``Q`` tokens at a time, as
matmuls: with ``cum_i = Σ_{k≤i} log a_k`` inside a chunk,

- within the chunk ``Y = (L ∘ C Bᵀ ∘ Δ_j) X``, ``L_ij = exp(cum_i − cum_j)``
  for ``j ≤ i``;
- the chunk leaves ``S_out = exp(cum_Q) · S_in + (B ∘ w)ᵀ X`` with
  ``w_j = exp(cum_Q − cum_j) · Δ_j``;
- what came before the chunk adds ``exp(cum_i) · (C S_in)_i``.

Forward and backward are one Mosaic call each, named ``ssd_fwd`` and
``ssd_bwd``, under one ``jax.custom_vjp``. Their grid is ``(batch, group,
head block, chunk)``, the chunk axis last and sequential: the state (forward)
or its cotangent (backward, chunks in reverse) lives in VMEM scratch from
chunk to chunk, and nothing of shape ``[chunks, Q, Q]`` ever leaves VMEM. A
head block is at most ``BLOCK_HEADS`` of a group's heads, so a group of 64
heads is eight blocks whose grid steps are each the size of a group of 8; a
group's own products are then taken once a block, and the backward writes
each block's share of ``dB`` and ``dC`` (float32) for one sum after the
call. Where a group is one block the backward writes them as they are. The
forward also writes each chunk's entering state (float32 ``[B, chunks, G,
N, heads·P]``); the backward reads that result as it is, no instruction
between the two calls. (A training step's forward pass is the differentiated
one, under a block's remat too, so a forward without that output would serve
evaluation alone.)

Heads narrower than the 128 lanes are worked on in packs of ``128 // P``
side by side: every load, store and matmul operand is a whole number of
lane tiles. A grid step has the heads of one head block, and a product
whose operand the heads share is taken once, a head's scaling ``s_h`` (one
number a token) moved to the other operand or to the float32 result, on that
head's lanes: ``(B ∘ s_h) dS_h = s_h ∘ (B dS_h)``, ``(B ∘ s_h)ᵀ X_h = Bᵀ (s_h ∘
X_h)``, ``Σ_h (dY_h ∘ s_h) S_hᵀ = (dY ∘ s) Sᵀ``. So, in tile products of
``[128, 128]`` at 8 heads of 64 (four packs):

- a head block: ``C Bᵀ``; in the backward its cotangent into ``dB`` and
  ``dC``;
- a pack: ``C S_in`` and ``Bᵀ (w ∘ X)``, the state a chunk leaves; in the
  backward also ``B dS``, ``(dY ∘ exp(cum)) S_inᵀ`` into ``dC``, ``(X ∘ w)
  dSᵀ`` into ``dB``, and ``Cᵀ (dY ∘ exp(cum))`` into the state's cotangent;
  ``dw`` is the sum of ``x ∘ (B dS)`` over a head's lanes;
- a head, because ``L`` is the head's own: ``M_h X`` in the forward, ``M_hᵀ
  dY_h`` and ``dM_h = dY_h Xᵀ`` in the backward (``dY_h`` zero off the head's
  lanes, the one mask), with the ``exp`` over ``[Q, Q]``.

That is 17 products a grid step forward and 39 backward (21 and 51 with a
product a head). The heads' scalars (``cum``, ``Δ``, ``exp(cum)``,
``exp(cum_Q − cum)``, ``w`` and their cotangents) are worked on as blocks for
the block's heads at once, ``[heads, Q]`` where a token is a lane and,
transposed once a grid step, ``[Q, heads]`` where a token is a sublane.

``A`` must be negative and ``Δ`` positive (decays in (0, 1]): the mixer's
``−exp(A_log)`` and ``softplus``. The decays, their cumulative sums and the
state are float32; ``x``, ``B`` and ``C`` multiply in their own dtype.

``impl="jnp"`` is the same chunked algorithm in plain ``jax.numpy``, autodiff
its backward: the path off the TPU (an interpreted kernel is slow) and the
kernels' test oracle beside the token-by-token recurrence.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.compat import per_shard
from tony_tpu.ops.attention import _interpret, _prec
from tony_tpu.parallel.mesh import BATCH_AXES

LANES = 128
MASKED = -1e30      # log-decay of a pair the causal mask drops
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
BLOCK_HEADS = 8     # the most heads a grid step holds


def _pack(heads: int, p: int) -> int:
    """Heads worked on side by side: the most that divide a group's heads
    and fit the 128 lanes together."""
    return max(n for n in range(1, heads + 1)
               if heads % n == 0 and (n == 1 or n * p <= LANES))


def _block_heads(per: int) -> int:
    """Heads of a grid step: the most, up to ``BLOCK_HEADS``, that divide a
    group's ``per`` heads."""
    return max(n for n in range(1, min(per, BLOCK_HEADS) + 1) if per % n == 0)


def _f32(x):
    return x.astype(jnp.float32)


def _dot(a, b, dims, prec):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=prec)


def _group_chunk(x_ref, b_ref, c_ref, cum_ref, dt_ref, pack: int, p: int):
    """What a grid step's heads share. The group's B and C ``[Q, N]``, ``C
    Bᵀ [Q, Q]``, the causal mask, a pack's lanes, the dtype and precision the
    products run in; and the heads' scalars as blocks, for all the block's
    heads at once: ``cum`` and ``Δ`` a head a row ``[heads, Q]`` and a head
    a column ``[Q, heads]``, and of the columns ``exp(cum)`` (what the
    entering state's share of token i has decayed to), ``exp(cum_Q − cum)``
    (what token j's share of the leaving state has) and ``cum_Q [1,
    heads]``."""
    bm, cm = b_ref[0], c_ref[0]
    prec = _prec(x_ref)
    q = bm.shape[0]
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, pack * p), 1)
    cum, dt = cum_ref[0, 0, 0], dt_ref[0, 0, 0]
    cum_t, dt_t = jnp.transpose(cum), jnp.transpose(dt)
    total = cum_t[q - 1:q]
    return (bm, cm, _dot(cm, bm, ((1,), (1,)), prec), rows >= cols, lane,
            x_ref.dtype, prec, cum, dt, cum_t, dt_t, jnp.exp(cum_t),
            jnp.exp(total - cum_t), total)


def _mine(lane, j: int, p: int):
    """The lanes of a pack's head ``j``."""
    return (lane >= j * p) & (lane < (j + 1) * p)


def _on_lanes(block, k: int, pack: int, p: int, lane):
    """Pack ``k``'s columns of ``block [rows, heads]``, each over its head's
    ``p`` lanes: ``[rows, pack·p]``."""
    out = jnp.broadcast_to(block[:, k * pack:k * pack + 1],
                           (block.shape[0], pack * p))
    for j in range(1, pack):
        out = jnp.where(lane >= j * p,
                        block[:, k * pack + j:k * pack + j + 1], out)
    return out


def _by_head(block, t, k: int, pack: int, p: int, lane):
    """``block [rows, heads]`` with pack ``k``'s columns set to the sums of
    ``t [rows, pack·p]`` over each head's lanes."""
    head = jax.lax.broadcasted_iota(jnp.int32, (1, block.shape[1]), 1)
    for j in range(pack):
        block = jnp.where(
            head == k * pack + j,
            jnp.sum(jnp.where(_mine(lane, j, p), t, 0.0), axis=1,
                    keepdims=True), block)
    return block


def _head_decay(cum, cum_t, h: int, causal):
    """Head ``h``'s masked decays ``L [Q, Q]``, ``L_ij = exp(cum_i −
    cum_j)`` for ``j ≤ i``."""
    return jnp.exp(jnp.where(causal, cum_t[:, h:h + 1] - cum[h:h + 1],
                             MASKED))


def _fwd_kernel(x_ref, b_ref, c_ref, cum_ref, dt_ref, d_ref, y_ref, s_in_ref,
                state, *, heads: int, p: int, pack: int):
    @pl.when(pl.program_id(3) == 0)
    def _row_start():
        state[...] = jnp.zeros_like(state)

    s_in_ref[0, 0, 0] = state[...]
    (bm, cm, cb, causal, lane, dtype, prec, cum, dt, cum_t, dt_t, before_t,
     tail_t, total) = _group_chunk(x_ref, b_ref, c_ref, cum_ref, dt_ref,
                                    pack, p)
    q, width = bm.shape[0], pack * p
    w_t = tail_t * dt_t
    for k in range(heads // pack):
        cols = slice(k * width, (k + 1) * width)
        x2, s2 = x_ref[0, :, cols], state[:, cols]          # [Q, w], [N, w]
        x32 = _f32(x2)
        within = jnp.zeros((q, width), jnp.float32)
        for j in range(pack):
            h = k * pack + j
            m = (cb * _head_decay(cum, cum_t, h, causal)
                 * dt[h:h + 1]).astype(dtype)
            within = jnp.where(_mine(lane, j, p),
                               _dot(m, x2, ((1,), (0,)), prec), within)
        carried = _dot(cm, s2.astype(dtype), ((1,), (0,)), prec)   # C S_in
        y_ref[0, :, cols] = (
            within + _on_lanes(before_t, k, pack, p, lane) * carried
            + d_ref[0, :, cols] * x32).astype(y_ref.dtype)
        # the state the chunk leaves: exp(cum_Q) S_in + Bᵀ (w ∘ X)
        xw = (x32 * _on_lanes(w_t, k, pack, p, lane)).astype(dtype)
        state[:, cols] = jnp.exp(_on_lanes(total, k, pack, p, lane)) * s2 \
            + _dot(bm, xw, ((0,), (0,)), prec)


def _bwd_kernel(x_ref, b_ref, c_ref, cum_ref, dt_ref, d_ref, s_in_ref, dy_ref,
                dx_ref, db_ref, dc_ref, dcum_ref, ddt_ref, dd_ref, dstate, *,
                heads: int, p: int, pack: int):
    @pl.when(pl.program_id(3) == 0)     # a row's last chunk: nothing follows
    def _row_end():
        dstate[...] = jnp.zeros_like(dstate)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    (bm, cm, cb, causal, lane, dtype, prec, cum, dt, cum_t, dt_t, before_t,
     tail_t, total) = _group_chunk(x_ref, b_ref, c_ref, cum_ref, dt_ref,
                                    pack, p)
    q, width = bm.shape[0], pack * p
    w_t = tail_t * dt_t
    head_row = jax.lax.broadcasted_iota(jnp.int32, (heads, 1), 0)
    head_col = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    dcb = jnp.zeros((q, q), jnp.float32)
    db = jnp.zeros(bm.shape, jnp.float32)
    dc = jnp.zeros(cm.shape, jnp.float32)
    # Cotangents of the heads' scalars, as blocks: Σ_i of a head's pairs a
    # row; a column each for Σ_j of the pairs, for exp(cum), for w and (one
    # row) for exp(cum_Q).
    pairs_j = jnp.zeros((heads, q), jnp.float32)
    pairs_i = jnp.zeros((q, heads), jnp.float32)
    dbefore = jnp.zeros((q, heads), jnp.float32)
    dw = jnp.zeros((q, heads), jnp.float32)
    dkept = jnp.zeros((1, heads), jnp.float32)
    for k in range(heads // pack):
        cols = slice(k * width, (k + 1) * width)
        x2, dy2 = x_ref[0, :, cols], dy_ref[0, :, cols]     # [Q, w]
        s2, ds2 = s_in_ref[0, 0, 0, :, cols], dstate[:, cols]   # [N, w] f32
        s2_lo, ds2_lo = s2.astype(dtype), ds2.astype(dtype)
        x32, dy32 = _f32(x2), _f32(dy2)
        w = _on_lanes(w_t, k, pack, p, lane)
        b_ds = _dot(bm, ds2_lo, ((1,), (0,)), prec)         # B dS  [Q, w]
        c_s = _dot(cm, s2_lo, ((1,), (0,)), prec)           # C S_in
        # x: the skip, into the state the chunk leaves, and (a head at a
        # time) through the chunk's own pairs M = C Bᵀ ∘ L ∘ Δ_j
        dx = d_ref[0, :, cols] * dy32 + w * b_ds
        for j in range(pack):
            h = k * pack + j
            dt_r = dt[h:h + 1]
            decay = _head_decay(cum, cum_t, h, causal)
            cbl = cb * decay
            dy_h = dy2 if pack == 1 else jnp.where(
                _mine(lane, j, p), dy2, jnp.zeros_like(dy2))
            dx = dx + _dot((cbl * dt_r).astype(dtype), dy_h, ((0,), (0,)),
                           prec)                            # Mᵀ dY
            dm = _dot(dy_h, x2, ((1,), (1,)), prec)         # [Q, Q]
            dcb = dcb + dm * decay * dt_r
            v = dm * cbl
            pairs_j = jnp.where(head_row == h,
                                jnp.sum(v, axis=0, keepdims=True), pairs_j)
            pairs_i = jnp.where(head_col == h,
                                jnp.sum(v * dt_r, axis=1, keepdims=True),
                                pairs_i)
        dx_ref[0, :, cols] = dx.astype(dx_ref.dtype)
        # what came before the chunk, exp(cum_i) · (C S_in)_i, and the state
        # it leaves, exp(cum_Q) S_in + Bᵀ (w ∘ X)
        dyb = (dy32 * _on_lanes(before_t, k, pack, p, lane)).astype(dtype)
        dc = dc + _dot(dyb, s2_lo, ((1,), (1,)), prec)
        db = db + _dot((x32 * w).astype(dtype), ds2_lo, ((1,), (1,)), prec)
        dstate[:, cols] = jnp.exp(_on_lanes(total, k, pack, p, lane)) * ds2 \
            + _dot(cm, dyb, ((0,), (0,)), prec)
        dd_ref[0, :, cols] += jnp.sum(dy32 * x32, axis=0, keepdims=True)
        dbefore = _by_head(dbefore, dy32 * c_s, k, pack, p, lane)
        dw = _by_head(dw, x32 * b_ds, k, pack, p, lane)
        dkept = _by_head(dkept, jnp.sum(ds2 * s2, axis=0, keepdims=True), k,
                         pack, p, lane)
    dcb = dcb.astype(dtype)
    db_ref[0] = (db + _dot(dcb, cm, ((0,), (0,)), prec)).astype(db_ref.dtype)
    dc_ref[0] = (dc + _dot(dcb, bm, ((1,), (0,)), prec)).astype(dc_ref.dtype)
    # w_j = exp(cum_Q − cum_j) Δ_j: Δ's share, and cum's with cum_Q's on the
    # chunk's last token
    ddt_t = dw * tail_t
    dtotal = jnp.sum(ddt_t * dt_t, axis=0, keepdims=True) \
        + jnp.exp(total) * dkept
    token = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0)
    dcum_t = pairs_i + before_t * dbefore - ddt_t * dt_t + jnp.where(
        token == q - 1, dtotal, 0.0)
    dcum_ref[0, 0, 0] = jnp.transpose(dcum_t) - pairs_j * dt
    ddt_ref[0, 0, 0] = jnp.transpose(ddt_t) + pairs_j


def _call(kernel, name: str, x, bm, cum, reverse: bool, operands: str,
          results: str):
    """The ``pallas_call`` of ``kernel`` on the grid ``(batch, group, head
    block, chunk)``, chunks in ``reverse`` for the backward; ``operands`` and
    ``results`` name each one's block: ``x`` the tokens' columns of a head
    block ``[1, Q, heads·P]``, ``b`` its group's B or C ``[1, Q, N]``, ``p``
    the head block's share of B's or C's cotangent (float32), ``h`` its heads'
    decays ``[1, 1, 1, heads, Q]``, ``d`` D's columns, ``s`` a chunk's
    entering state."""
    batch = x.shape[0]
    _, chunks, groups, per, q = cum.shape
    n, p = bm.shape[2] // groups, x.shape[2] // (groups * per)
    heads = _block_heads(per)
    blocks, width = per // heads, heads * p

    def at(c):
        return chunks - 1 - c if reverse else c

    spec = {
        "x": pl.BlockSpec((1, q, width),
                          lambda b, g, j, c: (b, at(c), g * blocks + j)),
        "b": pl.BlockSpec((1, q, n), lambda b, g, j, c: (b, at(c), g)),
        "p": pl.BlockSpec((1, q, n),
                          lambda b, g, j, c: (b, at(c), g * blocks + j)),
        "h": pl.BlockSpec((1, 1, 1, heads, q),
                          lambda b, g, j, c: (b, at(c), g, j, 0)),
        "d": pl.BlockSpec((1, 1, width),
                          lambda b, g, j, c: (b, 0, g * blocks + j)),
        "s": pl.BlockSpec((1, 1, 1, n, width),
                          lambda b, g, j, c: (b, at(c), g, 0, j)),
    }
    shape = {"x": (x.shape, x.dtype), "b": (bm.shape, bm.dtype),
             "p": ((batch, x.shape[1], groups * blocks * n), jnp.float32),
             "h": (cum.shape, jnp.float32),
             "d": ((batch, 1, x.shape[2]), jnp.float32),
             "s": ((batch, chunks, groups, n, per * p), jnp.float32)}
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, p=p, pack=_pack(heads, p)),
        grid=(batch, groups, blocks, chunks),
        in_specs=[spec[o] for o in operands],
        out_specs=[spec[r] for r in results],
        out_shape=[jax.ShapeDtypeStruct(*shape[r]) for r in results],
        scratch_shapes=[pltpu.VMEM((n, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=_interpret(), name=name)


def _fwd_call(x, bm, cm, cum, dt, d):
    """``x [B, S, H·P]``, ``bm``, ``cm [B, S, G·N]``, ``cum``, ``dt [B,
    chunks, G, heads, Q]`` float32, ``d [B, 1, H·P]`` float32 → ``y`` as
    ``x``, and each chunk's entering state."""
    return _call(_fwd_kernel, "ssd_fwd", x, bm, cum, False, "xbbhhd",
                 "xs")(x, bm, cm, cum, dt, d)


def _bwd_call(x, bm, cm, cum, dt, d, s_in, dy):
    """The cotangents of ``_fwd_call``'s six operands from ``dy``: a group of
    several head blocks has its blocks' shares of ``dB`` and ``dC`` summed."""
    b, s, gn = bm.shape
    groups, per = cum.shape[2:4]
    blocks = per // _block_heads(per)
    shared = "b" if blocks == 1 else "p"
    dx, db, dc, dcum, ddt, dd = _call(
        _bwd_kernel, "ssd_bwd", x, bm, cum, True, "xbbhhdsx",
        "x" + 2 * shared + "hhd")(x, bm, cm, cum, dt, d, s_in, dy)
    if blocks > 1:
        db, dc = (t.reshape(b, s, groups, blocks, gn // groups).sum(
            axis=3).reshape(b, s, gn).astype(bm.dtype) for t in (db, dc))
    return dx, db, dc, dcum, ddt, dd


# Every operand's leading dim is the batch: under a bound mesh each device
# runs the kernels on its own rows (compat.per_shard).
_ROWS = (BATCH_AXES,)


@jax.custom_vjp
def _scan(x, bm, cm, cum, dt, d):
    return per_shard(_fwd_call, _ROWS)(x, bm, cm, cum, dt, d)[0]


def _scan_fwd(x, bm, cm, cum, dt, d):
    y, s_in = per_shard(_fwd_call, _ROWS)(x, bm, cm, cum, dt, d)
    return y, (x, bm, cm, cum, dt, d, s_in)


def _scan_bwd(res, dy):
    return tuple(per_shard(_bwd_call, _ROWS)(*res, dy))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _grouped(a, groups: int):
    """``[B, chunks, Q, H]`` of per-head scalars → ``[B, chunks, G, heads,
    Q]``: a group's heads on sublanes, the chunk's tokens on lanes."""
    b, chunks, q, h = a.shape
    return a.reshape(b, chunks, q, groups, h // groups).transpose(
        0, 1, 3, 4, 2)


def _kernels(x, dt, a, bm, cm, d, chunk: int):
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    dt = _f32(dt).reshape(b, s // chunk, chunk, h)
    cum = jnp.cumsum(dt * _f32(a), axis=2)
    d_cols = jnp.broadcast_to(jnp.repeat(_f32(d), p)[None, None],
                              (b, 1, h * p))
    y = _scan(x.reshape(b, s, h * p), bm.reshape(b, s, g * n),
              cm.reshape(b, s, g * n), _grouped(cum, g), _grouped(dt, g),
              d_cols)
    return y.reshape(b, s, h, p)


def carried_states(left, kept):
    """The state entering each chunk, from what every chunk leaves behind
    (``left [B, chunks, H, N, P]``, from a zero state) and the share of its
    entering state it keeps (``kept [B, chunks, H]``): the hand-over from
    chunk to chunk, zero at a row's start."""
    def step(s, chunk):
        left_c, kept_c = chunk
        return kept_c[..., None, None] * s + left_c, s

    _, s_in = jax.lax.scan(step, jnp.zeros_like(left[:, 0]),
                           (left.swapaxes(0, 1), kept.swapaxes(0, 1)))
    return s_in.swapaxes(0, 1)


def _chunked(x, dt, a, bm, cm, d, chunk: int):
    """The kernels' algorithm in plain ``jax.numpy``, a row's chunks at
    once: float32 throughout, ``[chunks, Q, Q]`` held."""
    b, s, h, p = x.shape
    g, n = bm.shape[2:]
    c, per = s // chunk, h // g
    x32 = _f32(x).reshape(b, c, chunk, g, per, p)
    b32, c32 = (_f32(m).reshape(b, c, chunk, g, n) for m in (bm, cm))
    dt = _f32(dt).reshape(b, c, chunk, g, per)
    cum = jnp.cumsum(dt * _f32(a).reshape(g, per), axis=2)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(
        causal[None, None, :, :, None, None],
        cum[:, :, :, None] - cum[:, :, None, :], MASKED))   # [b,c,i,j,g,per]
    with jax.default_matmul_precision("highest"):
        cb = jnp.einsum("bcign,bcjgn->bcijg", c32, b32)
        m = cb[..., None] * decay * dt[:, :, None]
        within = jnp.einsum("bcijgh,bcjghp->bcighp", m, x32)
        total = cum[:, :, -1]                               # [b,c,g,per]
        w = jnp.exp(total[:, :, None] - cum) * dt
        left = jnp.einsum("bcjgn,bcjgh,bcjghp->bcghnp", b32, w, x32)
        s_in = carried_states(
            left.reshape(b, c, h, n, p),
            jnp.exp(total).reshape(b, c, h)).reshape(b, c, g, per, n, p)
        carried = jnp.einsum("bcign,bcghnp->bcighp", c32, s_in)
    y = within + jnp.exp(cum)[..., None] * carried \
        + _f32(d).reshape(g, per, 1) * x32
    return y.reshape(b, s, h, p).astype(x.dtype)


def ssd(x, dt, a, b, c, d, chunk: int = 128, impl: Optional[str] = None):
    """``y [B, S, H, P]`` of the recurrence above for ``x [B, S, H, P]``,
    steps ``dt [B, S, H]`` (positive), ``a [H]`` (negative), ``b``, ``c
    [B, S, G, N]`` (``G`` divides ``H``: head ``h`` reads group ``h // (H /
    G)``) and the skip ``d [H]``. Every row starts from a zero state. ``S``
    must be a whole number of chunks. ``impl``: ``"kernel"`` (the Mosaic
    calls; interpreted off the TPU), ``"jnp"``, or None for the kernels on a
    TPU and ``jax.numpy`` elsewhere."""
    if x.shape[1] % chunk:
        raise ValueError(f"a sequence of {x.shape[1]} is no whole number of "
                         f"chunks of {chunk}")
    if x.shape[2] % b.shape[2] or b.shape != c.shape:
        raise ValueError(f"{x.shape[2]} heads over B {b.shape} and C "
                         f"{c.shape}")
    if impl is None:
        impl = "jnp" if _interpret() else "kernel"
    if impl not in ("kernel", "jnp"):
        raise ValueError(f"impl {impl!r} is neither 'kernel' nor 'jnp'")
    return (_kernels if impl == "kernel" else _chunked)(x, dt, a, b, c, d,
                                                        chunk)
