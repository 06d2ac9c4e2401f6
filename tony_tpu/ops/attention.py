"""Blockwise (flash) attention as Pallas TPU kernels.

Memory-bound attention is the canonical HBM-bandwidth problem
(pallas_guide.md): materializing the [S, S] score matrix is O(S²) HBM
traffic, while the blockwise online-softmax formulation streams K/V tiles
through VMEM and keeps the running (max, sum, acc) state on-chip, so HBM
traffic stays O(S·D). Forward and backward are custom kernels under a
``jax.custom_vjp``; the forward saves only O and the row logsumexp L.

TPU-first design points (round-3 rework):

- **GQA is zero-copy.** K/V stay at their native ``[B, H_kv, S, D]`` shape;
  the q→kv head mapping happens in the BlockSpec index maps (``h // g``), so
  repeated heads cost no extra HBM footprint or bandwidth. The dk/dv grid
  folds the ``g`` group members into its innermost loop and accumulates in
  VMEM scratch.
- **Per-row stats are near-minimal.** lse/delta are ``[B, H, 8, S]`` f32 —
  the 8-sublane-broadcast layout (32 B/row, the smallest tileable form: the
  last two dims must tile (8, 128)) — not the ``[·, S, 128]``
  lane-broadcast layout of jax's bundled kernel (512 B/row; measurable at
  long context).
- **Matmuls run at native MXU rate.** Inputs keep their dtype (bf16 stays
  bf16) with ``preferred_element_type=f32`` accumulation; softmax state is
  f32 on-chip.
- **Causal tiles are skipped in the DMA, not just the ALU.** Index maps
  clamp fully-masked tiles to the previous fetch, so Pallas's pipeline
  skips the copy (revisited blocks are not re-fetched).
- **v has a width of its own.** q and k share ``D``; v, the output, ``dO``
  and ``dv`` have ``Dv`` (latent attention's 192 and 128). The scale comes
  from q's width. Where the two are equal every block is what it was.

Public layout is ``[batch, seq, heads, head_dim]`` (the layout the models
use); kernels run on ``[B, H, S, D]`` views. On non-TPU backends the kernels
run in Pallas interpret mode so the exact same code path is unit-tested on
the virtual CPU mesh (SURVEY.md §4 test strategy).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tony_tpu.compat import per_shard
from tony_tpu.parallel.mesh import BATCH_AXES

NEG_INF = -1e30
# Stats (lse/delta) sublane broadcast factor: min f32 tile is (8, 128), so
# a per-row float is stored as 8 identical sublanes over lanes=seq.
STAT_SUB = 8
# Default flash tile size, from the v5e sweeps documented on
# flash_attention: shared by every public attention entry point (flash,
# flash_with_lse, ring, ulysses) so a re-sweep updates one constant.
DEFAULT_BLOCK = 1024
# ``jax.ad_checkpoint.checkpoint_name`` tags of ``_flash``'s forward outputs
# (o, lse): the two residuals only the forward kernel can give back. A remat
# whose policy saves these names keeps them and drops its re-run of
# ``flash_fwd`` (models/transformer.py ``remat_policy_of``).
FLASH_RESIDUAL_NAMES = ("flash_o", "flash_lse")


def _prec(x):
    """Dot precision: TPU DEFAULT multiplies in bf16 (one MXU pass) — right
    for bf16 inputs, silently lossy for f32 ones. f32 inputs (the oracle /
    unit-test path) get HIGHEST (true f32 passes) so the kernel is exact
    where the caller asked for f32."""
    return (jax.lax.Precision.HIGHEST if x.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _load2d(ref, block_idx, block_rows, seq):
    """Load a [1, 1, block, d] block with out-of-range rows zeroed, keeping
    the stored dtype (bf16 in → bf16 out, so dots hit the MXU at full rate).
    Pallas pads partial edge blocks with undefined memory (NaN in interpret
    mode); a zero row is inert in every matmul below, undefined is not.
    When ``seq`` divides the block the guard compiles away entirely — the
    production path pays zero VPU passes here."""
    x = ref[0, 0]
    if seq % block_rows == 0:
        return x
    rows = block_idx * block_rows + jax.lax.broadcasted_iota(
        jnp.int32, x.shape, 0)
    return jnp.where(rows < seq, x, jnp.zeros_like(x))


def _load_stat(ref, block_idx, block_rows, seq):
    """Load a per-row statistic block [1, 1, STAT_SUB, block] (identical
    sublanes — see _finalize) as a [block, 1] COLUMN vector, zero past
    ``seq``. Column (sublane) orientation matters: the stats broadcast
    against the [bq, bk] score tile along lanes, and handing Mosaic a lane
    vector here would cost a lane→sublane relayout on every tile."""
    x = jnp.transpose(ref[0, 0][:1, :])        # [block, 1]
    if seq % block_rows == 0:
        return x
    rows = block_idx * block_rows + jax.lax.broadcasted_iota(
        jnp.int32, x.shape, 0)
    return jnp.where(rows < seq, x, 0.0)


def _store_stat(ref, col):
    """Store a [block, 1] column stat as the [STAT_SUB, block] sublane-
    broadcast block."""
    ref[0, 0] = jnp.broadcast_to(jnp.transpose(col), ref.shape[2:])


def _valid_kj(i, block_q, block_k, window=None):
    """(first, last) k-block index with any unmasked element for q-tile
    ``i``: causal, nothing after the tile's last row; under a window (query
    r sees keys r − w < c ≤ r), nothing before its first row's first key
    ``i·bq − w + 1``. Single source of truth for BOTH the kernels' compute
    guards and the index-map DMA clamps — they must never disagree."""
    last = (i * block_q + block_q - 1) // block_k
    if window is None:
        return 0, last
    lo = i * block_q - window + 1
    return (max(lo, 0) if isinstance(lo, int)
            else jnp.maximum(lo, 0)) // block_k, last


def _valid_qi(j, block_q, block_k, window=None):
    """(first, last) q-block index with any unmasked element for k-tile
    ``j`` (first: ceil((j·bk − bq + 1)/bq) == floor(j·bk/bq)). ``last`` is
    None without a window (every later q-tile sees the k-tile); under a
    window the tile's last key is seen up to row ``(j+1)·bk − 2 + w``."""
    first = (j * block_k) // block_q
    if window is None:
        return first, None
    return first, ((j + 1) * block_k - 2 + window) // block_q


def _band_blocks(n_outer: int, n_inner: int, valid) -> int:
    """Length of a windowed kernel's innermost grid axis: the most inner
    tiles any outer tile has between its first and last valid one
    (``valid(index) -> (first, last)`` on plain ints)."""
    return max(min(last, n_inner - 1) - first + 1
               for first, last in map(valid, range(n_outer)))


def _mask_scores(s, qi, kj, block_q, block_k, causal, seq_q, seq_k,
                 window=None):
    """Set invalid scores to NEG_INF so they vanish through exp().

    VPU passes over the [bq, bk] score tile are the flash bottleneck at
    small head_dim, so the mask is ONE broadcast compare + ONE select built
    from 1-D iotas ([bq,1] vs [1,bk] — register-cheap), and the
    sequence-edge guards (grid padding when seq % block != 0) are emitted
    only for ragged shapes: the production path (divisible seq) pays 2
    passes for causal, 0 for non-causal. A window is one compare more from
    the same iotas. (A row whose keys in this tile are all masked keeps
    m = NEG_INF and adds exp(0) garbage; NEG_INF is finite, so the first
    tile that holds one of its keys scales that garbage by exp(NEG_INF − m)
    = 0, and under a causal mask every row has its own diagonal.)

    Returns (masked s, valid) — ``valid`` is None when only the causal
    compare ran (no padded rows/cols exist, so exp(masked) needs no extra
    zeroing)."""
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (s.shape[0], 1), 0)
    cols = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, s.shape[1]), 1)
    ragged = bool(seq_q % block_q) or bool(seq_k % block_k)
    valid = None
    if ragged:
        # Padded-q rows are masked too so backward passes can't scatter
        # garbage into dk/dv (forward writes of padded rows are dropped).
        valid = (cols < seq_k) & (rows < seq_q)
        if causal:
            valid = valid & (rows >= cols)
    elif causal:
        valid = rows >= cols
    if window is not None:
        valid = valid & (rows < cols + window)
    if valid is None:
        return s, None
    s = jnp.where(valid, s, NEG_INF)
    return s, (valid if ragged else None)


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        scale: Optional[float] = None,
                        window: Optional[int] = None) -> jax.Array:
    """Plain XLA attention ([B,S,H,D] layout) — the correctness oracle.
    ``window=w`` (causal): query i sees keys i − w < j ≤ i.
    Einsums run at HIGHEST precision: on TPU the DEFAULT is bf16 multiplies,
    which would make the oracle less accurate than the kernel under test."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   precision=_prec(q)).astype(jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      precision=_prec(v))


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch,
                scale: float, causal: bool, block_q: int, block_k: int,
                num_k_blocks: int, seq_q: int, seq_k: int,
                fused_rowsum: bool, window: Optional[int] = None):
    if fused_rowsum:
        m_scr, acc_scr = scratch
        l_scr = None
    else:
        m_scr, l_scr, acc_scr = scratch
    qi = pl.program_id(2)
    j = pl.program_id(3)
    # ``num_k_blocks`` is the grid's last axis: every k tile, or under a
    # window the band's, counted from the q tile's first valid one.
    first, last = _valid_kj(qi, block_q, block_k, window)
    kj = j if window is None else first + j

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        acc_scr[:] = jnp.zeros_like(acc_scr)
        if not fused_rowsum:
            l_scr[:] = jnp.zeros_like(l_scr)

    # Causal: skip fully-masked tiles (k strictly after the q tile's end).
    run = True
    if causal:
        run = kj <= last

    @pl.when(run)
    def _compute():
        q = _load2d(q_ref, qi, block_q, seq_q)    # [block_q, d]
        k = _load2d(k_ref, kj, block_k, seq_k)    # [block_k, d]
        v = _load2d(v_ref, kj, block_k, seq_k)    # [block_k, dv]
        # Scale folded into the [·, d] q block — 8–16× fewer elements than
        # a post-hoc pass over the [bq, bk] score tile.
        qs = q * jnp.asarray(scale, q.dtype)
        s = jax.lax.dot_general(
            qs, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(q))                   # [block_q, block_k]
        s, _ = _mask_scores(s, qi, kj, block_q, block_k, causal, seq_q,
                            seq_k, window)
        # All row stats stay [block_q, 1] COLUMN vectors: reductions use
        # keepdims and the scratch is (block_q, 1), so no lane↔sublane
        # relayout ever happens on the hot path (1-D lane vectors with
        # [:, None] broadcasts cost a relayout per tile).
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        if fused_rowsum:
            # The row-sum rides the MXU: a ones column appended to v makes
            # the pv dot produce [o_partial | l_partial] in one accumulator
            # — free while dv+1 fits the 128-wide MXU/lane tile, deleting
            # the VPU sum-reduce pass over the score tile. (At dv >= 128
            # the extra column would pad to a second lane tile, doubling
            # accumulator VMEM — the plain reduce is used instead.)
            v1 = jnp.concatenate(
                [v, jnp.ones((v.shape[0], 1), v.dtype)], axis=1)
            acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
                p.astype(v.dtype), v1, preferred_element_type=jnp.float32,
                precision=_prec(v))
        else:
            l_scr[:] = l_scr[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32,
                precision=_prec(v))
        m_scr[:] = m_new

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        if fused_rowsum:
            acc = acc_scr[:]
            l = jnp.maximum(acc[:, -1:], 1e-30)
            o_ref[0, 0] = (acc[:, :-1] / l).astype(o_ref.dtype)
        else:
            l = jnp.maximum(l_scr[:], 1e-30)
            o_ref[0, 0] = (acc_scr[:] / l).astype(o_ref.dtype)
        _store_stat(lse_ref, m_scr[:] + jnp.log(l))


# ---------------------------------------------------------------------------
# Backward kernels (standard flash backward, two passes)
# ---------------------------------------------------------------------------
def _p_block(s, lse, qi, kj, block_q, block_k, causal, seq_q, seq_k,
             window=None):
    """exp(s − lse) with NEG_INF masking (causal entries vanish through the
    exp). Ragged shapes additionally zero p explicitly: padded lse/do reads
    are undefined memory on TPU, so exp(s − lse) can't be trusted there —
    for divisible shapes that where() is statically elided."""
    sm, valid = _mask_scores(s, qi, kj, block_q, block_k, causal, seq_q,
                             seq_k, window)
    p = jnp.exp(sm - lse)                       # lse is [bq, 1]
    if valid is not None:
        p = jnp.where(valid, p, 0.0)
    return p


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_scr, *, scale: float, causal: bool, block_q: int,
                   block_k: int, num_k_blocks: int, seq_q: int, seq_k: int,
                   window: Optional[int] = None):
    qi = pl.program_id(2)
    j = pl.program_id(3)
    first, last = _valid_kj(qi, block_q, block_k, window)
    kj = j if window is None else first + j

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        run = kj <= last

    @pl.when(run)
    def _compute():
        q = _load2d(q_ref, qi, block_q, seq_q)
        k = _load2d(k_ref, kj, block_k, seq_k)
        v = _load2d(v_ref, kj, block_k, seq_k)
        do = _load2d(do_ref, qi, block_q, seq_q)
        lse = _load_stat(lse_ref, qi, block_q, seq_q)
        delta = _load_stat(delta_ref, qi, block_q, seq_q)
        # One scaled copy of the [·, d] k block serves both dots:
        # s = q·(k·scale)ᵀ and dq += ds_hat·(k·scale), where
        # ds_hat = p·(dp − delta) — no [bq, bk]-sized scale pass.
        ks = k * jnp.asarray(scale, k.dtype)
        s = jax.lax.dot_general(
            q, ks, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(q))
        p = _p_block(s, lse, qi, kj, block_q, block_k, causal, seq_q,
                     seq_k, window)                         # [bq, bk]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(v))
        ds = (p * (dp - delta)).astype(k.dtype)
        acc_scr[:] += jax.lax.dot(ds, ks,
                                  preferred_element_type=jnp.float32,
                                  precision=_prec(k))

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, 0] = acc_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                    causal: bool, block_q: int, block_k: int,
                    num_q_blocks: int, num_inner: int, seq_q: int,
                    seq_k: int, window: Optional[int] = None,
                    num_q_total: int = 0):
    kj = pl.program_id(2)
    t = pl.program_id(3)          # folds (group member, q block)
    # ``num_q_blocks`` q tiles a group member: every one, or under a window
    # the band's, counted from the k tile's first valid one.
    first, last = _valid_qi(kj, block_q, block_k, window)
    qi = t % num_q_blocks
    if window is not None:
        qi = first + qi

    @pl.when(t == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if window is not None:
        # q tiles wholly after the last row that sees the k tile's end.
        run = qi <= jnp.minimum(last, num_q_total - 1)
    elif causal:
        # q tiles strictly before the k tile's start contribute nothing.
        run = qi >= first

    @pl.when(run)
    def _compute():
        q = _load2d(q_ref, qi, block_q, seq_q)
        k = _load2d(k_ref, kj, block_k, seq_k)
        v = _load2d(v_ref, kj, block_k, seq_k)
        do = _load2d(do_ref, qi, block_q, seq_q)
        lse = _load_stat(lse_ref, qi, block_q, seq_q)
        delta = _load_stat(delta_ref, qi, block_q, seq_q)
        # One scaled [·, d] q block serves s = (q·scale)·kᵀ and
        # dk += ds_hatᵀ·(q·scale) — no [bq, bk]-sized scale pass.
        qs = q * jnp.asarray(scale, q.dtype)
        s = jax.lax.dot_general(
            qs, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=_prec(q))
        p = _p_block(s, lse, qi, kj, block_q, block_k, causal, seq_q,
                     seq_k, window)                         # [bq, bk]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(do))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(v))
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds, qs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(q))

    @pl.when(t == num_inner - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------
def _interpret() -> bool:
    """Pallas interpret mode, for every platform but TPU (the CPU suite
    runs the same kernels through the interpreter). Keyed on the devices
    in use — the bound mesh's when there is one, so an ahead-of-time
    lowering for a TPU topology from a CPU host gets the Mosaic kernels —
    and on the default backend otherwise."""
    dev = jax.sharding.get_abstract_mesh().abstract_device
    if dev is not None:
        return not dev.device_kind.startswith("TPU")
    return jax.default_backend() != "tpu"


#: Every kernel operand is laid out [batch, heads (q or kv), ...]: under a
#: bound mesh each device runs the kernels on its own
#: [B/batch-axes, H/tp, S, D] shard (compat.per_shard).
_KERNEL_DIM_AXES = (BATCH_AXES, ("tp",))


def _round_up(x: int, m: int) -> int:
    """Blocks must honour TPU sublane tiling (8 f32 / 16 bf16 rows);
    a block clamped to a ragged seq length would not lower."""
    return -(-x // m) * m


def _fwd_impl(q, k, v, scale, causal, block_q, block_k, out_dtype=None,
              window=None):
    return per_shard(
        functools.partial(_fwd_call, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k,
                          out_dtype=out_dtype, window=window),
        _KERNEL_DIM_AXES)(q, k, v)


def _kernel_name(kind: str, window) -> str:
    """Windowed calls carry names of their own in the device trace, so that
    a reader of the full kernels' names never counts them."""
    return f"flash_{kind}" if window is None else f"flash_win_{kind}"


def _fwd_call(q, k, v, *, scale, causal, block_q, block_k, out_dtype,
              window=None):
    b, h, sq, d = q.shape
    dv = v.shape[3]             # o is v's width; q and k share d
    hk = k.shape[1]
    g = h // hk
    sk = k.shape[2]
    # q blocks round to 128: block_q is the stats blocks' LANE dim, which
    # must be a multiple of 128 (k blocks only ever sit on sublanes → 16).
    block_q = min(block_q, _round_up(sq, 128))
    block_k = min(block_k, _round_up(sk, 16))
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    # Under a window the last grid axis walks the band alone: a skipped
    # tile still costs a grid step, and most of a long row's are outside.
    nkb = nk if window is None else _band_blocks(
        nq, nk, lambda i: _valid_kj(i, block_q, block_k, window))
    kv_j = functools.partial(_kv_index, block_q=block_q, block_k=block_k,
                             causal=causal, window=window)

    fused_rowsum = dv < 128
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, num_k_blocks=nkb, seq_q=sq, seq_k=sk,
        fused_rowsum=fused_rowsum, window=window)
    o, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nkb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, i, j: (b, h // g, kv_j(i, j), 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b, h, i, j: (b, h // g, kv_j(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, STAT_SUB, block_q),
                         lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_shape=[
            # out_dtype=f32 hands the caller the kernel's own f32
            # accumulator unrounded — ring attention threads it through
            # hops so error stays flat in sp degree (ops/ring.py).
            jax.ShapeDtypeStruct((b, h, sq, dv), out_dtype or q.dtype),
            jax.ShapeDtypeStruct((b, h, STAT_SUB, sq), jnp.float32),
        ],
        scratch_shapes=(
            [pltpu.VMEM((block_q, 1), jnp.float32),
             pltpu.VMEM((block_q, dv + 1), jnp.float32)]
            if fused_rowsum else
            [pltpu.VMEM((block_q, 1), jnp.float32),
             pltpu.VMEM((block_q, 1), jnp.float32),
             pltpu.VMEM((block_q, dv), jnp.float32)]),
        interpret=_interpret(),
        name=_kernel_name("fwd", window),
    )(q, k, v)
    return o, lse


def _kv_index(i, j, *, block_q, block_k, causal, window):
    """The k tile that grid step ``j`` of q tile ``i`` fetches. Fully-masked
    causal tiles clamp to the previous fetch so the pipeline skips the DMA
    (revisited blocks are not re-fetched); under a window ``j`` counts from
    the q tile's first valid k tile."""
    if not causal:
        return j
    first, last = _valid_kj(i, block_q, block_k, window)
    return jnp.minimum(j if window is None else first + j, last)


def _bwd_impl(q, k, v, o, lse, do, scale, causal, block_q, block_k,
              dlse=None, window=None):
    b, h, sq, _ = q.shape
    delta_rows = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                         axis=-1)                    # [B, H, S]
    if dlse is not None:
        # lse cotangent (flash_attention_with_lse): ∂lse_i/∂s_ij = p_ij, so
        # the extra term folds into the existing ds = p·(dp − delta) as
        # ds = p·(dp − (delta − dlse)) — one subtract, zero kernel changes.
        delta_rows = delta_rows - dlse.astype(jnp.float32)
    delta = jnp.broadcast_to(
        delta_rows[:, :, None, :],
        (b, h, STAT_SUB, sq))                        # sublane-bcast like lse
    return per_shard(
        functools.partial(_bwd_call, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, window=window),
        _KERNEL_DIM_AXES)(q, k, v, do, lse, delta)


def _bwd_call(q, k, v, do, lse, delta, *, scale, causal, block_q, block_k,
              window=None):
    b, h, sq, d = q.shape
    dv = v.shape[3]             # v, do and dv are v's width
    hk = k.shape[1]
    g = h // hk
    sk = k.shape[2]
    block_q = min(block_q, _round_up(sq, 128))
    block_k = min(block_k, _round_up(sk, 16))
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(sk, block_k)
    nkb = nk if window is None else _band_blocks(
        nq, nk, lambda i: _valid_kj(i, block_q, block_k, window))
    kv_j = functools.partial(_kv_index, block_q=block_q, block_k=block_k,
                             causal=causal, window=window)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nkb,
                          seq_q=sq, seq_k=sk, window=window),
        grid=(b, h, nq, nkb),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, h, i, j: (b, h // g, kv_j(i, j), 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b, h, i, j: (b, h // g, kv_j(i, j), 0)),
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, STAT_SUB, block_q),
                         lambda b, h, i, j: (b, h, 0, i)),
            pl.BlockSpec((1, 1, STAT_SUB, block_q),
                         lambda b, h, i, j: (b, h, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=_interpret(),
        name=_kernel_name("dq", window),
    )(q, k, v, do, lse, delta)

    # dk/dv: one grid cell per kv head; the g q-head group members are
    # folded into the innermost loop (t = gi·nqb + qi) and accumulated in
    # VMEM — repeated K/V is never materialized, in either direction.
    # nqb q tiles a member: all of them, or under a window the band's.
    nqb = nq if window is None else _band_blocks(
        nk, nq, lambda j: _valid_qi(j, block_q, block_k, window))
    ni = g * nqb

    def qh(hk_, t):
        return hk_ * g + t // nqb

    def q_i(j, t):
        i = t % nqb
        if not causal:
            return i
        first, last = _valid_qi(j, block_q, block_k, window)
        if window is None:
            # First q-tile with any unmasked element for k-tile j; clamping
            # masked tiles to it skips their DMA.
            return jnp.maximum(i, first)
        return jnp.minimum(first + i, jnp.minimum(last, nq - 1))

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q_blocks=nqb,
                          num_inner=ni, seq_q=sq, seq_k=sk, window=window,
                          num_q_total=nq),
        grid=(b, hk, nk, ni),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, hk_, j, t: (b, qh(hk_, t), q_i(j, t), 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, hk_, j, t: (b, hk_, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b, hk_, j, t: (b, hk_, j, 0)),
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda b, hk_, j, t: (b, qh(hk_, t), q_i(j, t), 0)),
            pl.BlockSpec((1, 1, STAT_SUB, block_q),
                         lambda b, hk_, j, t: (b, qh(hk_, t), 0, q_i(j, t))),
            pl.BlockSpec((1, 1, STAT_SUB, block_q),
                         lambda b, hk_, j, t: (b, qh(hk_, t), 0, q_i(j, t))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b, hk_, j, t: (b, hk_, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda b, hk_, j, t: (b, hk_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hk, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b, hk, sk, dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
        ],
        interpret=_interpret(),
        name=_kernel_name("dkv", window),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, window):
    o, _ = _fwd_impl(q, k, v, scale, causal, block_q, block_k,
                     window=window)
    return o


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, window):
    o, lse = _fwd_impl(q, k, v, scale, causal, block_q, block_k,
                       window=window)
    # Identity outside a jax.checkpoint. _flash_lse below is NOT tagged:
    # ring attention calls it once per hop, and keeping sp partial outputs
    # in f32 per layer is another trade.
    o, lse = map(checkpoint_name, (o, lse), FLASH_RESIDUAL_NAMES)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, window, res, do):
    q, k, v, o, lse = res
    return _bwd_impl(q, k, v, o, lse, do, scale, causal, block_q, block_k,
                     window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, scale, causal, block_q, block_k, out_dtype):
    o, lse = _fwd_impl(q, k, v, scale, causal, block_q, block_k, out_dtype)
    return o, lse[:, :, 0, :]


def _flash_lse_fwd(q, k, v, scale, causal, block_q, block_k, out_dtype):
    o, lse = _fwd_impl(q, k, v, scale, causal, block_q, block_k, out_dtype)
    return (o, lse[:, :, 0, :]), (q, k, v, o, lse)


def _flash_lse_bwd(scale, causal, block_q, block_k, out_dtype, res, cts):
    do, dlse = cts
    q, k, v, o, lse = res
    # With out_dtype=f32 the cotangent arrives f32 while q/k/v are bf16;
    # the backward kernels' matmuls must stay at the INPUT dtype's MXU
    # rate (and Mosaic wants matched operand dtypes) — the o·do delta
    # product inside _bwd_impl is f32 regardless, so no precision is
    # given up that the pre-out_dtype path had.
    return _bwd_impl(q, k, v, o, lse, do.astype(q.dtype), scale, causal,
                     block_q, block_k, dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _check_and_transpose(q, k, v, causal, scale):
    """Shared wrapper plumbing for the public entry points: validate the
    [B,S,H,D] shapes, default the scale, hand back [B,H,S,D] kernel
    views."""
    sq, h = q.shape[1], q.shape[2]
    hk = k.shape[2]
    if causal and sq != k.shape[1]:
        raise ValueError(
            f"causal flash attention requires seq_q == seq_k, got {sq} vs "
            f"{k.shape[1]} (the kernel's mask is top-left aligned; for "
            f"decode-style offsets use ring attention or causal=False with "
            f"an explicit mask)")
    if k.shape[2] != v.shape[2]:
        raise ValueError(f"k heads ({k.shape[2]}) != v heads "
                         f"({v.shape[2]})")
    if q.shape[3] != k.shape[3]:
        raise ValueError(f"q width ({q.shape[3]}) != k width "
                         f"({k.shape[3]}); v may differ")
    if h % hk:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hk}")
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), scale)


def _window_blocks(window: int, block_q: int, block_k: int) -> tuple:
    """The tiles of a windowed call follow its window: a tile wider than the
    window computes whole tiles of pairs for a band a fraction as wide (at
    w = 512 a 1024 × 1024 tile has at most a quarter of its pairs inside the
    band). So the tile is the window rounded up to 128 lanes where that is
    under the caller's block, and the caller's block otherwise: a window of
    4,096 keeps 1,024, a window of 512 takes 512."""
    tile = _round_up(window, 128)
    return min(block_q, tile), min(block_k, tile)


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = True,
                             scale: Optional[float] = None,
                             block_q: int = DEFAULT_BLOCK,
                             block_k: int = DEFAULT_BLOCK,
                             out_dtype=None):
    """Flash attention returning ``(o [B,S,H,D], lse [B,S,H] f32)``.

    ``lse`` is the per-row logsumexp of the (scaled, masked) scores — the
    online-softmax merge statistic. Two partial results over disjoint key
    sets combine exactly as::

        lse = logaddexp(lse_a, lse_b)
        o   = o_a·exp(lse_a − lse) + o_b·exp(lse_b − lse)

    which is what ring attention does across ``sp`` hops (``ops/ring.py``).
    Both outputs are differentiable (the lse cotangent rides the existing
    backward's delta statistic).

    ``out_dtype=jnp.float32`` returns the kernel's f32 accumulator
    unrounded (inputs and matmul rate unchanged) — for callers that merge
    partials and must not pay a bf16 rounding per merge."""
    qh, kh, vh, scale = _check_and_transpose(q, k, v, causal, scale)
    oh, lse = _flash_lse(qh, kh, vh, scale, causal, block_q, block_k,
                         out_dtype)
    return oh.transpose(0, 2, 1, 3), lse.transpose(0, 2, 1)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK,
                    block_k: int = DEFAULT_BLOCK,
                    window: Optional[int] = None) -> jax.Array:
    """Flash attention, layout ``[B, S, H, D]`` (GQA: H_kv may divide H).

    ``window=w`` (causal only) lets query ``i`` see keys ``i − w < j ≤ i``.
    The three kernels then skip, in compute and in DMA, the k tiles wholly
    before a q tile's window as they skip those after its diagonal, their
    innermost grid axis walks that band alone, and the device trace names
    them ``flash_win_fwd`` / ``flash_win_dq`` / ``flash_win_dkv``. A window
    that covers the whole sequence is the full causal call, under the full
    kernels' names. A window narrower than ``block_q`` / ``block_k`` takes
    tiles of its own width (``_window_blocks``); no caller carries a second
    pair of block sizes.

    Differentiable (custom flash backward); accumulation in f32 regardless
    of input dtype (bf16 in, bf16 out, f32 softmax state on-chip), matmuls
    at the input dtype's MXU rate. GQA K/V are indexed in the BlockSpecs,
    never repeated.

    Default blocks (1024, 1024) come from v5e sweeps on the 317M flagship
    at seq 2048 (round 3, bf16 VMEM loads): 1024×1024 → 0.526 MFU
    end-to-end vs 0.477 at 512×512, 0.473 at 1024×512, 0.39 at ·×256;
    2048-wide k blocks exceed VMEM (the [bq, bk] f32 score tile is the
    limiter). Small tiles lose to per-tile VPU overhead at head_dim 64.
    The optimum HOLDS at long context (round-4 sweep, same model at seq
    8192, chunked-CE training end-to-end): 1024×1024 → 41.7k tok/s (MFU
    0.573) vs 40.3k at 512×1024 and 37.4k at 1024×512; 2048 in either
    dimension fails to compile (VMEM) at d=128. Blocks clamp to the
    actual (rounded-up) sequence, so short-seq/test calls are unaffected.
    """
    qh, kh, vh, scale = _check_and_transpose(q, k, v, causal, scale)
    if window is not None:
        if not causal or window < 1:
            raise ValueError(f"a window ({window}) needs causal=True and at "
                             f"least one key a query")
        if window >= q.shape[1]:
            window = None       # every query sees its whole causal prefix
        else:
            block_q, block_k = _window_blocks(window, block_q, block_k)
    oh = _flash(qh, kh, vh, scale, causal, block_q, block_k, window)
    return oh.transpose(0, 2, 1, 3)
