"""Sparse experts: one expert layer, told which experts it holds.

No reference analogue — TonY has no expert/model parallelism anywhere
(SURVEY.md §2.3, verified absent); this is TPU-first new work.

The layer routes every token over ALL of the model's experts (the router
keeps its published width) and computes the part of the result that the
experts **held here** give: ``experts_held = (first, count)``, the chip's
share of a deployment whose other experts live on further chips, or every
expert where none is named. What the absent experts would add is left out;
the shares of all the chips add up to the whole layer
(``tests/test_moe.py``).

- **Router**: a float32 matmul at ``highest`` precision on the block's
  normed PRE-attention input (it reads what attention reads) or on the
  experts' own, top-k of the logits, softmax over the chosen k
  (``scoring="softmax"``); or sigmoid scores, the k largest chosen and
  weighted by their own scores over the chosen's sum
  (``scoring="sigmoid"``). Routing is discrete, so the one matmul whose
  rounding can move a choice is the one kept exact.
- **No capacity, no dropped token.** The (token, choice) pairs that met a
  held expert are laid out expert by expert (a counting sort: a cumulative
  sum of one-hots gives each pair its place, one ``argsort`` gives each row
  its pair), every expert's rows padded to the matmul's row tile, never to
  a capacity. Shapes are static under ``jit``: the buffer has room for every
  pair a chunk of tokens could send here, and the kernels skip the tiles
  past the rows that came, in compute and in DMA. So do XLA's passes over
  the row buffers in the backward (``_live_rows``): the live rows are a
  prefix of the buffer, ``n_active`` tiles long.
- **Grouped matmuls** are named Mosaic calls: ``moe_gmm`` (forward and
  input gradient: each row tile times its own expert's matrix, the expert
  read from a prefetched table) and ``moe_tgmm`` (weight gradient: the row
  tiles of an expert accumulated into its matrix). The backward never
  densifies: dispatch and combine are gathers in both directions.
- Tokens go through in chunks (``chunk_tokens``), each recomputed in the
  backward pass, so the row buffers are a chunk's and not the batch's. The
  loop over chunks is one ``custom_vjp`` (``_chunks``) whose backward is
  hand-written: a scan over the chunks that carries the three expert
  leaves' float32 gradient sums, each chunk's ``moe_tgmm`` calls starting
  their result from the sum so far, in its buffer. The sum over chunks is
  taken inside the kernel; XLA adds no leaf to a leaf.
- **Expert parallelism** (an ``ep`` mesh axis > 1): the same body runs per
  shard on all of its group's rows (rows are split over the batch axes and
  never over ``ep``, so every ``ep`` shard has them: gathered), each shard
  holding ``n_experts / ep`` experts from ``axis_index · count``; the
  partial results are summed and scattered back (``psum_scatter``). That is
  "the shares add up" as a collective; no capacity is needed because no
  shard ever receives rows, it selects its own.
- **Experts of three matrices or of two.** Gated, an expert is
  ``down(act(gate x) · up x)``; with ``ExpertSpec.gated`` off it is
  ``down(act(up x))`` and the layer has no ``gate`` leaf: six ``moe_gmm``
  and two ``moe_tgmm`` calls a chunk where the gated layer makes nine and
  three, and the chunk loop's backward carries two sums.
- **A shared expert** (``ExpertSpec.shared_width``, a width of its own) is
  one more feed-forward of the experts' kind that every token goes through,
  beside the routed ones and unweighted. It is what every chip of a
  deployment computes alike, so it is no shard's part: dense projections of
  the experts' input (``shared/gate`` where gated, ``shared/up``,
  ``shared/down``, laid out as the dense ``MLP``'s), outside the
  ``shard_map`` body and added to the routed sum after the
  ``psum_scatter``, once. ``routed_scale`` multiplies the routed weights
  and leaves the shared expert alone.

Spans: ``tony.moe.route``, ``tony.moe.dispatch``, ``tony.moe.experts``,
``tony.moe.combine``, ``tony.moe.shared`` (``jax.named_scope``). Counters,
sown into the
``intermediates`` collection and reduced by ``moe_counters``:
``moe_rows_routed``, ``moe_rows_unrouted_share``,
``moe_expert_load_max_over_mean``, ``moe_buffer_rows_live_share``,
``moe_token_rows_gathered_share``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from tony_tpu import compat
from tony_tpu.ops.attention import _interpret, _prec
from tony_tpu.ops.quant import (INT8, dense, quantize_symmetric,
                                resolve_mode)
from tony_tpu.parallel.mesh import BATCH_AXES

EP_AXIS = "ep"
ACTIVATIONS = {"silu": nn.silu, "relu": nn.relu,
               "relu2": lambda x: jnp.square(nn.relu(x))}
SCORINGS = ("softmax", "sigmoid")
# Mosaic's scoped VMEM default (16 MiB) is under a [2560, 768] expert
# matrix double-buffered beside its row tiles; the v5e has 128 MiB.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
# XLA's passes over a row buffer go a segment of whole tiles at a time and
# stop where the live rows end (``_live_rows``).
SEGMENT_ROWS = 4096
# The token side's sums go a segment of tokens at a time and stop where a
# slot's tokens end (``_gather_sum``).
TOKEN_SEGMENT_ROWS = 256


@dataclasses.dataclass(frozen=True)
class ExpertSpec:
    """A layer's sparse feed-forward: ``n_experts`` router outputs,
    ``top_k`` experts a token, experts of hidden ``width``, ``gated``
    (``down(act(gate x) · up x)``, three matrices) or not (``down(act(up
    x))``, two), and ``held = (first, count)``, the experts that live here
    (None: all of them). ``shared_width``: a shared expert of that hidden
    width beside them (None: none); ``routed_scale``: the factor on the
    routed experts' weights. ``scoring``: the weights are the softmax of the
    chosen logits, or (``"sigmoid"``) each chosen expert's sigmoid score
    over the sum of the chosen's."""
    n_experts: int
    top_k: int
    width: int
    activation: str = "silu"
    held: Optional[Tuple[int, int]] = None
    # The router reads the block's normed PRE-attention input (what
    # attention reads) and not the experts' own input.
    route_before_attention: bool = False
    tile_rows: int = 256        # the grouped matmul's row tile
    chunk_tokens: int = 8192    # tokens routed at a time
    shared_width: Optional[int] = None
    routed_scale: float = 1.0
    gated: bool = True
    scoring: str = "softmax"

    @property
    def into(self) -> Tuple[str, ...]:
        """The matrices that read an expert's input, by their leaves'
        names; ``down`` reads what they give."""
        return ("gate", "up") if self.gated else ("up",)

    def __post_init__(self):
        first, count = self.held or (0, self.n_experts)
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"experts held {self.held} are not a range of "
                             f"the {self.n_experts} experts")
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} of {self.n_experts}")
        if self.activation not in ACTIVATIONS:
            raise ValueError(f"activation {self.activation!r} is not one "
                             f"of {sorted(ACTIVATIONS)}")
        if self.scoring not in SCORINGS:
            raise ValueError(f"scoring {self.scoring!r} is not one of "
                             f"{SCORINGS}")


class MoEConfig:
    """Constructors of ``TransformerConfig``s whose every layer has
    experts (``examples/moe/``)."""

    @staticmethod
    def tiny_moe(n_experts: int = 4, top_k: int = 2, **kw):
        from tony_tpu.models.transformer import (LayerSpec,
                                                 TransformerConfig)

        defaults = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, mlp_dim=128, max_seq_len=128,
                        dtype=jnp.float32, remat=False)
        defaults.update(kw)
        experts = ExpertSpec(n_experts=n_experts, top_k=top_k,
                             width=defaults["mlp_dim"], tile_rows=8)
        defaults.setdefault("layers", (LayerSpec(experts=experts),)
                            * defaults["n_layers"])
        return TransformerConfig(**defaults)


# ---------------------------------------------------------------------------
# Grouped matmul kernels: every row tile belongs to one expert
# ---------------------------------------------------------------------------
def _gmm_kernel(tile_expert, n_active, lhs_ref, rhs_ref, *rest,
                transpose_rhs: bool):
    del tile_expert
    *scales, out_ref = rest     # int8 operands bring their two scales

    @pl.when(pl.program_id(1) < n_active[0])
    def _compute():
        dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        if scales:
            rows_scale, cols_scale = scales     # [tile, 1], [1, tn]
            out = jax.lax.dot_general(
                lhs_ref[...], rhs_ref[...], dims,
                preferred_element_type=jnp.int32).astype(jnp.float32) \
                * rows_scale[...] * cols_scale[...]
        else:
            out = jax.lax.dot_general(
                lhs_ref[...], rhs_ref[...], dims,
                preferred_element_type=jnp.float32,
                precision=_prec(lhs_ref))
        out_ref[...] = out.astype(out_ref.dtype)


def _tgmm_kernel(tile_expert, n_active, lhs_ref, rhs_ref, *rest):
    *acc_ref, out_ref = rest    # a running sum comes as one more block
    i = pl.program_id(1)
    # Tiles of one expert are consecutive and every expert has at least
    # one, so its [K, tn] block stays in VMEM from its first tile to its
    # last and each block is started exactly once: from the first product,
    # or from the running sum's block and the first product.
    new_expert = jnp.logical_or(
        i == 0, tile_expert[i] != tile_expert[jnp.maximum(i - 1, 0)])

    @pl.when(i < n_active[0])
    def _compute():
        part = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=_prec(lhs_ref))

        @pl.when(new_expert)
        def _first():
            out_ref[...] = acc_ref[0][...] + part if acc_ref else part

        @pl.when(jnp.logical_not(new_expert))
        def _more():
            out_ref[...] += part


def _col_tile(n: int, want: int = 1024) -> int:
    """The column tile of ``n`` columns: a multiple of 128 of at most
    ``want`` that covers them in the fewest columns, the largest such (the
    largest divisor where one divides ``n``; where none does, as at 1,856,
    the last tile hangs over the edge, and since a product's columns do not
    mix, what it computes there is never written). ``n`` itself under 128
    (tiny test widths)."""
    tiles = range(min(n, want) // 128 * 128, 0, -128)
    return min(tiles, key=lambda t: (-(-n // t) * t, -t), default=n)


def _live_row(i, n_active):
    """Row tiles past ``n_active`` clamp to the last live one, so they
    fetch nothing and their (skipped) steps leave its finished block in
    place."""
    return jnp.minimum(i, n_active[0] - 1)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _gmm_call(lhs, rhs, tile_expert, n_active, *, tile_rows: int,
              transpose_rhs: bool = False, scales=None, out_dtype=None):
    """``out[tile i] = lhs[tile i] @ rhs[tile_expert[i]]`` (``rhs[e]ᵀ`` with
    ``transpose_rhs``) for the first ``n_active`` row tiles; the rows of the
    others are left unwritten and nothing reads them. int8 operands come
    with ``scales = (a row's [M, 1], an expert's output channel's [count, 1,
    N])``, applied to the int32 product, and state their ``out_dtype``."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn = _col_tile(n)
    tiles = m // tile_rows

    row = _live_row         # grid (column tile, row tile)
    rhs_block = (None, tn, k) if transpose_rhs else (None, k, tn)
    in_specs = [
        pl.BlockSpec((tile_rows, k), lambda j, i, te, na: (row(i, na), 0)),
        pl.BlockSpec(rhs_block,
                     (lambda j, i, te, na: (te[row(i, na)], j, 0))
                     if transpose_rhs else
                     (lambda j, i, te, na: (te[row(i, na)], 0, j))),
    ]
    if scales is not None:
        in_specs += [
            pl.BlockSpec((tile_rows, 1),
                         lambda j, i, te, na: (row(i, na), 0)),
            pl.BlockSpec((None, 1, tn),
                         lambda j, i, te, na: (te[row(i, na)], 0, j)),
        ]
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(n, tn), tiles),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((tile_rows, tn),
                                   lambda j, i, te, na: (row(i, na), j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype or lhs.dtype),
        compiler_params=_params(),
        interpret=_interpret(),
        name="moe_gmm",
    )(tile_expert, n_active, lhs, rhs, *(scales or ()))


def _tgmm_call(lhs, rhs, tile_expert, n_active, *, tile_rows: int,
               count: int, acc=None):
    """``out[e] = acc[e] + Σ_{tiles i of expert e} lhs[tile i]ᵀ @ rhs[tile
    i]`` in float32, ``[count, K, N]``; ``acc`` is a running sum of that
    shape, which the result takes the place of, or None for zero. Rows past
    an expert's own within its last tile must be zero on one side (they
    are: a padded row's cotangent is scaled by its zero weight), so an
    expert without rows keeps its sum."""
    m, k = lhs.shape
    n = rhs.shape[1]
    tn = _col_tile(n, 512)
    tiles = m // tile_rows
    row = _live_row
    # Every block of the result is visited, and read (where there is a sum
    # to read) before it is written: the sum's buffer can be the result's.
    out_spec = pl.BlockSpec(
        (None, k, tn), lambda j, i, te, na: (te[row(i, na)], 0, j))
    in_specs = [
        pl.BlockSpec((tile_rows, k), lambda j, i, te, na: (row(i, na), 0)),
        pl.BlockSpec((tile_rows, tn), lambda j, i, te, na: (row(i, na), j)),
    ]
    operands = [tile_expert, n_active, lhs, rhs]
    aliases = {}
    if acc is not None:
        aliases = {len(operands): 0}    # the prefetched tables count
        in_specs.append(out_spec)
        operands.append(acc)

    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(pl.cdiv(n, tn), tiles),
            in_specs=in_specs,
            out_specs=out_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((count, k, n), jnp.float32),
        input_output_aliases=aliases,
        compiler_params=_params(),
        interpret=_interpret(),
        name="moe_tgmm",
    )(*operands)


def _grouped_forward(lhs, w_lo, w_q, tile_expert, n_active, tile_rows):
    if w_q is None:
        return _gmm_call(lhs, w_lo, tile_expert, n_active,
                         tile_rows=tile_rows)
    q_lhs, rows_scale = quantize_symmetric(lhs, INT8, axis=-1)
    return _gmm_call(q_lhs, w_q[0], tile_expert, n_active,
                     tile_rows=tile_rows, scales=(rows_scale, w_q[1]),
                     out_dtype=lhs.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7,))
def grouped_matmul(lhs, w, w_lo, w_q, dw_so_far, tile_expert, n_active,
                   tile_rows):
    """Rows ``lhs [M, K]`` times their experts' matrices ``[count, K, N]``.
    ``w`` is the parameter, which takes the gradient (float32); ``w_lo`` its
    cast to the matmul dtype, made once a layer outside the chunk loop,
    which the kernels read. ``w_q`` is None, or ``w_lo`` in int8 with its
    scales (``quantize_symmetric`` over the contraction): the forward
    product is then int8 by int8, a row's scale taken here, and the
    gradients stay those of the unquantized product (straight through, as
    ``ops/quant.py`` has it for a dense layer). ``dw_so_far`` is None, or
    the float32 sum of ``w``'s gradient over the chunks that went before:
    what comes back as ``w``'s cotangent is then that sum with this call's
    share added, unrounded, inside ``moe_tgmm`` (``_chunks_bwd`` carries it
    from chunk to chunk)."""
    del w, dw_so_far
    return _grouped_forward(lhs, w_lo, w_q, tile_expert, n_active, tile_rows)


def _grouped_matmul_fwd(lhs, w, w_lo, w_q, dw_so_far, tile_expert, n_active,
                        tile_rows):
    del w
    out = _grouped_forward(lhs, w_lo, w_q, tile_expert, n_active, tile_rows)
    return out, (lhs, w_lo, dw_so_far, tile_expert, n_active)


def _grouped_matmul_bwd(tile_rows, res, dout):
    lhs, w_lo, dw_so_far, tile_expert, n_active = res
    dw = _tgmm_call(lhs, dout, tile_expert, n_active, tile_rows=tile_rows,
                    count=w_lo.shape[0], acc=dw_so_far)
    # The weight gradient first: it is the last reader of ``lhs``, whose
    # buffer is then free before the input gradient's is made. Left to the
    # scheduler, gate's and up's ``xs`` outlives both of their input
    # gradients and the compiled step is 0.23 GB larger.
    dw, dout = jax.lax.optimization_barrier((dw, dout))
    dlhs = _gmm_call(dout, w_lo, tile_expert, n_active, tile_rows=tile_rows,
                     transpose_rhs=True)
    return dlhs, dw, None, None, None, None, None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


# ---------------------------------------------------------------------------
# The layout: (token, choice) pairs, expert by expert, padded to the tile
# ---------------------------------------------------------------------------
def _padded(sizes, tile_rows: int):
    """Rows an expert takes of the buffer: its own, in whole tiles, and at
    least one (the weight gradient writes every expert's block)."""
    return jnp.maximum(-(-sizes // tile_rows), 1) * tile_rows


def _counted(keys, n: int):
    """A counting sort's two counts of ``keys [N]`` in ``[0, n)``: how many
    have each value, and how many of a key's equals come before it (0 for a
    key past ``n``)."""
    onehot = (keys[:, None] == jnp.arange(n, dtype=keys.dtype)[None, :]
              ).astype(jnp.int32)                           # [N, n]
    return (jnp.sum(onehot, axis=0),
            jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1))


def _layout(idx, first, count: int, tile_rows: int, rows: int):
    """Where each (token, choice) pair of ``idx [T, k]`` (expert ids) goes in
    a buffer of ``rows`` rows that holds experts ``[first, first + count)``
    one after another, each padded to whole tiles (at least one, so that the
    weight gradient writes every expert's block).

    Returns ``held [T, k]`` (the pair's expert lives here), ``pos [T, k]``
    (its row; ``rows`` where not held), ``row_pair [rows]`` (the row's pair
    ``t·k + c``; any pair on a padding row), ``row_live [rows]``,
    ``tile_expert [rows / tile_rows]``, ``n_active [1]`` (tiles in use) and
    ``sizes [count]`` (rows an expert)."""
    t, k = idx.shape
    local = idx - first
    held = (local >= 0) & (local < count)
    flat = jnp.where(held, local, count).reshape(-1)        # sentinel last
    sizes, rank = _counted(flat, count)
    padded = _padded(sizes, tile_rows)
    ends = jnp.cumsum(padded)
    starts = ends - padded
    pos = jnp.where(held.reshape(-1),
                    starts[jnp.minimum(flat, count - 1)] + rank, rows)
    # Rows to pairs: a stable sort by expert lists an expert's pairs in
    # token order, which is the order ``rank`` counted them in.
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    tiles = rows // tile_rows
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(tiles) * tile_rows, side="right"),
        count - 1).astype(jnp.int32)
    row = jnp.arange(rows, dtype=jnp.int32)
    row_expert = jnp.repeat(tile_expert, tile_rows)
    offset = row - starts[row_expert]
    row_live = (offset < sizes[row_expert]) & (row < ends[-1])
    sorted_starts = jnp.cumsum(sizes) - sizes
    row_pair = order[jnp.clip(sorted_starts[row_expert] + offset, 0,
                              t * k - 1)]
    n_active = (ends[-1:] // tile_rows).astype(jnp.int32)
    return (held, pos.reshape(t, k).astype(jnp.int32), row_pair, row_live,
            tile_expert, n_active, sizes)


def _live_rows(fn, written, read, n_active, tile_rows: int,
               segment_rows: Optional[int] = None):
    """``written[r], … = fn(written[r], …, read[r], …)`` for the rows ``r`` of
    the live prefix ``[0, n_active · tile_rows)`` of buffers with one leading
    dim; the rows past it stay as they are, and nothing reads them. ``fn``
    works row by row on a segment of whole tiles (``segment_rows``, or
    ``SEGMENT_ROWS``); the loop's trip count follows ``n_active``, so autodiff
    never meets it: only the hand-written halves of a ``custom_vjp`` call
    this. A buffer in ``written`` that is dead afterwards is updated in
    place."""
    rows = written[0].shape[0]
    seg = min(max((segment_rows or SEGMENT_ROWS) // tile_rows, 1) * tile_rows,
              rows)

    def segment(i, bufs):
        # Where segments do not divide the buffer the last one starts early,
        # and the rows an earlier one wrote keep what it wrote.
        start = jnp.minimum(i * seg, rows - seg)
        old = [jax.lax.dynamic_slice_in_dim(b, start, seg) for b in bufs]
        new = fn(*old, *(jax.lax.dynamic_slice_in_dim(a, start, seg)
                         for a in read))
        if rows % seg:
            fresh = start + jnp.arange(seg) >= i * seg
            new = [jnp.where(fresh.reshape(-1, *(1,) * (o.ndim - 1)), n, o)
                   for n, o in zip(new, old)]
        return tuple(jax.lax.dynamic_update_slice_in_dim(b, n, start, 0)
                     for b, n in zip(bufs, new))

    live = n_active[0] * tile_rows
    return jax.lax.fori_loop(0, -(-live // seg), segment, tuple(written))


def _orders_tokens(spec: ExpertSpec, count: int) -> bool:
    """Whether a layer that holds ``count`` experts orders its tokens by the
    pairs they have here (``_token_order``): where the rows it then gathers a
    token under an even router, the ``top_k · count / n_experts`` held pairs
    and the one row back, are fewer than the ``top_k`` a loop over the
    choices gathers (a gathered row costs either way about the same:
    ``PERF.md`` 6, PR 33). A layer that holds every expert does not, nor
    does one whose tokens choose a single expert."""
    return spec.top_k * count / spec.n_experts + 1 < spec.top_k


def _token_order(held, slots: int):
    """The token side's order of work for a chunk whose pairs ``held [T, k]``
    met an expert here; a token has at most ``slots`` such pairs. Each token's
    held pairs are counted off in their choice order, its ``j``-th into slot
    ``j``, and the tokens are listed by how many they have, most first (a
    counting sort by the ``slots + 1`` counts, as ``_layout``'s by expert).
    Slot ``j`` is then filled for a prefix of the list and for no token past
    it.

    Returns ``in_slot [T, slots, k]`` (choice ``c`` of token ``t`` is its
    ``j``-th held pair), ``listed [T]`` (the list's ``p``-th token), ``n
    [slots]`` (the prefix: tokens with more than ``j`` pairs here) and
    ``place [T]`` (where token ``t`` is in the list)."""
    held_i = held.astype(jnp.int32)
    rank = jnp.cumsum(held_i, axis=1) - held_i      # held pairs before it
    slot = jnp.arange(slots, dtype=jnp.int32)
    in_slot = held[:, None, :] & (rank[:, None, :] == slot[None, :, None])
    group = slots - jnp.sum(held_i, axis=1)         # most pairs first
    sizes, rank = _counted(group, slots + 1)
    ends = jnp.cumsum(sizes)
    place = (ends - sizes)[group] + rank
    listed = jnp.argsort(group, stable=True).astype(jnp.int32)
    # Tokens with more than j pairs are groups 0 … slots − j − 1.
    return in_slot, listed, ends[slots - 1 - slot], place.astype(jnp.int32)


def _gather_sum(src, pos, scale, order):
    """``out[t] = Σ_c scale[t, c] · src[pos[t, c]]`` in float32, one choice
    at a time (a [T, k, D] gather is never held); ``scale`` is zero where the
    pair is not held, and such a ``pos`` is clamped, never read for its
    value. With an ``order`` (``_token_order``) only held pairs are gathered:
    slot by slot over the slot's prefix of the listed tokens, a segment
    (``TOKEN_SEGMENT_ROWS``) at a time, so a token's pairs are summed in its
    own order of choices, and one gather takes the sums back to the tokens'
    order. Only the hand-written halves of a ``custom_vjp`` call this
    (``_live_rows``)."""
    def part(rows, scale):
        # Rows past the live prefix of ``src`` may hold anything.
        return jnp.where(scale[:, None] != 0,
                         src[rows].astype(jnp.float32), 0.0) * scale[:, None]

    if order is None:
        safe = jnp.minimum(pos, src.shape[0] - 1)
        out = None
        for c in range(pos.shape[1]):
            each = part(safe[:, c], scale[:, c])
            out = each if out is None else out + each
        return out
    in_slot, listed, n, place = order

    def by_slot(of_pair):
        # [T, k] of a token's pairs → [slots, T] of the listed tokens' slots;
        # zero where a slot is empty: row 0, scaled by nothing.
        return jnp.sum(jnp.where(in_slot, of_pair[:, None, :], 0),
                       axis=-1)[listed].T

    rows, scale = by_slot(pos), by_slot(scale)

    def slot(j, out):
        # One loop over the slots holds one loop over a slot's segments:
        # a slot each would be traced and compiled ``slots`` times over.
        rows_j, scale_j = (jax.lax.dynamic_index_in_dim(a, j, keepdims=False)
                           for a in (rows, scale))
        (out,) = _live_rows(
            lambda acc, at, by: (acc + part(at, by),), (out,),
            (rows_j, scale_j), jax.lax.dynamic_slice_in_dim(n, j, 1), 1,
            TOKEN_SEGMENT_ROWS)
        return out

    out = jax.lax.fori_loop(
        0, rows.shape[0], slot,
        jnp.zeros((pos.shape[0], src.shape[1]), jnp.float32))
    return out[place]


@jax.custom_vjp
def _dispatch(x, row_token, pos, held, order):
    """Rows for the experts: ``xs[r] = x[row_token[r]]``. Its transpose is a
    gather too: a token's gradient is the sum over its held pairs' rows."""
    del pos, held, order
    return x[row_token]


def _dispatch_fwd(x, row_token, pos, held, order):
    return x[row_token], (pos, held, order)


def _dispatch_bwd(res, dxs):
    pos, held, order = res
    dx = _gather_sum(dxs, pos, held.astype(jnp.float32), order)
    return dx.astype(dxs.dtype), None, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _for_gate_and_up(xs, n_active, tile_rows):
    """``xs`` twice, for the two products that read it: the transpose sums
    their two cotangents over the live rows, in the first one's buffer."""
    del n_active
    return xs, xs


def _for_gate_and_up_fwd(xs, n_active, tile_rows):
    return (xs, xs), n_active


def _for_gate_and_up_bwd(tile_rows, n_active, dboth):
    (dxs,) = _live_rows(lambda a, b: (a + b,), dboth[:1], dboth[1:],
                        n_active, tile_rows)
    return dxs, None


_for_gate_and_up.defvjp(_for_gate_and_up_fwd, _for_gate_and_up_bwd)


def _hidden_of(activation: str, gated: bool):
    act = ACTIVATIONS[activation]
    return (lambda gate, up: act(gate) * up) if gated else act


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _hidden(pre, n_active, tile_rows, activation):
    """``act(gate) · up`` of ``pre = (gate, up)``, or ``act(up)`` of ``pre =
    (up,)``. Forward it is one fused pass over the whole buffer (a loop
    would first fill a fresh buffer with zeros, which costs what the dead
    rows do); its transpose works over the live rows, in the buffers of
    ``pre``."""
    del n_active
    return _hidden_of(activation, len(pre) == 2)(*pre)


def _hidden_fwd(pre, n_active, tile_rows, activation):
    return _hidden_of(activation, len(pre) == 2)(*pre), (pre, n_active)


def _hidden_bwd(tile_rows, activation, res, dhidden):
    pre, n_active = res

    def transpose(*rows):       # what autodiff writes, a segment at a time
        *pre, dh = rows
        return jax.vjp(_hidden_of(activation, len(pre) == 2), *pre)[1](dh)

    return _live_rows(transpose, pre, (dhidden,), n_active, tile_rows), None


_hidden.defvjp(_hidden_fwd, _hidden_bwd)


def _combined(y, weights, pos, held, order):
    return _gather_sum(y, pos, jnp.where(held, weights, 0.0),
                       order).astype(y.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _combine(y, weights, pos, held, order, row_pair, row_live, n_active,
             tile_rows):
    """``out[t] = Σ_c weights[t, c] · y[pos[t, c]]`` over the held pairs."""
    del row_pair, row_live, n_active
    return _combined(y, weights, pos, held, order)


def _combine_fwd(y, weights, pos, held, order, row_pair, row_live, n_active,
                 tile_rows):
    return (_combined(y, weights, pos, held, order),
            (y, weights, pos, held, row_pair, row_live, n_active))


def _combine_bwd(tile_rows, res, dout):
    y, weights, pos, held, row_pair, row_live, n_active = res
    k = weights.shape[1]
    pair_weight = weights.reshape(-1)

    def row_side(y_rows, _, pair, live):
        # One gather of ``dout``'s rows gives both cotangents: ``dy`` takes
        # ``y``'s place, and a row's ``Σ_d dout · y`` is its pair's weight
        # gradient. A padding row's ``dy`` is exactly zero: the weight
        # gradient's kernel counts on it.
        dout_rows = dout[pair // k].astype(jnp.float32)
        weight = jnp.where(live, pair_weight[pair], 0.0)
        return ((dout_rows * weight[:, None]).astype(y.dtype),
                jnp.sum(dout_rows * y_rows.astype(jnp.float32), axis=-1))

    dy, dweight_rows = _live_rows(
        row_side, (y, jnp.zeros(y.shape[:1], jnp.float32)),
        (row_pair, row_live), n_active, tile_rows)
    dweights = jnp.where(held, dweight_rows[jnp.minimum(pos, y.shape[0] - 1)],
                         0.0).astype(weights.dtype)
    return dy, dweights, None, None, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _buffer_rows(tokens: int, spec: ExpertSpec, count: int) -> int:
    """Rows a chunk's buffer needs: every pair that could meet one of the
    ``count`` experts here (a token's choices are distinct, so at most
    ``min(top_k, count)`` of them), and one tile of padding an expert."""
    tile = spec.tile_rows
    most = tokens * min(spec.top_k, count)
    return (-(-most // tile) + count) * tile


def _chunk_tokens(spec: ExpertSpec, tokens: int) -> int:
    """Tokens routed at a time: ``chunk_tokens``, or all where that does not
    divide them."""
    return tokens if tokens % spec.chunk_tokens else spec.chunk_tokens


def _one_chunk(spec: ExpertSpec, xc, ic, wc, ws, lo, q, dws_so_far, first):
    """A chunk's tokens ``xc [chunk, D]`` through the experts held here;
    ``ws``, ``lo``, ``q`` and ``dws_so_far`` are ``grouped_matmul``'s ``w``,
    ``w_lo``, ``w_q`` and ``dw_so_far`` for gate, up and down, or for up and
    down where the experts are not gated."""
    count = lo[0].shape[0]
    rows = _buffer_rows(xc.shape[0], spec, count)
    with jax.named_scope("tony.moe.dispatch"):
        held, pos, row_pair, row_live, tile_expert, n_active, _ = \
            _layout(ic, first, count, spec.tile_rows, rows)
        order = _token_order(held, min(spec.top_k, count)) \
            if _orders_tokens(spec, count) else None
        xs = _dispatch(xc, row_pair // spec.top_k, pos, held, order)
    with jax.named_scope("tony.moe.experts"):
        *into, down = (
            functools.partial(grouped_matmul, w=w, w_lo=w_lo, w_q=w_q,
                              dw_so_far=dw, tile_expert=tile_expert,
                              n_active=n_active, tile_rows=spec.tile_rows)
            for w, w_lo, w_q, dw in zip(ws, lo, q, dws_so_far))
        if spec.gated:
            gate, up = into
            xs_gate, xs_up = _for_gate_and_up(xs, n_active, spec.tile_rows)
            pre = (gate(xs_gate), up(xs_up))
        else:
            pre = (into[0](xs),)
        y = down(_hidden(pre, n_active, spec.tile_rows, spec.activation))
    with jax.named_scope("tony.moe.combine"):
        return _combine(y, wc, pos, held, order, row_pair, row_live,
                        n_active, spec.tile_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _chunks(spec: ExpertSpec, int8: bool, x, idx, weights, ws, first):
    """``_one_chunk`` over the leading dim of ``x [n, chunk, D]``, ``idx``
    and ``weights [n, chunk, k]``, the experts' matrices ``ws`` (three, or
    two where they are not gated) cast to ``x``'s
    dtype (and to int8 where ``int8`` says so) once for all chunks. The
    backward is a loop of its own and not the transpose autodiff makes of
    this one, which would add each chunk's float32 ``[count, K, N]`` weight
    gradients to its carry as a pass of XLA's: three reads and writes of a
    leaf a chunk that ``moe_tgmm`` can do on its way."""
    return _chunks_fwd(spec, int8, x, idx, weights, ws, first)[0]


def _chunks_fwd(spec, int8, x, idx, weights, ws, first):
    with jax.named_scope("tony.moe.experts"):
        lo = tuple(w.astype(x.dtype) for w in ws)
        q = tuple(quantize_symmetric(w, INT8, axis=1) if int8 else None
                  for w in lo)
    # The loop's own slices and stacks of the chunks count with dispatch.
    with jax.named_scope("tony.moe.dispatch"):
        out = jax.lax.map(
            lambda c: _one_chunk(spec, *c, ws, lo, q, (None,) * len(ws),
                                 first), (x, idx, weights))
    # A chunk keeps nothing for its backward but its inputs: what it kept
    # would be stacked over the chunks, which is the buffer chunks avoid.
    return out, (x, idx, weights, ws, lo, q, first)


def _chunks_bwd(spec, int8, res, dout):
    x, idx, weights, ws, lo, q, first = res

    def step(dws_so_far, chunk):
        # The chunk again, and its cotangents as autodiff writes them from
        # the hand-written halves; the weights' are the sums carried on.
        xc, ic, wc, dc = chunk
        _, vjp = jax.vjp(
            lambda xc, wc, ws: _one_chunk(spec, xc, ic, wc, ws, lo, q,
                                          dws_so_far, first), xc, wc, ws)
        dxc, dwc, dws = vjp(dc)
        return dws, (dxc, dwc)

    with jax.named_scope("tony.moe.experts"):
        zeros = tuple(jnp.zeros(w.shape, jnp.float32) for w in ws)
    with jax.named_scope("tony.moe.dispatch"):     # as the forward's loop
        dws, (dx, dweights) = jax.lax.scan(step, zeros,
                                           (x, idx, weights, dout))
    return dx, None, dweights, dws, None


_chunks.defvjp(_chunks_fwd, _chunks_bwd)


def routed_experts(spec: ExpertSpec, x, idx, weights, ws, first, dtype,
                   int8: bool = False):
    """What the experts ``[first, first + count)`` (``count`` from the
    leading dim of their matrices ``ws``: gate, up and down, or up and down)
    add for tokens ``x [T, D]`` whose choices are ``idx [T, k]`` with
    ``weights [T, k]``: ``[T, D]`` in ``dtype``, the forward products in
    int8 where ``int8`` says so. The tokens go ``_chunk_tokens`` at a time;
    one chunk is a loop of one trip."""
    t, d = x.shape
    n = t // _chunk_tokens(spec, t)
    parts = _chunks(spec, int8, x.astype(dtype).reshape(n, -1, d),
                    idx.reshape(n, t // n, -1), weights.reshape(n, t // n, -1),
                    tuple(ws), first)
    return parts.reshape(t, d)


def routing_counters(spec: ExpertSpec, idx, first, count, token_groups=1,
                     expert_groups=1) -> dict:
    """A step's routing, for experts ``[first, first + count)``: the rows
    (token, choice) that met one of them, the share of tokens none of whose
    choices did, the fullest expert's rows over the mean, the share of a
    chunk's row buffer that is live (the rows that came and their padding, by
    the layout's own sizes: ``n_active · tile_rows`` over the buffer's rows)
    and the rows the token side gathers a ``_gather_sum`` (the slots' prefixes
    in whole segments and the gather back, by the order's own counts) over
    the ``chunk · top_k`` a loop over every choice gathers, which is what a
    layer that holds every expert does: 1. The last two are means over the
    chunks. Where the tokens are split ``token_groups`` ways and the experts
    ``expert_groups`` ways over devices, a chunk and a buffer are one
    device's."""
    held = (idx >= first) & (idx < first + count)
    chunk = _chunk_tokens(spec, idx.shape[0] // token_groups)
    here = count // expert_groups               # experts a device
    sizes = jnp.sum((idx.reshape(-1, chunk * idx.shape[1], 1) - first
                     == jnp.arange(count)).astype(jnp.int32), axis=1)
    load = jnp.sum(sizes, axis=0).astype(jnp.float32)
    live = jnp.sum(_padded(sizes, spec.tile_rows).reshape(
        -1, expert_groups, here), axis=-1)
    gathered = jnp.float32(chunk * spec.top_k)
    if _orders_tokens(spec, here):
        # A token's pairs with each device's experts, and from them the
        # slots' prefixes as ``_token_order`` counts them.
        mine = jnp.sum(((idx - first)[..., None] // here
                        == jnp.arange(expert_groups)).astype(jnp.int32),
                       axis=1).reshape(-1, chunk, expert_groups, 1)
        n = jnp.sum((mine > jnp.arange(min(spec.top_k, here))
                     ).astype(jnp.int32), axis=1)
        seg = min(TOKEN_SEGMENT_ROWS, chunk)
        gathered = jnp.mean((jnp.sum(-(-n // seg) * seg, axis=-1) + chunk
                             ).astype(jnp.float32))
    return {
        "moe_rows_routed": jnp.sum(held.astype(jnp.float32)),
        "moe_rows_unrouted_share":
            1.0 - jnp.mean(jnp.any(held, axis=-1).astype(jnp.float32)),
        "moe_expert_load_max_over_mean":
            jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9),
        "moe_buffer_rows_live_share":
            jnp.mean(live.astype(jnp.float32))
            / _buffer_rows(chunk, spec, here),
        "moe_token_rows_gathered_share": gathered / (chunk * spec.top_k),
    }


def moe_counters(intermediates) -> dict:
    """The layers' sown counters as one dict of scalars for a step's aux
    metrics: rows summed over layers, the shares their mean, the load its
    worst layer. {} for a model without experts."""
    found: dict = {}
    for path, value in jax.tree_util.tree_leaves_with_path(intermediates):
        name = next((str(getattr(k, "key", "")) for k in reversed(path)
                     if str(getattr(k, "key", "")).startswith("moe_")), None)
        if name:
            found.setdefault(name, []).append(value)
    reduce = {"moe_rows_routed": jnp.sum,
              "moe_rows_unrouted_share": jnp.mean,
              "moe_expert_load_max_over_mean": jnp.max,
              "moe_buffer_rows_live_share": jnp.mean,
              "moe_token_rows_gathered_share": jnp.mean}
    return {name: reduce[name](jnp.stack(values))
            for name, values in found.items()}


class SharedExpert(nn.Module):
    """The feed-forward every token goes through: the dense ``MLP`` at the
    shared expert's width (without its ``gate`` where the experts are not
    gated), ``matmul_dtype`` covering its projections as it covers that
    one's."""

    spec: ExpertSpec
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    matmul_dtype: str

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        proj = functools.partial(dense, dtype=self.dtype,
                                 param_dtype=self.param_dtype,
                                 matmul_dtype=self.matmul_dtype)
        width = self.spec.shared_width
        pre = tuple(proj(width, ("embed", "mlp"), name)(x)
                    for name in self.spec.into)
        h = nn.with_logical_constraint(
            _hidden_of(self.spec.activation, self.spec.gated)(*pre),
            ("batch", "seq", "mlp"))
        return proj(x.shape[-1], ("mlp", "embed"), "down")(h)


class ExpertLayer(nn.Module):
    """Top-k routed experts, and the shared expert beside them where the
    spec has one. ``router_in`` is what the router reads
    (the block's normed pre-attention input, or the same tensor as ``x`` for
    a router after attention); ``x`` is what the experts read."""

    spec: ExpertSpec
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    # ``TransformerConfig.matmul_dtype`` (the ``tony.train.matmul-dtype``
    # knob): "int8" quantizes the experts' three forward products as
    # ``ops/quant.py`` does a dense projection's. They are most of a sparse
    # model's products, so a knob that stopped at the attention projections
    # would do next to nothing here and not say so
    # (``tests/test_quant.py``: the loss-parity gate with experts).
    matmul_dtype: str = ""

    @nn.compact
    def __call__(self, router_in: jax.Array, x: jax.Array) -> jax.Array:
        spec = self.spec
        b, s, d = x.shape
        t = b * s
        first, count = spec.held or (0, spec.n_experts)
        mode = resolve_mode(self.matmul_dtype)
        if mode not in (None, INT8):
            raise ValueError(f"the grouped matmuls have an int8 path alone, "
                             f"not {mode!r}")
        routed = functools.partial(routed_experts, int8=mode == INT8)

        router = self.param(
            "router", nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "expert_logits")),
            (d, spec.n_experts), self.param_dtype)

        def w(name, shape, axes):
            return self.param(name, nn.with_logical_partitioning(
                nn.initializers.lecun_normal(batch_axis=(0,)), axes), shape,
                self.param_dtype)

        ws = tuple(w(name, (count, d, spec.width), ("expert", "embed", "mlp"))
                   for name in spec.into) \
            + (w("down", (count, spec.width, d), ("expert", "mlp", "embed")),)

        # Mosaic kernels cannot be partitioned automatically: under a bound
        # mesh the body runs in a shard_map manual over every axis that is
        # not manual already (size-1 axes included, as compat.per_shard).
        mesh = compat.current_mesh()
        auto = () if mesh is None else tuple(
            a for a in mesh.axis_names if a not in mesh.manual_axes)
        n_ep = mesh.shape[EP_AXIS] if EP_AXIS in auto else 1
        rows = tuple(a for a in BATCH_AXES if a in auto)
        n_rows = math.prod(mesh.shape[a] for a in rows)
        if t % n_rows:      # computed whole, and alike, on every device
            rows, n_rows = (), 1
        if n_ep > 1 and (spec.held is not None or count % n_ep
                         or (t // n_rows) % n_ep):
            raise ValueError(
                f"an ep axis of {n_ep} shares out all {count} experts "
                f"(held={spec.held}) and scatters {t // n_rows} tokens: "
                f"both must divide, and no share may be named")

        with jax.named_scope("tony.moe.route"):
            # float32 at highest precision: the one matmul whose rounding
            # can move a discrete choice.
            logits = jnp.dot(router_in.reshape(t, d).astype(jnp.float32),
                             router.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            if spec.scoring == "softmax":
                top, idx = jax.lax.top_k(logits, spec.top_k)
                weights = jax.nn.softmax(top, axis=-1)
            else:
                top, idx = jax.lax.top_k(jax.nn.sigmoid(logits), spec.top_k)
                weights = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
            if spec.routed_scale != 1.0:
                weights = weights * spec.routed_scale
            for name, value in routing_counters(
                    spec, idx, first, count, n_rows, n_ep).items():
                self.sow("intermediates", name, value)

        def with_shared(out):
            # What every shard computes alike is added once: here, outside
            # the shards' parts and after their sum.
            out = out.reshape(b, s, d)
            if spec.shared_width is None:
                return out
            with jax.named_scope("tony.moe.shared"):
                return out + SharedExpert(
                    spec, self.dtype, self.param_dtype, self.matmul_dtype,
                    name="shared")(x)

        xt = x.reshape(t, d)
        if not auto:
            return with_shared(routed(spec, xt, idx, weights, ws, first,
                                      self.dtype))

        def shard(xt, idx, weights, *ws):
            if n_ep == 1:
                return routed(spec, xt, idx, weights, ws, first, self.dtype)
            # Every shard has all of its group's rows (they are not split
            # over ep) and selects its own experts' pairs; the shares add
            # up, and each shard keeps a slice of the sum.
            mine = jax.lax.axis_index(EP_AXIS) * (count // n_ep)
            part = routed(spec, xt, idx, weights, ws, mine, self.dtype)
            return jax.lax.psum_scatter(part, EP_AXIS, scatter_dimension=0,
                                        tiled=True)

        tok = P(rows or None)
        held_w = P(EP_AXIS) if n_ep > 1 else P()
        out = jax.shard_map(
            shard, axis_names=set(auto),
            in_specs=(tok, tok, tok) + (held_w,) * len(ws),
            out_specs=P(rows + (EP_AXIS,)) if n_ep > 1 else tok,
            check_vma=False)(xt, idx, weights, *ws)
        return with_shared(out)


def dryrun_ep_step(devices, ep: int) -> float:
    """One FULL expert-layer train step (fwd + bwd + optimizer update) on an
    ep≥2 mesh, asserting the compiled program sums the shards' shares with a
    reduce-scatter over ``ep``. Used by ``__graft_entry__.dryrun_multichip``;
    returns the loss."""
    import optax

    from tony_tpu.models.transformer import Transformer, causal_lm_loss
    from tony_tpu.parallel import MeshSpec, build_mesh, init_sharded_state
    from tony_tpu.parallel.sharding import DEFAULT_RULES

    n = len(devices)
    mesh = build_mesh(MeshSpec(dp=n // ep, ep=ep), devices=devices)
    cfg = MoEConfig.tiny_moe()
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.key(0), (2 * (n // ep), 32), 0,
                                cfg.vocab_size)
    state, _sh = init_sharded_state(model, tokens, optax.adam(1e-3), mesh)

    def loss_fn(p):
        with nn.logical_axis_rules(list(DEFAULT_RULES)):
            return causal_lm_loss(model.apply({"params": p}, tokens), tokens)

    def step(state):
        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads), loss

    # set_mesh binds the abstract mesh ExpertLayer reads to pick the ep
    # path; without it n_ep resolves to 1 and the dry run would only
    # validate the one-device body.
    with jax.set_mesh(mesh):
        compiled = jax.jit(step).lower(state).compile()
        hlo = compiled.as_text()
        assert "reduce-scatter" in hlo or "all-reduce" in hlo, \
            "ep dryrun compiled WITHOUT a sum of the shards' shares"
        state, loss = compiled(state)
    loss = float(loss)
    assert jnp.isfinite(loss), f"ep MoE train step diverged: {loss}"
    return loss
