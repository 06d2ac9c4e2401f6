"""Ring attention: exact attention over a sequence sharded on the ``sp`` axis.

Long-context support is absent from the reference (SURVEY.md §5 "long-context
— absent"); here it is first-class. Each device holds a [B, S/n, H, D] shard
of Q/K/V. K/V chunks rotate around the ``sp`` ring via ``ppermute`` (nearest-
neighbour ICI traffic only) while each device accumulates its Q shard's
online-softmax state — after n steps every Q block has seen every K/V block
and the K/V shards are back home. Compute at step i overlaps the transfer for
step i+1 (XLA schedules the ppermute DMA asynchronously with the compute).

TPU-first structure (the RingAttention-paper blockwise design, built on our
own kernel):

- **Each hop runs the Pallas flash kernel** on (local Q, visiting K/V chunk)
  and yields a normalized partial ``(o, lse)``; hops merge by the exact
  logsumexp rule (``flash_attention_with_lse``). Per-hop memory is
  O(S_local·D) — no [S_local, S_local] score chunk ever exists in HBM, so
  per-device context is bounded by flash's streaming VMEM footprint, not by
  a materialized score matrix.
- **Causally dead hops are skipped, not masked.** Under causal attention the
  visiting chunk is strictly-future for half the hops on average; a
  ``lax.switch`` dispatches diagonal hops to causal flash, past chunks to
  non-causal flash, and future chunks to a free zero/−inf partial (XLA
  conditionals execute one branch — unlike inside a Pallas kernel). The old
  einsum formulation computed every dead chunk and masked it to −inf.
- **GQA is native end-to-end**: K/V rotate at their H_kv width (the per-hop
  ppermute payload — ring attention's bandwidth bottleneck at long context —
  is H/H_kv× smaller than with repeated heads), and the flash BlockSpecs
  index kv-heads directly, so repeated heads never materialize anywhere.

`ring_attention` is the *per-shard* function, for use inside `shard_map`
(this is how model code composes it with other sharded ops);
`ring_attention_sharded` wraps it for global arrays.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from tony_tpu.ops.attention import DEFAULT_BLOCK, flash_attention_with_lse

NEG_INF = -1e30


def bound_axis_size(axis_name: str):
    """Size of a bound mesh axis, None when NO axes are bound (init or
    single-shard trace — callers fall back to local semantics), and a loud
    NameError when other axes ARE bound but this one isn't (a misnamed axis
    under shard_map must not silently degrade to shard-local attention)."""
    try:
        from jax._src import core

        sizes = dict(getattr(core.get_axis_env(), "axis_sizes", {}) or {})
    except Exception:  # private API moved: fall back to probing
        try:
            return jax.lax.psum(1, axis_name)
        except NameError:
            # The requested axis isn't bound — but another mesh axis might
            # be, which would mean a *misnamed* axis, not an unsharded
            # trace. Probe the standard mesh axes so that case still fails
            # loudly instead of silently degrading to shard-local attention.
            from tony_tpu.parallel.mesh import MESH_AXES

            bound = []
            for name in MESH_AXES:
                if name == axis_name:
                    continue
                try:
                    jax.lax.psum(1, name)
                    bound.append(name)
                except NameError:
                    pass
            if bound:
                raise NameError(
                    f"axis {axis_name!r} is not bound under this shard_map; "
                    f"bound axes include: {bound} — pass the right axis_name")
            return None
    if axis_name in sizes:
        return jax.lax.psum(1, axis_name)
    if sizes:
        raise NameError(
            f"axis {axis_name!r} is not bound under this shard_map; bound "
            f"axes: {sorted(sizes)} — pass the right axis_name")
    return None


def refuse_window(impl: str, window) -> None:
    """The sequence-parallel attentions know the full causal mask alone: a
    hop or a head swap would have to carry the window's second bound."""
    if window is not None:
        raise ValueError(
            f"{impl} attention has no windowed mask (window={window}): a "
            f"layer with a window needs attn_impl 'flash' or 'xla'")


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str = "sp", causal: bool = True,
                   scale: Optional[float] = None,
                   block_q: int = DEFAULT_BLOCK,
                   block_k: int = DEFAULT_BLOCK,
                   window: Optional[int] = None) -> jax.Array:
    """Per-shard ring attention ([B, S_local, H, D] in/out; GQA: K/V may
    carry H_kv heads with H_kv | H). Call inside shard_map with the
    sequence dim sharded over ``axis_name``.

    Precision: each hop's partial output leaves the flash kernel as the
    kernel's OWN f32 accumulator (``out_dtype=f32`` — never rounded to the
    input dtype), and hops merge in f32 by the exact logsumexp rule, so
    the only rounding to bf16 is the single final cast. Ring error is
    therefore ~flat in the sp degree (asserted by
    ``test_ring_error_flat_in_sp_degree``); the wire/rotation dtype of the
    K/V chunks stays the input dtype — ICI bandwidth is unchanged."""
    refuse_window("ring", window)
    b, s_loc, h, d = q.shape
    hk = k.shape[2]
    if k.shape[2] != v.shape[2]:
        raise ValueError(f"k heads ({k.shape[2]}) != v heads "
                         f"({v.shape[2]})")
    if h % hk:
        raise ValueError(f"q heads {h} not a multiple of kv heads {hk}")
    g = h // hk
    n = bound_axis_size(axis_name)
    if n is None:
        # No axes bound at all (model init / single-shard apply): the
        # "ring" is a single chunk — plain causal attention.
        from tony_tpu.ops.attention import reference_attention
        if g > 1:
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        return reference_attention(q, k, v, causal=causal, scale=scale)
    my = jax.lax.axis_index(axis_name)
    scale = scale if scale is not None else d ** -0.5
    perm = [(j, (j + 1) % n) for j in range(n)]
    flash = functools.partial(flash_attention_with_lse, scale=scale,
                              block_q=block_q, block_k=block_k,
                              out_dtype=jnp.float32)

    def hop_full(args):
        k_c, v_c = args
        return flash(q, k_c, v_c, causal=False)

    def hop_diag(args):
        k_c, v_c = args
        return flash(q, k_c, v_c, causal=True)

    def hop_skip(args):
        return (jnp.zeros((b, s_loc, h, d), jnp.float32),
                jnp.full((b, s_loc, h), NEG_INF, jnp.float32))

    def step(carry, i):
        k_c, v_c, lse_acc, o_acc = carry
        # After i forward rotations we hold the chunk originally on (my - i).
        kv_idx = (my - i) % n
        if causal:
            case = jnp.where(kv_idx == my, 2,
                             jnp.where(kv_idx < my, 1, 0))
            o_c, lse_c = jax.lax.switch(
                case, [hop_skip, hop_full, hop_diag], (k_c, v_c))
        else:
            o_c, lse_c = hop_full((k_c, v_c))
        lse_new = jnp.logaddexp(lse_acc, lse_c)
        o_acc = (o_acc * jnp.exp(lse_acc - lse_new)[..., None]
                 + o_c * jnp.exp(lse_c - lse_new)[..., None])
        k_c, v_c = jax.lax.ppermute((k_c, v_c), axis_name, perm)
        return (k_c, v_c, lse_new, o_acc), None

    lse0 = jnp.full((b, s_loc, h), NEG_INF, jnp.float32)
    o0 = jnp.zeros((b, s_loc, h, d), jnp.float32)
    (_, _, _, o_acc), _ = jax.lax.scan(
        step, (k, v, lse0, o0), jnp.arange(n))
    return o_acc.astype(q.dtype)


def ring_attention_sharded(mesh: Mesh, q: jax.Array, k: jax.Array,
                           v: jax.Array, causal: bool = True,
                           scale: Optional[float] = None,
                           axis_name: str = "sp",
                           block_q: int = DEFAULT_BLOCK,
                           block_k: int = DEFAULT_BLOCK) -> jax.Array:
    """Global-array wrapper: [B, S, H, D] with S sharded over ``axis_name``,
    batch over (dp, fsdp), heads replicated along sp."""
    spec = P(("dcn_dp", "dp", "fsdp"), axis_name, None, None)
    fn = functools.partial(ring_attention, axis_name=axis_name,
                           causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
