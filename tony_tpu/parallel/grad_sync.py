"""Overlapped, bucketed cross-slice gradient synchronization.

``jit_train_step`` (parallel/train.py) compiles forward, backward and the
gradient reduction into ONE XLA program — correct, but the cross-slice
(``dcn_dp``) all-reduce then materializes as a single monolithic psum that
XLA schedules strictly behind the whole backward pass, and nothing on the
host can attribute the time it takes: the DCN wait books as
``step_compute`` and a COMMS_BOUND job looks healthy (the exact blind spot
docs/operations.md called out). This module replaces that monolith with
the structure DDP-style systems use:

1. **Microbatched accumulation** (``tony.train.accum-steps``): the global
   batch is split into A microbatches scanned inside one program; grads
   accumulate locally, so the cross-slice sync runs once per A backward
   passes — the compute:DCN ratio rises A-fold.
2. **Per-slice gradients, explicitly.** Instead of letting XLA insert the
   batch-axis reduction, the accumulate program computes grads *per sync
   slice* (``jax.vmap`` over a leading slice dim sharded over the sync
   axes) and returns them UNSYNCED — the cross-slice reduction has not
   happened yet when the program ends.
3. **Bucketed, order-stable sync** (``tony.train.bucket-mb``): the sync
   program flattens the stacked grads in tree order, packs them into
   ≤bucket-MiB buckets (a param bigger than the bucket spills into its
   own), and mean-reduces each bucket over the slice dim — one
   independent all-reduce per bucket that XLA's async collectives can
   overlap, instead of one serialized monolith. Packing order is the
   tree-flatten order both here and in the split-back, so the result is
   deterministic and allclose to the monolithic psum.
4. **An attributable comms phase.** Because the sync is its own dispatch,
   the host wraps it in ``telemetry.phase("comms")`` anchored with
   ``block_until_ready`` — the dcn_dp MULTICHIP dryrun and any
   instrumented job finally report a real comms fraction, and
   COMMS_BOUND verdicts point at knobs this module actually has.

The optimizer update runs in a third program on the synced grads. The
three dispatches are enqueued asynchronously; only the comms phase's
``block_until_ready`` synchronizes (and that is the measurement).

Semantics note: ``loss_fn(params, batch, rng)`` must compute a MEAN over
its batch argument (the ``jit_train_step`` contract) — the mean of
per-slice/per-microbatch means then equals the global mean because every
piece is the same size (divisibility is checked loudly). The rng handed
to each microbatch/slice is a distinct fold of the step rng, so an
rng-using loss sees different draws than the monolithic step; the
equivalence guarantee is for the batch-determined gradient.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, List, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu import telemetry
from tony_tpu.parallel.mesh import (BATCH_AXES, replicated_sharding,
                                    tree_batch_shardings)
from tony_tpu.parallel.sharding import DEFAULT_RULES

#: default bucket size (MiB) — matches tony.train.bucket-mb's default.
DEFAULT_BUCKET_MB = 32
#: axes the explicit sync path reduces over; dcn_dp is the multislice
#: axis the whole design aims at, dp rides along where it exists so the
#: in-slice gradient reduction buckets/overlaps the same way.
DEFAULT_SYNC_AXES = ("dcn_dp", "dp")


@dataclasses.dataclass(frozen=True)
class GradSyncSpec:
    """The conf-shaped knobs (``tony.train.*``) in one carryable value."""

    accum_steps: int = 1
    bucket_mb: int = DEFAULT_BUCKET_MB
    matmul_dtype: str = ""

    @classmethod
    def from_conf(cls, conf) -> "GradSyncSpec":
        from tony_tpu.conf import keys as K

        return cls(
            accum_steps=max(1, conf.get_int(K.TRAIN_ACCUM_STEPS, 1)),
            bucket_mb=max(1, conf.get_int(K.TRAIN_BUCKET_MB,
                                          DEFAULT_BUCKET_MB)),
            matmul_dtype=str(conf.get(K.TRAIN_MATMUL_DTYPE, "") or ""))


def plan_buckets(leaf_descs: Sequence[Tuple[Tuple[int, ...], Any]],
                 bucket_mb: int = DEFAULT_BUCKET_MB) -> List[List[int]]:
    """Order-stable bucket plan over flattened grad leaves.

    ``leaf_descs`` is ``[(shape, dtype), ...]`` in tree-flatten order;
    returns a list of buckets, each a list of leaf indices. Greedy in
    order — never reorders leaves, so packing and split-back agree and
    the reduction is deterministic. A bucket closes when it would exceed
    ``bucket_mb`` or when the dtype changes (mixed-dtype grads are never
    silently upcast into one flat buffer). A single leaf larger than the
    bucket gets a bucket of its own (the one-param-spills edge)."""
    cap = max(1, int(bucket_mb)) << 20
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    cur_dtype = None
    for i, (shape, dtype) in enumerate(leaf_descs):
        dt = jnp.dtype(dtype)
        nbytes = math.prod(shape) * dt.itemsize
        if cur and (dt != cur_dtype or cur_bytes + nbytes > cap):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nbytes
        cur_dtype = dt
    if cur:
        buckets.append(cur)
    return buckets


def bucketed_sync(stacked: Any,
                  bucket_mb: int = DEFAULT_BUCKET_MB,
                  part_sharding: Any = None) -> Any:
    """Mean-reduce per-slice stacked grads ``[n_sync, ...]`` over the
    leading (sync-axes-sharded) dim, bucket by bucket. Jittable; each
    bucket's reduction is an independent collective under SPMD. Returns
    the grads tree without the leading dim — allclose to the monolithic
    psum (same addends, deterministic packing order).

    ``part_sharding`` (a NamedSharding for a [n_sync, elems] part,
    normally ``P(sync_axes, None)``) pins every flattened bucket member
    to ONE layout before packing. On a sharded mesh this is required,
    not cosmetic: grad leaves arrive with heterogeneous layouts
    (fsdp/tp-sharded kernels next to replicated norm scales), and
    concatenating mixed-sharding operands both miscompiles on older jax
    (verified on 0.4.37's CPU SPMD) and would make XLA reshard the
    bucket mid-collective anyway — slice-sharded/replicated-within is
    the layout the DCN all-reduce wants."""
    leaves, treedef = jax.tree.flatten(stacked)
    if not leaves:
        return stacked
    n = leaves[0].shape[0]
    plan = plan_buckets([(l.shape[1:], l.dtype) for l in leaves],
                        bucket_mb)
    out: List[Any] = [None] * len(leaves)

    def flat_part(leaf):
        part = leaf.reshape(n, -1)
        if part_sharding is not None:
            part = jax.lax.with_sharding_constraint(part, part_sharding)
        return part

    for bucket in plan:
        if len(bucket) == 1:
            i = bucket[0]
            inv = jnp.asarray(1.0 / n, leaves[i].dtype)
            out[i] = jnp.sum(leaves[i], axis=0) * inv
            continue
        flat = jnp.concatenate([flat_part(leaves[i]) for i in bucket],
                               axis=1)
        red = jnp.sum(flat, axis=0) * jnp.asarray(1.0 / n, flat.dtype)
        off = 0
        for i in bucket:
            shape = leaves[i].shape[1:]
            size = math.prod(shape)
            out[i] = red[off:off + size].reshape(shape)
            off += size
    return treedef.unflatten(out)


def monolithic_grads(loss_fn: Callable, params: Any, batch: Any,
                     rng: jax.Array,
                     rules: Sequence[Tuple[str, Any]] = DEFAULT_RULES
                     ) -> Any:
    """The reference the bucketed path is tested against: one global-mean
    loss, XLA's own end-of-backward reduction. Call under jit/set_mesh."""
    def global_loss(p):
        with nn.logical_axis_rules(list(rules)):
            loss, _ = loss_fn(p, batch, rng)
        return loss

    return jax.grad(global_loss)(params)


def _sync_sizes(mesh: Mesh, sync_axes: Sequence[str]) -> int:
    shape = dict(mesh.shape)
    bad = [a for a in sync_axes if a not in shape]
    if bad:
        raise ValueError(f"sync axes {bad} not in mesh axes "
                         f"{sorted(shape)}")
    not_batch = [a for a in sync_axes if a not in BATCH_AXES]
    if not_batch:
        raise ValueError(
            f"sync axes must be pure data-parallel batch axes "
            f"(params replicated over them); {not_batch} are not in "
            f"{BATCH_AXES}")
    return math.prod(shape[a] for a in sync_axes)


def stacked_grad_shardings(mesh: Mesh, param_shardings: Any,
                           sync_axes: Sequence[str]) -> Any:
    """Shardings for the stacked per-slice grads: each param leaf's spec
    gains a leading dim split over the sync axes."""
    axes = tuple(sync_axes)

    def one(sh):
        spec = tuple(sh.spec) if isinstance(sh, NamedSharding) else ()
        return NamedSharding(mesh, P(axes, *spec))

    return jax.tree.map(one, param_shardings)


def _build_accum_fn(loss_fn: Callable, mesh: Mesh, accum_steps: int,
                    n_sync: int, sync_axes: Tuple[str, ...],
                    rules: Sequence[Tuple[str, Any]]):
    """accum(params, batch, rng) -> (stacked_grads, loss, aux): scan A
    microbatches, vmap per sync slice, accumulate locally — no
    cross-slice collective anywhere in this program."""
    local_axes = tuple(a for a in BATCH_AXES if a not in sync_axes)

    def ruled_loss(p, b, r):
        with nn.logical_axis_rules(list(rules)):
            return loss_fn(p, b, r)

    def accum(params, batch, rng):
        leaves, treedef = jax.tree.flatten(batch)
        is_scalar = [jnp.ndim(l) == 0 for l in leaves]
        batched = []
        for leaf, scalar in zip(leaves, is_scalar):
            if scalar:
                continue
            gb = leaf.shape[0]
            if gb % (n_sync * accum_steps):
                raise ValueError(
                    f"global batch {gb} not divisible by "
                    f"sync slices ({n_sync} over {sync_axes}) x "
                    f"tony.train.accum-steps ({accum_steps})")
            local = gb // (n_sync * accum_steps)
            x = leaf.reshape((n_sync, accum_steps, local)
                             + leaf.shape[1:])
            x = jnp.moveaxis(x, 1, 0)       # [A, n_sync, local, ...]
            x = jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(None, sync_axes,
                                         local_axes or None,
                                         *([None] * (leaf.ndim - 1)))))
            batched.append(x)
        scalars = [l for l, s in zip(leaves, is_scalar) if s]

        def rebuild(micro_batched):
            it_b = iter(micro_batched)
            it_s = iter(scalars)
            return treedef.unflatten(
                [next(it_s) if s else next(it_b) for s in is_scalar])

        vmap_axes = treedef.unflatten(
            [None if s else 0 for s in is_scalar])
        keys = jax.random.split(rng, accum_steps * n_sync)
        keys = keys.reshape((accum_steps, n_sync) + keys.shape[1:])

        grad_one = jax.vmap(
            jax.value_and_grad(ruled_loss, has_aux=True),
            in_axes=(None, vmap_axes, 0))

        zeros = jax.tree.map(
            lambda p: jnp.zeros((n_sync,) + p.shape, p.dtype), params)

        def body(acc, xs):
            ks, micro = xs
            (l, aux), g = grad_one(params, rebuild(list(micro)), ks)
            return jax.tree.map(jnp.add, acc, g), (l, aux)

        stacked, (losses, auxes) = jax.lax.scan(
            body, zeros, (keys, tuple(batched)))
        stacked = jax.tree.map(
            lambda g: g * jnp.asarray(1.0 / accum_steps, g.dtype),
            stacked)
        loss = jnp.mean(losses)
        aux = jax.tree.map(jnp.mean, auxes)
        return stacked, loss, aux

    return accum


def jit_train_step_accum(
    loss_fn: Callable[[Any, Any, jax.Array], Tuple[jax.Array, dict]],
    mesh: Mesh,
    state_shardings: Any,
    sample_batch: Any,
    *,
    accum_steps: int = 1,
    bucket_mb: int = DEFAULT_BUCKET_MB,
    sync_axes: Sequence[str] = DEFAULT_SYNC_AXES,
    rules: Sequence[Tuple[str, Any]] = DEFAULT_RULES,
    donate: bool = True,
    comms_phase: bool = True,
):
    """The grad-sync twin of ``jit_train_step``: same signature for the
    returned ``step(state, batch, rng) -> (state, metrics)``, but the
    gradient path is microbatched (``accum_steps``), explicitly
    cross-slice-synced bucket-by-bucket (``bucket_mb`` MiB over
    ``sync_axes``), and the sync dispatch is wrapped in
    ``telemetry.phase("comms")`` so the DCN wait is attributable.

    ``sync_axes`` defaults to ``("dcn_dp", "dp")`` — the pure
    data-parallel axes over which params are replicated (``fsdp`` stays
    with XLA's automatic reduction: its params are sharded, so the
    per-slice vmap would replicate them). Axes of size 1 cost nothing.
    """
    sync_axes = tuple(sync_axes)
    n_sync = _sync_sizes(mesh, sync_axes)
    accum_steps = max(1, int(accum_steps))

    param_sh = state_shardings.params
    stacked_sh = stacked_grad_shardings(mesh, param_sh, sync_axes)
    batch_sh = tree_batch_shardings(mesh, sample_batch)
    rep = replicated_sharding(mesh)

    accum_jit = jax.jit(
        _build_accum_fn(loss_fn, mesh, accum_steps, n_sync, sync_axes,
                        rules),
        in_shardings=(param_sh, batch_sh, rep),
        out_shardings=(stacked_sh, rep, rep))

    # No donation here: the [n_sync, ...] inputs can never alias the
    # reduced outputs (different shapes), so donating would only emit
    # XLA's unusable-donation warning on every compile.
    part_sh = NamedSharding(mesh, P(sync_axes, None))
    sync_jit = jax.jit(
        lambda stacked: bucketed_sync(stacked, bucket_mb,
                                      part_sharding=part_sh),
        in_shardings=(stacked_sh,),
        out_shardings=param_sh)

    def apply_fn(state, grads, loss, aux):
        new_state = state.apply_gradients(grads)
        metrics = {"loss": loss, "step": new_state.step, **aux}
        return new_state, metrics

    apply_jit = jax.jit(
        apply_fn,
        in_shardings=(state_shardings, param_sh, rep, rep),
        out_shardings=(state_shardings, rep),
        donate_argnums=(0, 1) if donate else ())

    def step(state, batch, rng):
        with jax.set_mesh(mesh):
            stacked, loss, aux = accum_jit(state.params, batch, rng)
            if comms_phase:
                with telemetry.phase("comms") as p:
                    grads = sync_jit(stacked)
                    p.block_until_ready(grads)
            else:
                grads = sync_jit(stacked)
            return apply_jit(state, grads, loss, aux)

    return step
