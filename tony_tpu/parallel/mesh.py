"""Device-mesh construction over TPU ICI/DCN topology.

The reference framework's unit of placement is the YARN container matched to a
task by priority (``TonySession.java:208``); tensors never cross its mind. Here
the unit of placement is a **mesh axis**: every parallelism strategy is a named
axis of a `jax.sharding.Mesh`, and XLA inserts the collectives (psum /
all_gather / reduce_scatter / ppermute) that ride ICI within a slice and DCN
across slices.

Axis order encodes the physical hierarchy (scaling-book recipe): the outermost
axes change slowest across the device array, so we put DCN-friendly,
low-traffic axes (``dp``, then ``pp``) outermost and bandwidth-hungry axes
(``tp``) innermost where neighbours share ICI links.

Axes:
    dp    pure data parallelism (gradient psum only — cheapest, DCN-safe)
    fsdp  data parallelism with sharded params/optimizer (all_gather weights)
    pp    pipeline stages (point-to-point ppermute between neighbours)
    ep    expert parallelism for MoE (all_to_all dispatch)
    sp    sequence/context parallelism (ring ppermute / all_to_all)
    tp    tensor parallelism (activation all_reduce every layer — ICI only)
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu import telemetry

# Outermost (slow, DCN-tolerant) → innermost (fast, wants ICI neighbours).
# ``dcn_dp`` is the multislice axis: pure data parallelism ACROSS slices,
# whose only collective (the gradient psum) is the one thing DCN bandwidth
# can afford — every other axis stays inside a slice on ICI. Size 1 on a
# single slice, so single-slice code never notices it.
MESH_AXES = ("dcn_dp", "dp", "fsdp", "pp", "ep", "sp", "tp")
# Every axis that consumes the batch dim — the single source of truth
# (rules, pipeline, data pipeline all import this).
BATCH_AXES = ("dcn_dp", "dp", "fsdp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Sizes for each mesh axis. At most one axis may be -1 (inferred so the
    product equals the device count). Unused axes stay 1 — they are kept in
    the mesh so sharding rules are uniform across strategies."""

    dcn_dp: int = 1
    dp: int = -1
    fsdp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1

    def sizes(self) -> Sequence[int]:
        return tuple(getattr(self, a) for a in MESH_AXES)

    def resolve(self, n_devices: int) -> "MeshSpec":
        sizes = list(self.sizes())
        bad = [s for s in sizes if s < 1 and s != -1]
        if bad:
            raise ValueError(
                f"axis sizes must be positive or -1 (inferred), got {self}")
        unknown = [i for i, s in enumerate(sizes) if s == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one axis may be -1, got {self}")
        known = math.prod(s for s in sizes if s != -1)
        if unknown:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {known} in {self}")
            sizes[unknown[0]] = n_devices // known
        elif known != n_devices:
            raise ValueError(
                f"mesh {self} wants {known} devices, have {n_devices}")
        return MeshSpec(**dict(zip(MESH_AXES, sizes)))

    def respec(self, n_devices: int) -> "MeshSpec":
        """Re-solve this spec for a NEW device count — the elastic
        shrink/grow recipe (coordinator/elastic.py): the model axes
        (fsdp/pp/ep/sp/tp) keep their shapes so saved shards stay
        compatible, and the pure-data axis ``dp`` absorbs the delta.
        Raises when the fixed axes don't divide the new count (shrink
        below the model-parallel footprint needs a different spec)."""
        d = dict(zip(MESH_AXES, self.sizes()))
        d["dp"] = -1
        return MeshSpec(**d).resolve(n_devices)

    @classmethod
    def from_string(cls, s: str) -> "MeshSpec":
        """Parse ``"dp=2,tp=4"`` — the config-file form
        (key ``tony.tpu.mesh-shape``, see ``conf/keys.py``)."""
        kwargs = {}
        for part in filter(None, (p.strip() for p in s.split(","))):
            k, sep, v = part.partition("=")
            if k not in MESH_AXES:
                raise ValueError(f"unknown mesh axis {k!r} (not in "
                                 f"{MESH_AXES})")
            if not sep or not v.lstrip("-").isdigit():
                raise ValueError(
                    f"expected axis=size in {part!r} (e.g. 'tp=4')")
            kwargs[k] = int(v)
        if "dp" not in kwargs:
            kwargs["dp"] = -1
        return cls(**kwargs)


def build_mesh(spec: Optional[MeshSpec] = None,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh whose axis layout respects physical topology.

    On real TPU slices `mesh_utils.create_device_mesh` maps axes onto the
    torus so innermost axes land on ICI neighbours; on a host-platform
    (CPU test) mesh the devices are virtual and a plain reshape suffices.
    """
    if devices is None:
        # The call that brings the backend (libtpu) up in a job that lets
        # the mesh find its devices: a boot span of the user process.
        with telemetry.span("user.backend_init"):
            devices = jax.devices()
    devices = list(devices)
    spec = (spec or MeshSpec()).resolve(len(devices))
    sizes = spec.sizes()
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils
        if spec.dcn_dp > 1:
            # Multislice: per-slice axes laid out on each slice's torus,
            # dcn_dp across slices (grouped by device.slice_index).
            dev_array = mesh_utils.create_hybrid_device_mesh(
                (1,) + tuple(sizes[1:]),
                (spec.dcn_dp,) + (1,) * (len(sizes) - 1),
                devices=devices)
        else:
            dev_array = mesh_utils.create_device_mesh(sizes,
                                                      devices=devices)
    else:
        # Virtual/CPU: contiguous groups stand in for slices.
        dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, MESH_AXES)


@functools.lru_cache(maxsize=256)
def _cached_batch_sharding(mesh: Mesh, extra_dims: int) -> NamedSharding:
    return NamedSharding(mesh, P(BATCH_AXES, *([None] * extra_dims)))


def batch_sharding(mesh: Mesh, extra_dims: int = 1) -> NamedSharding:
    """Sharding for a [batch, ...] input: batch split over every
    data-parallel-ish axis (dcn_dp, dp and fsdp all consume batch).
    Memoized per (mesh, extra_dims): large batch pytrees map every leaf
    through here on the submit path, and NamedSharding construction is
    not free — identical requests return the same object."""
    return _cached_batch_sharding(mesh, extra_dims)


def tree_batch_shardings(mesh: Mesh, sample_batch: Any) -> Any:
    """Per-leaf batch shardings for a whole batch pytree: [batch, ...]
    leaves split over the batch axes, scalar (0-d) leaves replicated —
    the one shared recipe for ``jit_train_step`` and the grad-sync accum
    step. Shardings are memoized per (mesh, ndim), so a batch tree with
    thousands of leaves pays for at most a handful of constructions."""
    import jax.numpy as jnp

    replicated = replicated_sharding(mesh)
    return jax.tree.map(
        lambda leaf: (batch_sharding(mesh, extra_dims=jnp.ndim(leaf) - 1)
                      if jnp.ndim(leaf) > 0 else replicated),
        sample_batch)


@functools.lru_cache(maxsize=256)
def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
