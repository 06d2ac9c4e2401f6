"""Helpers over the mesh that ``jax.set_mesh`` binds (jax 0.9.0, the one
pinned installation — see pyproject.toml).

Model and ops code never receives a mesh argument: it reads the ambient
abstract mesh (``jax.sharding.get_abstract_mesh()``) that the library's
jitted steps bind with ``jax.set_mesh``, and falls back to its unsharded
path when none is bound (``model.init`` under ``eval_shape``, eager unit
tests). The three questions such code asks live here, once:

- ``mesh_axis_size``: how large is a named axis of the bound mesh;
- ``partial_shard_map``: manual collectives over ONE axis, every other
  mesh axis left to the partitioner (the MoE expert exchange);
- ``per_shard``: run a Pallas kernel on each device's own shard — the
  TPU compiler refuses to partition a Mosaic kernel automatically.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional, Sequence

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["current_mesh", "mesh_axis_size", "partial_shard_map",
           "per_shard"]


def current_mesh() -> Optional[Any]:
    """The abstract mesh bound by ``jax.set_mesh`` (inside a ``shard_map``:
    the same mesh with the mapped axes typed Manual), or None outside any
    mesh context."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def mesh_axis_size(axis_name: str) -> int:
    """Size of ``axis_name`` on the currently bound mesh; 1 when no mesh
    is bound or the mesh has no such axis (the unsharded path)."""
    m = current_mesh()
    return 1 if m is None else int(m.shape.get(axis_name, 1))


def partial_shard_map(fn, axis_name: str, in_specs, out_specs):
    """``shard_map`` manual over exactly ``axis_name``; every other axis of
    the bound mesh stays automatic (partial-manual collectives — the MoE
    expert-exchange shape). Must run under ``jax.set_mesh``."""
    return jax.shard_map(fn, axis_names={axis_name},
                         in_specs=in_specs, out_specs=out_specs)


def per_shard(fn: Callable, dim_axes: Sequence[Sequence[str]]) -> Callable:
    """Wrap a Pallas kernel call so each device runs it on its own shard.

    XLA:TPU cannot partition a Mosaic custom call ("Mosaic kernels cannot
    be automatically partitioned"), so a kernel traced into a jitted step
    over a multi-device mesh must sit inside a ``shard_map``. ``fn`` takes
    and returns arrays whose leading dims are all laid out alike;
    ``dim_axes[d]`` names the mesh axes dim ``d`` may be split over (e.g.
    ``(BATCH_AXES, ("tp",))`` for ``[batch, heads, ...]`` operands). A dim
    is split only when every operand's extent divides the axes' size —
    otherwise it stays whole and the shards compute it redundantly, which
    is what the partitioner would do with an unannotated operand.

    The map is manual over EVERY axis of the bound mesh that is not manual
    already (size-1 axes included: the compiler rejects the kernel under
    any remaining automatic axis). With no mesh bound, or inside a
    ``shard_map`` that already covers the whole mesh (ring, Ulysses,
    pipeline stages), ``fn`` runs as is.
    """
    def wrapped(*args):
        mesh = current_mesh()
        auto = () if mesh is None else tuple(
            a for a in mesh.axis_names if a not in mesh.manual_axes)
        if not auto:
            return fn(*args)
        spec = []
        for d, axes in enumerate(dim_axes):
            axes = tuple(a for a in axes if a in auto)
            n = math.prod(mesh.shape[a] for a in axes)
            split = n > 1 and all(x.shape[d] % n == 0 for x in args)
            spec.append(axes if split else None)
        # check_vma off: pallas_call outputs carry no varying-axes type.
        return jax.shard_map(fn, axis_names=set(auto), in_specs=P(*spec),
                             out_specs=P(*spec), check_vma=False)(*args)

    return wrapped
