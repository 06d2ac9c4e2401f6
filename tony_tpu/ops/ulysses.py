"""Ulysses-style sequence parallelism: all-to-all head/sequence swap.

The other long-context pattern (SURVEY.md §5): instead of rotating K/V chunks
(ring), transpose the sharding — two ``all_to_all`` collectives swap a
sequence-sharded layout [B, S/n, H, D] into a head-sharded layout
[B, S, H/n, D], run *full-sequence* attention locally on each device's head
group (using the Pallas flash kernel), then swap back. Communication is two
all-to-alls regardless of sequence length, which beats the ring when heads
divide evenly and the per-device full sequence fits HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from tony_tpu.ops.attention import DEFAULT_BLOCK, flash_attention


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str = "sp", causal: bool = True,
                      scale: Optional[float] = None,
                      block_q: int = DEFAULT_BLOCK,
                      block_k: int = DEFAULT_BLOCK,
                      window: Optional[int] = None) -> jax.Array:
    """Per-shard Ulysses attention ([B, S_local, H, D] in/out), for use
    inside shard_map. Requires both q and k/v head counts divisible by the
    axis size."""

    from tony_tpu.ops.ring import bound_axis_size, refuse_window

    refuse_window("ulysses", window)
    if bound_axis_size(axis_name) is None:
        # No axes bound at all (model init / single-shard apply): no swap.
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k)

    def seq_to_heads(x):
        # [B, S/n, H, D] → [B, S, H/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    oh = flash_attention(qh, kh, vh, causal=causal, scale=scale,
                         block_q=block_q, block_k=block_k)
    return heads_to_seq(oh)


def ulysses_attention_sharded(mesh: Mesh, q: jax.Array, k: jax.Array,
                              v: jax.Array, causal: bool = True,
                              scale: Optional[float] = None,
                              axis_name: str = "sp",
                              block_q: int = DEFAULT_BLOCK,
                              block_k: int = DEFAULT_BLOCK) -> jax.Array:
    """Global-array wrapper: [B, S, H, D] with S sharded over ``axis_name``."""
    n = mesh.shape[axis_name]
    if q.shape[2] % n or k.shape[2] % n:
        raise ValueError(f"Ulysses needs q heads ({q.shape[2]}) and kv "
                         f"heads ({k.shape[2]}) divisible by the "
                         f"{axis_name!r} axis size ({n}); use ring "
                         f"attention instead")
    spec = P(("dcn_dp", "dp", "fsdp"), axis_name, None, None)
    fn = functools.partial(ulysses_attention, axis_name=axis_name,
                           causal=causal, scale=scale,
                           block_q=block_q, block_k=block_k)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
