"""Logical-axis sharding rules: name tensor dimensions, map names to mesh axes.

Models annotate weights with *logical* axis names (``embed``, ``mlp``,
``heads``…) via ``flax.linen.with_logical_partitioning``; one rules table maps
those names onto the physical mesh axes of `tony_tpu.parallel.mesh`. Changing
the parallelism strategy = changing the table, never the model. (The scaling
book's "annotate shardings, let XLA insert collectives" recipe.)

The reference has no analogue — its sharding story is "hand each task a
host:port list and hope the user framework sorts it out"
(``TonySession.java:226-246``).
"""

from __future__ import annotations

from typing import Any, Sequence, Tuple

import flax.linen as nn
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu.parallel.mesh import BATCH_AXES

# Logical name → mesh axis (or tuple of axes). Maxtext-style assignment:
# batch over dp+fsdp, params sharded over fsdp (FSDP) with the model
# dimension split over tp, sequence over sp.
DEFAULT_RULES: Tuple[Tuple[str, Any], ...] = (
    ("batch", BATCH_AXES),
    ("seq", "sp"),
    ("embed", "fsdp"),
    ("mlp", "tp"),
    ("heads", "tp"),
    ("kv_heads", "tp"),
    ("kv", None),
    ("qkv", None),
    ("vocab", "tp"),
    # Embedding-table dims (see models/transformer.py): vocab rows over
    # both model axes, embed dim whole — the gather then partitions as
    # masked-lookup + all-reduce instead of an embed-sharded output that
    # SPMD can only reshard by full rematerialization.
    ("vocab_table", ("tp", "fsdp")),
    ("embed_table", None),
    ("layers", None),
    ("stage", "pp"),
    ("expert", "ep"),
    ("expert_logits", None),
    ("norm", None),
    # A state-space mixer's columns, heads and conv taps stay whole: its
    # scan is not split over chips (ROADMAP M6').
    ("ssm_inner", None),
    ("ssm_heads", None),
    ("conv", None),
    # Likewise a linear-attention mixer's (models/kda.py), and a low-rank
    # product's narrow side (its gates' down-projections, latent attention's
    # shared latent).
    ("kda_inner", None),
    ("kda_heads", None),
    ("rank", None),
)


def with_rules(rules: Sequence[Tuple[str, Any]] = DEFAULT_RULES):
    """Context manager activating logical rules for flax's
    `with_logical_constraint` calls inside model code."""
    return nn.logical_axis_rules(rules)


def logical_sharding(mesh: Mesh, *logical_axes: str,
                     rules: Sequence[Tuple[str, Any]] = DEFAULT_RULES
                     ) -> NamedSharding:
    """NamedSharding for a tensor whose dims carry the given logical names."""
    spec = nn.logical_to_mesh_axes(logical_axes, rules=list(rules))
    return NamedSharding(mesh, spec)


def reshard(tree: Any, shardings: Any) -> Any:
    """Re-lay an in-memory pytree onto new shardings — the elastic
    re-mesh path when state survives in host memory rather than on disk
    (checkpoint restore covers the on-disk path: orbax's StandardRestore
    re-lays-out onto whatever mesh the target shardings name).
    ``jax.device_put`` moves each leaf shard-by-shard; cross-mesh moves
    stage through host where devices disagree, which is exactly the
    shrink/grow case."""
    return jax.tree.map(lambda x, s: jax.device_put(x, s), tree,
                        shardings)


def param_shardings(mesh: Mesh, abstract_tree: Any,
                    rules: Sequence[Tuple[str, Any]] = DEFAULT_RULES) -> Any:
    """Map a tree of flax ``Partitioned`` metadata (from ``jax.eval_shape`` of
    ``model.init``) to a tree of NamedShardings. Leaves without metadata are
    replicated."""
    spec_tree = nn.get_partition_spec(abstract_tree)
    logical = nn.logical_to_mesh(spec_tree, rules=list(rules))

    def to_sharding(spec):
        if not isinstance(spec, P):
            spec = P()
        return NamedSharding(mesh, spec)

    return jax.tree.map(
        to_sharding, logical,
        is_leaf=lambda x: isinstance(x, P) or x is None)
