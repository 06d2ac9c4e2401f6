"""Pipeline parallelism: GPipe-style microbatched schedule over the ``pp``
mesh axis.

Nothing to cite in the reference — TonY has no tensor/pipeline/sequence
parallelism anywhere (SURVEY.md §2.3, verified absent); this is the genuinely
new TPU-first work the blueprint requires.

Design:
- All transformer blocks' params are **stacked on a leading "stage" axis**
  ``[n_layers, ...]`` sharded over ``pp`` (``DEFAULT_RULES`` maps
  ``stage → pp``). With ``n_layers % pp == 0``, jax.sharding hands each
  device a *contiguous* layer range — the classic stage assignment falls
  out of array sharding, no bespoke placement code.
- Inside ``shard_map`` each device scans its local ``[L/S, ...]`` params
  over its resident activation (``lax.scan`` — compiled once, not unrolled).
- The schedule is GPipe: split the local batch into M microbatches; at tick
  t, stage 0 injects microbatch t, every stage applies its layers to its
  resident activation, the last stage banks the finished microbatch
  ``t-(S-1)``, and activations rotate to the next stage via a single
  neighbour ``ppermute`` (pure ICI traffic; the ``pp`` axis is laid out so
  neighbours share links — mesh.py axis order). Total ticks ``M + S - 1``,
  bubble fraction ``(S-1)/(M+S-1)``.
- Embedding and the LM head run *outside* the shard_map, auto-sharded by
  jit like every other op. Composes with data parallelism: activations ride
  in sharded over ``(dp, fsdp)`` and stay that way inside (the shard_map
  covers those axes too, it just doesn't communicate over them).
- Backward is plain autodiff: ``ppermute``'s transpose is the reverse
  ppermute, so reverse-mode replays the schedule mirror-image — GPipe's
  backward pass without writing one. Per-layer ``jax.checkpoint`` keeps
  residency at O(activations · microbatch), not O(· full batch).

Why GPipe and not 1F1B — quantified, because the tradeoff is different on
TPU than in the papers:

- **The memory argument mostly disappears under remat.** 1F1B's benefit is
  capping in-flight microbatch stashes at S (stages) instead of M. With
  per-layer ``jax.checkpoint`` the stash per microbatch is only the stage
  boundary activation (``mb·seq·dim``), so the delta is
  ``(M−S)·mb·seq·dim·2 B`` — for the 8B flagship shape (mb=1, seq 8192,
  dim 4096, M=8, S=4) that is ~256 MB of 95 GB v5p HBM (<0.3%).
- **True 1F1B breaks SPMD uniformity where it counts.** Backward for
  microbatch t must start while t+1 is still in forward, which needs the
  last stage's lm_head+loss *inside* the tick loop. In a uniform SPMD
  program every stage would execute the head every tick (≈ +S× the head's
  ~10% FLOP share — +30% total at S=4); per-stage divergent programs are
  not expressible under one jit. GPipe's loop body is the same code on
  every stage every tick, and autodiff derives the mirror-image backward
  schedule from the ``ppermute`` transpose for free.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu.models.transformer import (Block, TransformerConfig,
                                         causal_lm_loss)

from tony_tpu.parallel.mesh import BATCH_AXES

PP_AXIS = "pp"


def init_pipeline_params(cfg: TransformerConfig, rng: jax.Array
                         ) -> Dict[str, Any]:
    """Params pytree with every block stacked on a leading stage axis:
    ``{"embedding", "blocks"[n_layers, ...], "final_norm", "lm_head"}``."""
    r_blocks, r_emb, r_head = jax.random.split(rng, 3)
    dummy_x = jnp.zeros((1, 8, cfg.dim), cfg.dtype)
    dummy_pos = jnp.zeros((1, 8), jnp.int32)
    block = Block(cfg)

    def init_one(r):
        return nn.meta.unbox(block.init(r, dummy_x, dummy_pos))["params"]

    blocks = jax.vmap(init_one)(jax.random.split(r_blocks, cfg.n_layers))
    head_init = nn.initializers.lecun_normal()
    return {
        "embedding": (jax.random.normal(
            r_emb, (cfg.vocab_size, cfg.dim), cfg.param_dtype) * 0.02),
        "blocks": blocks,
        "final_norm": jnp.ones((cfg.dim,), cfg.param_dtype),
        "lm_head": head_init(r_head, (cfg.dim, cfg.vocab_size),
                             cfg.param_dtype),
    }


def _block_fsdp_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Per-leaf index of the dimension to shard over ``fsdp`` in a block's
    params (the dim whose logical name maps to fsdp under DEFAULT_RULES —
    i.e. ``embed``), or None for leaves without one (norm scales). Indices
    are for the UNSTACKED leaf; the stacked stage axis goes in front."""
    from tony_tpu.parallel.sharding import DEFAULT_RULES

    rules = dict(DEFAULT_RULES)
    block = Block(cfg)
    dummy_x = jnp.zeros((1, 8, cfg.dim), cfg.dtype)
    dummy_pos = jnp.zeros((1, 8), jnp.int32)
    boxed = jax.eval_shape(block.init, jax.random.key(0), dummy_x,
                           dummy_pos)["params"]
    spec_tree = nn.get_partition_spec(boxed)

    def leaf_axis(spec):
        # -1 = no fsdp dim (None would vanish from the pytree structure)
        if not isinstance(spec, P):
            return -1
        for i, name in enumerate(spec):
            if name is not None and rules.get(name) == "fsdp":
                return i
        return -1

    return jax.tree.map(leaf_axis, spec_tree,
                        is_leaf=lambda x: isinstance(x, P) or x is None)


def _block_specs(fsdp_axes: Any, blocks: Any) -> Any:
    """PartitionSpecs for the stacked block leaves: stage axis over ``pp``
    plus each leaf's fsdp dim. Single source for BOTH the at-rest param
    shardings and the shard_map in_specs — if they diverged, shard_map
    would silently force a full reshard on entry."""
    def leaf_spec(ax, leaf):
        spec = [PP_AXIS] + [None] * (leaf.ndim - 1)
        if ax >= 0:
            spec[ax + 1] = "fsdp"
        return P(*spec)

    return jax.tree.map(leaf_spec, fsdp_axes, blocks)


def pipeline_param_shardings(mesh: Mesh, params: Dict[str, Any],
                             cfg: Optional[TransformerConfig] = None
                             ) -> Dict[str, Any]:
    """Composed shardings: stacked blocks over ``pp`` on the stage axis AND
    ``fsdp`` on each leaf's embed dim (gathered just-in-time inside the
    stage loop — see ``_stage_apply``); embedding/lm_head/final_norm —
    exactly the tensors that dominate memory at 8B scale — shard over
    fsdp/tp outside the shard_map. With fsdp>1, no leaf of the pipeline
    state is fully replicated."""
    if cfg is not None:
        spec_tree = _block_specs(_block_fsdp_axes(cfg), params["blocks"])
    else:   # stage-only sharding (no fsdp composition)
        spec_tree = jax.tree.map(lambda _: P(PP_AXIS), params["blocks"])
    # Embedding sharded on the VOCAB dim: an embed-sharded table makes the
    # lookup's output embed-sharded, which SPMD can only reshard to the
    # batch-sharded activations by full rematerialization (see
    # models/transformer.py embedding comment; XLA b/433785288).
    return {
        "embedding": NamedSharding(mesh, P("fsdp", None)),
        "blocks": jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                               is_leaf=lambda x: isinstance(x, P)),
        "final_norm": NamedSharding(mesh, P("fsdp")),
        "lm_head": NamedSharding(mesh, P("fsdp", "tp")),
    }


def _stage_apply(cfg: TransformerConfig, fsdp_axes: Any, n_fsdp: int,
                 stage_params: Any, x: jax.Array,
                 positions: jax.Array) -> jax.Array:
    """Apply this device's contiguous layer range ([L/S, ...] stacked).

    With fsdp>1 the stage's params arrive as fsdp-local chunks; each
    layer's weights are all-gathered just-in-time inside the (possibly
    remat'd) apply — so the gather is recomputed in backward instead of
    living as a residual, and its transpose is the FSDP reduce-scatter of
    the gradients. This is FSDP-in-PP: at rest every block leaf is sharded
    over pp×fsdp."""
    block = Block(cfg)

    def apply_one(p_local, h):
        if n_fsdp > 1:
            p_local = jax.tree.map(
                lambda a, ax: a if ax < 0 else lax.all_gather(
                    a, "fsdp", axis=ax, tiled=True),
                p_local, fsdp_axes)
        return block.apply({"params": p_local}, h, positions)

    if cfg.remat:
        apply_one = jax.checkpoint(apply_one, prevent_cse=False)

    def body(h, layer_params):
        return apply_one(layer_params, h), None

    x, _ = lax.scan(body, x, stage_params)
    return x


def _pipeline_blocks(cfg: TransformerConfig, num_microbatches: int,
                     fsdp_axes: Any, n_fsdp: int,
                     blocks_local: Any, x: jax.Array,
                     positions: jax.Array) -> jax.Array:
    """Per-shard GPipe loop (runs inside shard_map over pp + batch axes).

    ``x``: [B_local, S, D] embedded activations (replicated over pp);
    ``blocks_local``: this stage's [L/S, ...] param stack.
    """
    n_stages = lax.psum(1, PP_AXIS)
    stage = lax.axis_index(PP_AXIS)
    m = num_microbatches
    b_loc, seq, d = x.shape
    mb = b_loc // m
    mbs = x.reshape(m, mb, seq, d)
    pos_mb = positions[:mb]

    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    state0 = jnp.zeros_like(mbs[0])
    out0 = jnp.zeros_like(mbs)

    def tick(carry, t):
        state, out = carry
        inject = lax.dynamic_index_in_dim(
            mbs, jnp.clip(t, 0, m - 1), axis=0, keepdims=False)
        state = jnp.where(stage == 0, inject, state)
        state = _stage_apply(cfg, fsdp_axes, n_fsdp, blocks_local, state,
                             pos_mb)
        done_idx = t - (n_stages - 1)
        banked = lax.dynamic_update_index_in_dim(
            out, state, jnp.clip(done_idx, 0, m - 1), axis=0)
        out = jnp.where((stage == n_stages - 1) & (done_idx >= 0),
                        banked, out)
        state = lax.ppermute(state, PP_AXIS, perm)
        return (state, out), None

    (_, out), _ = lax.scan(tick, (state0, out0),
                           jnp.arange(m + n_stages - 1))
    # Only the last stage holds non-zero outputs; psum replicates them over
    # pp so the head (outside the shard_map) sees a well-defined array.
    out = lax.psum(out, PP_AXIS)
    return out.reshape(b_loc, seq, d)


def pipeline_forward(cfg: TransformerConfig, mesh: Mesh,
                     params: Dict[str, Any], tokens: jax.Array,
                     num_microbatches: int = 2) -> jax.Array:
    """Causal-LM forward with the block stack pipelined over ``pp``.

    tokens [B, S] (B sharded over dp·fsdp; B/(dp·fsdp) must divide evenly
    into ``num_microbatches``) → logits [B, S, vocab] f32.
    """
    if cfg.n_layers % mesh.shape[PP_AXIS]:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pp="
            f"{mesh.shape[PP_AXIS]}")
    if tokens.shape[1] > cfg.max_seq_len:
        raise ValueError(f"seq {tokens.shape[1]} > max {cfg.max_seq_len}")
    x = params["embedding"][tokens].astype(cfg.dtype)
    positions = jnp.broadcast_to(
        jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :], tokens.shape)

    n_fsdp = mesh.shape.get("fsdp", 1)
    if n_fsdp > 1:
        fsdp_axes = _block_fsdp_axes(cfg)
    else:
        fsdp_axes = jax.tree.map(lambda _: -1, params["blocks"])
    blocks_spec = _block_specs(fsdp_axes, params["blocks"])

    fn = functools.partial(_pipeline_blocks, cfg, num_microbatches,
                           fsdp_axes, n_fsdp)
    x = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(blocks_spec, P(BATCH_AXES), P(BATCH_AXES)),
        out_specs=P(BATCH_AXES), check_vma=False,
    )(params["blocks"], x, positions)

    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    xf = xf * lax.rsqrt(var + cfg.norm_eps) * params["final_norm"]
    return xf @ params["lm_head"].astype(jnp.float32)


def pipeline_loss(cfg: TransformerConfig, mesh: Mesh, params: Dict[str, Any],
                  tokens: jax.Array, num_microbatches: int = 2) -> jax.Array:
    logits = pipeline_forward(cfg, mesh, params, tokens, num_microbatches)
    return causal_lm_loss(logits, tokens)
