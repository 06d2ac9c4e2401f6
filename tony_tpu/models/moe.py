"""Mixture-of-Experts transformer with expert parallelism over the ``ep``
mesh axis.

No reference analogue — TonY has no expert/model parallelism anywhere
(SURVEY.md §2.3, verified absent); this is TPU-first new work.

Design (GShard/Switch-style dense dispatch — the TPU-idiomatic formulation):
- Expert FFN weights are stacked ``[n_experts, ...]`` with logical axis
  ``expert → ep``; the router is a small replicated Dense.
- Dispatch/combine are **einsums against one-hot dispatch tensors**, not
  gather/scatter — dense MXU work instead of dynamic indexing the TPU
  can't tile (pallas_guide.md: avoid data-dependent shapes under jit;
  capacity-factor padding keeps every shape static).
- The expert exchange is an explicit ``lax.all_to_all`` pair inside a
  *partial-manual* ``shard_map`` over the ``ep`` axis only (dp/fsdp/tp
  stay auto): each ep shard routes its token group locally (GShard
  "groups" = ep shards, per-group capacity), ships expert-major slices to
  the expert owners over ICI, FFNs its resident experts, and ships results
  back. Token tensors never pass through an all-gather.
- Top-k routing (k configurable) with per-group per-expert capacity
  ``c = ceil(k·T_group/E · capacity_factor)``; tokens over capacity are
  dropped (their residual path passes through — standard Switch behaviour).
- Aux load-balancing loss (Switch eq. 4: E · Σ_e fraction_e · prob_e) is
  returned alongside the logits so the train loss can add it.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from tony_tpu import compat
from tony_tpu.models.transformer import (Attention, RMSNorm,
                                         TransformerConfig, remat_policy_of)


@dataclasses.dataclass(frozen=True)
class MoEConfig(TransformerConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01

    @classmethod
    def tiny_moe(cls, **kw) -> "MoEConfig":
        defaults = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, mlp_dim=128, max_seq_len=128,
                        dtype=jnp.float32, remat=False, n_experts=4,
                        top_k=2)
        defaults.update(kw)
        return cls(**defaults)


def _routed_ffn_group(cfg: MoEConfig, xt: jax.Array, probs: jax.Array,
                      w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array,
                      n_ep: int) -> jax.Array:
    """One routing group's expert FFN. ``xt``/``probs`` are the group's
    [T_g, D]/[T_g, E] slices; ``w_*`` are the E/n_ep resident experts'
    weights. Runs per-shard under shard_map when n_ep > 1."""
    t, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k
    capacity = max(k, int(math.ceil(k * t / e * cfg.capacity_factor)))

    gate_vals, gate_idx = jax.lax.top_k(probs, k)          # [T_g, k]
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

    # Position-in-expert with slot priority: slot 0 of every token beats
    # slot 1, earlier tokens beat later ones (deterministic, static).
    dispatch = jnp.zeros((t, e, capacity), cfg.dtype)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    offset = jnp.zeros((e,), jnp.int32)
    for slot in range(k):
        onehot = jax.nn.one_hot(gate_idx[:, slot], e, dtype=jnp.int32)
        loc = jnp.cumsum(onehot, axis=0) - 1 + offset[None, :]
        offset = offset + jnp.sum(onehot, axis=0)
        keep = (onehot > 0) & (loc < capacity)             # [T_g, E]
        loc_oh = jax.nn.one_hot(loc, capacity, dtype=jnp.float32)
        sel = keep[..., None] * loc_oh                     # [T_g, E, C]
        dispatch = dispatch + sel.astype(cfg.dtype)
        combine = combine + gate_vals[:, slot, None, None] * sel

    expert_in = jnp.einsum("tec,td->ecd", dispatch,
                           xt.astype(cfg.dtype))           # [E, c, D]
    if n_ep > 1:
        # Ship each expert's slots to its owner: [E, c, D] → split experts
        # into n_ep groups, concat received slot-chunks → [E/n_ep, n_ep·c, D].
        expert_in = jax.lax.all_to_all(expert_in, EP_AXIS, split_axis=0,
                                       concat_axis=1, tiled=True)
    h = nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, w_gate)) \
        * jnp.einsum("ecd,edf->ecf", expert_in, w_up)
    expert_out = jnp.einsum("ecf,efd->ecd", h, w_down)
    if n_ep > 1:
        # Ship results back slot-major: [E/n_ep, n_ep·c, D] → [E, c, D].
        expert_out = jax.lax.all_to_all(expert_out, EP_AXIS, split_axis=1,
                                        concat_axis=0, tiled=True)
    return jnp.einsum("tec,ecd->td", combine.astype(cfg.dtype), expert_out)


EP_AXIS = "ep"


class MoEMLP(nn.Module):
    """Top-k routed expert FFN (gated-silu experts, like the dense MLP)."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        b, s, d = x.shape
        t = b * s
        e = cfg.n_experts

        xt = x.reshape(t, d)
        # Router in f32: stability matters more than speed for a [d, E] dot.
        router = nn.Dense(
            e, use_bias=False, dtype=jnp.float32,
            param_dtype=cfg.param_dtype, name="router",
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "expert_logits")))
        probs = jax.nn.softmax(router(xt.astype(jnp.float32)), axis=-1)

        def w(name, shape, axes):
            return self.param(name, nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), axes), shape,
                cfg.param_dtype).astype(cfg.dtype)

        w_gate = w("gate", (e, d, cfg.mlp_dim), ("expert", "embed", "mlp"))
        w_up = w("up", (e, d, cfg.mlp_dim), ("expert", "embed", "mlp"))
        w_down = w("down", (e, cfg.mlp_dim, d), ("expert", "mlp", "embed"))

        n_ep = compat.mesh_axis_size(EP_AXIS)
        if n_ep > 1:
            from jax.sharding import PartitionSpec as P

            if t % n_ep or e % n_ep:
                raise ValueError(
                    f"tokens ({t}) and experts ({e}) must divide the ep "
                    f"axis ({n_ep})")
            out = compat.partial_shard_map(
                functools.partial(_routed_ffn_group, cfg, n_ep=n_ep),
                EP_AXIS,
                in_specs=(P(EP_AXIS), P(EP_AXIS), P(EP_AXIS), P(EP_AXIS),
                          P(EP_AXIS)),
                out_specs=P(EP_AXIS),
            )(xt, probs, w_gate, w_up, w_down)
        else:
            out = _routed_ffn_group(cfg, xt, probs, w_gate, w_up, w_down,
                                    n_ep=1)
        out = out.reshape(b, s, d)

        # Switch aux loss: E · Σ_e (token fraction to e) · (mean router prob).
        gate_idx = jnp.argmax(probs, axis=-1)
        token_frac = jnp.mean(
            jax.nn.one_hot(gate_idx, e, dtype=jnp.float32), axis=0)
        prob_frac = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(token_frac * prob_frac)
        return out, aux


class MoEBlock(nn.Module):
    cfg: MoEConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.cfg
        h = x + Attention(cfg, name="attn")(
            RMSNorm(cfg.norm_eps, cfg.param_dtype, name="attn_norm")(x),
            positions)
        mlp_out, aux = MoEMLP(cfg, name="moe")(
            RMSNorm(cfg.norm_eps, cfg.param_dtype, name="mlp_norm")(h))
        out = h + mlp_out
        return nn.with_logical_constraint(out, ("batch", "seq", "embed")), aux


class MoETransformer(nn.Module):
    """Causal LM with routed-expert FFNs: tokens → (logits, aux_loss)."""

    cfg: MoEConfig

    @nn.compact
    def __call__(self, tokens, positions=None):
        cfg = self.cfg
        if positions is None:
            pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)
            positions = jnp.broadcast_to(pos[None, :], tokens.shape)
        emb = self.param(
            "embedding", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab", "embed")),
            (cfg.vocab_size, cfg.dim), cfg.param_dtype)
        x = emb[tokens].astype(cfg.dtype)
        x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        block = MoEBlock
        if cfg.remat:
            # prevent_cse=True: layers are a Python loop, and with False
            # XLA CSEs the recomputation away and silently un-remats the
            # model (same defect found and measured in
            # models/transformer.py; False is only sound inside
            # scan/while bodies — see parallel/pipeline.py for the
            # legitimate case). The policy is the dense transformer's.
            block = nn.remat(MoEBlock, prevent_cse=True,
                             policy=remat_policy_of(cfg))
        aux_total = jnp.zeros((), jnp.float32)
        for i in range(cfg.n_layers):
            x, aux = block(cfg, name=f"layer_{i}")(x, positions)
            aux_total = aux_total + aux
        x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=jnp.float32,
            param_dtype=cfg.param_dtype, name="lm_head",
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "vocab")))(
                    x.astype(jnp.float32))
        return logits, aux_total / cfg.n_layers


def moe_lm_loss(model_out, tokens, aux_weight: float) -> jax.Array:
    from tony_tpu.models.transformer import causal_lm_loss

    logits, aux = model_out
    return causal_lm_loss(logits, tokens) + aux_weight * aux


def dryrun_ep_step(devices, ep: int) -> float:
    """One FULL MoE train step (fwd + bwd + optimizer update) on an ep≥2
    mesh, asserting the compiled program dispatches experts via all_to_all.
    Used by ``__graft_entry__.dryrun_multichip``; returns the loss."""
    import optax

    from tony_tpu.parallel import MeshSpec, build_mesh, init_sharded_state
    from tony_tpu.parallel.sharding import DEFAULT_RULES

    n = len(devices)
    mesh = build_mesh(MeshSpec(dp=n // ep, ep=ep), devices=devices)
    cfg = MoEConfig.tiny_moe()
    model = MoETransformer(cfg)
    tokens = jax.random.randint(jax.random.key(0), (2 * (n // ep), 32), 0,
                                cfg.vocab_size)
    state, _sh = init_sharded_state(model, tokens, optax.adam(1e-3), mesh)

    def loss_fn(p):
        with nn.logical_axis_rules(list(DEFAULT_RULES)):
            return moe_lm_loss(model.apply({"params": p}, tokens), tokens,
                               cfg.aux_loss_weight)

    def step(state):
        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads), loss

    # set_mesh binds the abstract mesh MoEMLP reads to pick the ep path;
    # without it n_ep resolves to 1 and the dry run would only validate the
    # replicated fallback (advisor finding, round 2).
    with jax.set_mesh(mesh):
        compiled = jax.jit(step).lower(state).compile()
        hlo = compiled.as_text()
        assert "all-to-all" in hlo, \
            "ep dryrun compiled WITHOUT all_to_all expert dispatch"
        state, loss = compiled(state)
    loss = float(loss)
    assert jnp.isfinite(loss), f"ep MoE train step diverged: {loss}"
    return loss
