"""MoE / expert parallelism on the virtual 8-device CPU mesh
(SURVEY.md §2.3 — EP is a first-class requirement, no reference analogue)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tony_tpu.models.moe import (MoEConfig, MoEMLP, MoETransformer,
                                 moe_lm_loss)
from tony_tpu.parallel import MeshSpec, build_mesh, init_sharded_state
from tony_tpu.parallel.sharding import DEFAULT_RULES


def _rules():
    return nn.logical_axis_rules(list(DEFAULT_RULES))


def test_single_expert_equals_dense_mlp():
    """E=1, k=1, generous capacity: routing is the identity, so the MoE MLP
    must equal a plain gated-silu MLP with the same weights."""
    cfg = MoEConfig.tiny_moe(n_experts=1, top_k=1, capacity_factor=2.0)
    x = jax.random.normal(jax.random.key(0), (2, 16, cfg.dim))
    moe = MoEMLP(cfg)
    with _rules():
        variables = moe.init(jax.random.key(1), x)
        out, aux = moe.apply(variables, x)
    p = nn.meta.unbox(variables)["params"]
    w_gate, w_up, w_down = p["gate"][0], p["up"][0], p["down"][0]
    want = nn.silu(x @ w_gate) * (x @ w_up) @ w_down
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(aux) == pytest.approx(1.0)  # all mass on the one expert


def test_capacity_respected_and_balanced_uniform_router():
    """With a zeroed router every token ties; top-k dispatch must respect
    per-expert capacity exactly and spread slot-0 tokens by tie-break."""
    cfg = MoEConfig.tiny_moe(n_experts=4, top_k=2, capacity_factor=1.0)
    x = jax.random.normal(jax.random.key(0), (2, 32, cfg.dim))
    moe = MoEMLP(cfg)
    with _rules():
        variables = moe.init(jax.random.key(1), x)
    import flax

    params = nn.meta.unbox(variables)["params"]
    flat = flax.traverse_util.flatten_dict(params, sep="/")
    flat = {k: (jnp.zeros_like(v) if k.startswith("router") else v)
            for k, v in flat.items()}  # zero router → uniform probs
    params = flax.traverse_util.unflatten_dict(flat, sep="/")
    with _rules():
        out, aux = MoEMLP(cfg).apply({"params": params}, x)
    assert bool(jnp.isfinite(out).all())


def test_moe_transformer_trains_on_ep_mesh():
    """Full train step on a dp×ep mesh: loss finite and decreasing, and the
    compiled program moves tokens with all-to-all over ep."""
    mesh = build_mesh(MeshSpec(dp=4, ep=2))
    cfg = MoEConfig.tiny_moe()
    model = MoETransformer(cfg)
    tokens = jax.random.randint(jax.random.key(0), (8, 32), 0,
                                cfg.vocab_size)
    state, sh = init_sharded_state(model, tokens, optax.adam(3e-3), mesh)

    def loss_fn(p):
        with _rules():
            return moe_lm_loss(model.apply({"params": p}, tokens), tokens,
                               cfg.aux_loss_weight)

    @jax.jit
    def step(state):
        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        return state.apply_gradients(grads), loss

    with jax.set_mesh(mesh):
        losses = []
        for _ in range(5):
            state, loss = step(state)
            losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]


def test_moe_expert_weights_sharded_over_ep():
    mesh = build_mesh(MeshSpec(dp=4, ep=2))
    cfg = MoEConfig.tiny_moe()
    model = MoETransformer(cfg)
    tokens = jnp.zeros((8, 16), jnp.int32)
    state, sh = init_sharded_state(model, tokens, optax.adam(1e-3), mesh)
    gate = state.params["layer_0"]["moe"]["gate"]
    assert gate.shape[0] == cfg.n_experts
    for shard in gate.addressable_shards:
        assert shard.data.shape[0] == cfg.n_experts // mesh.shape["ep"]


def test_moe_dispatch_is_all_to_all_on_ep_mesh():
    mesh = build_mesh(MeshSpec(dp=4, ep=2))
    cfg = MoEConfig.tiny_moe()
    model = MoETransformer(cfg)
    tokens = jnp.zeros((8, 16), jnp.int32)
    state, sh = init_sharded_state(model, tokens, optax.adam(1e-3), mesh)

    def loss_fn(p):
        with _rules():
            return moe_lm_loss(model.apply({"params": p}, tokens), tokens,
                               cfg.aux_loss_weight)

    with jax.set_mesh(mesh):
        txt = jax.jit(jax.grad(loss_fn)).lower(state.params).compile()\
            .as_text()
    assert "all-to-all" in txt, "expert dispatch did not lower to all_to_all"


def test_aux_loss_penalizes_imbalance():
    """Collapsed routing (all tokens → expert 0) must score a higher aux
    loss than uniform routing."""
    cfg = MoEConfig.tiny_moe(n_experts=4, top_k=1)
    x = jax.random.normal(jax.random.key(0), (1, 64, cfg.dim))
    moe = MoEMLP(cfg)
    with _rules():
        variables = moe.init(jax.random.key(1), x)

    import flax

    flat = flax.traverse_util.flatten_dict(
        nn.meta.unbox(variables)["params"], sep="/")
    flat = {k: jnp.asarray(v) for k, v in flat.items()}
    collapsed = dict(flat)
    kernel = collapsed["router/kernel"]
    bias_to_zero = jnp.zeros_like(kernel).at[:, 0].set(10.0)
    collapsed["router/kernel"] = bias_to_zero
    uniform = dict(flat)
    uniform["router/kernel"] = jnp.zeros_like(kernel)

    def aux_of(p):
        with _rules():
            _, aux = MoEMLP(cfg).apply(
                {"params": flax.traverse_util.unflatten_dict(p, sep="/")}, x)
        return float(aux)

    # Uniform routing is the analytic minimum of the Switch loss (== 1.0);
    # any skew toward one expert must score strictly worse.
    assert aux_of(uniform) == pytest.approx(1.0, abs=1e-5)
    assert aux_of(collapsed) > aux_of(uniform) + 0.1
