"""Parallelism library tests on the 8-device virtual CPU mesh (conftest.py).

TPU analogue of the reference's MiniCluster-based tests (SURVEY.md §4.1):
real sharded compilation and collectives, no hardware.
"""

import jax
import jax.numpy as jnp
import flax.linen as nn
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tony_tpu.parallel import (MeshSpec, TrainState, batch_sharding,
                               build_mesh, init_sharded_state, jit_train_step,
                               logical_sharding, with_rules)


class TinyMLP(nn.Module):
    features: int = 32

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(
            self.features,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "mlp")))(x)
        x = nn.relu(x)
        x = nn.Dense(
            8,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("mlp", "embed")))(x)
        return x


def test_mesh_spec_resolve_and_parse():
    spec = MeshSpec.from_string("tp=2,fsdp=2")
    resolved = spec.resolve(8)
    assert resolved.dp == 2 and resolved.tp == 2 and resolved.fsdp == 2
    with pytest.raises(ValueError):
        MeshSpec(dp=3).resolve(8)
    with pytest.raises(ValueError):
        MeshSpec.from_string("bogus=2")


def test_build_mesh_axes():
    mesh = build_mesh(MeshSpec(dp=2, tp=4))
    assert mesh.shape["dp"] == 2 and mesh.shape["tp"] == 4
    assert mesh.devices.size == 8


def test_logical_sharding_maps_rules():
    mesh = build_mesh(MeshSpec(dp=2, tp=4))
    # fsdp is consumed by batch, so a [batch, embed] activation can't reuse
    # it on dim 1 (one mesh axis shards at most one dim of a tensor).
    sh = logical_sharding(mesh, "batch", "embed")
    assert sh.spec == P(("dcn_dp", "dp", "fsdp"), None)
    # A weight [embed, mlp] shards fsdp x tp.
    sh = logical_sharding(mesh, "embed", "mlp")
    assert sh.spec == P("fsdp", "tp")


def test_init_sharded_state_tp_and_fsdp():
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    model = TinyMLP()
    x = jnp.ones((8, 16))
    state, state_sh = init_sharded_state(model, x, optax.adam(1e-2), mesh)
    k0 = state.params["Dense_0"]["kernel"]
    # ("embed","mlp") → (fsdp, tp): 16/2 x 32/2 per-device shards.
    assert k0.sharding.spec == P("fsdp", "tp")
    shard_shape = k0.sharding.shard_shape(k0.shape)
    assert shard_shape == (8, 16)
    # Adam mu mirrors param sharding via propagation.
    mu0 = state.opt_state[0].mu["Dense_0"]["kernel"]
    assert mu0.sharding.spec == P("fsdp", "tp")


def test_train_step_loss_decreases_sharded():
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    model = TinyMLP()
    rng = jax.random.key(0)
    x = jax.random.normal(rng, (16, 16))
    w = jax.random.normal(jax.random.key(1), (16, 8))
    y = x @ w
    batch = {"x": x, "y": y}

    def loss_fn(params, batch, rng):
        pred = model.apply({"params": params}, batch["x"])
        loss = jnp.mean((pred - batch["y"]) ** 2)
        return loss, {}

    state, state_sh = init_sharded_state(model, x, optax.adam(1e-2), mesh)
    step = jit_train_step(loss_fn, mesh, state_sh, batch)
    losses = []
    for i in range(20):
        state, metrics = step(state, batch, jax.random.key(i))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.7
    assert int(state.step) == 20


def test_apply_gradients_is_optax_adamw_bit_for_bit():
    """The barrier ``apply_gradients`` puts on each gradient changes no
    arithmetic: over three steps, params and the whole AdamW state (both
    moments, the count) equal, bit for bit,
    ``optax.apply_updates(params, tx.update(grads, ...))``."""
    tx = optax.adamw(1e-2, weight_decay=0.1)
    keys = jax.random.split(jax.random.key(0), 6)
    params = {"w": jax.random.normal(keys[0], (64, 128)),
              "v": jax.random.normal(keys[1], (128,)),
              "k": jax.random.normal(keys[2], (4, 16, 8))}

    @jax.jit
    def ours(state, grads):
        return state.apply_gradients(grads)

    @jax.jit
    def theirs(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=tx.init(params), tx=tx)
    want = (params, tx.init(params))
    for i in range(3):
        grads = jax.tree.map(
            lambda p, k=keys[3 + i]: jax.random.normal(k, p.shape), params)
        state = ours(state, grads)
        want = theirs(*want, grads)
    jax.tree.map(np.testing.assert_array_equal,
                 (state.params, state.opt_state), want)
    assert int(state.step) == 3


def test_batch_sharding_splits_batch_dim():
    mesh = build_mesh(MeshSpec(dp=4, fsdp=2))
    sh = batch_sharding(mesh, extra_dims=2)
    x = jax.device_put(jnp.ones((16, 3, 3)), sh)
    assert x.sharding.shard_shape(x.shape) == (2, 3, 3)


def test_multislice_dcn_dp_train_step():
    """Multislice: dcn_dp is an outermost pure-DP axis across (virtual)
    slices — only the gradient psum crosses it, everything else stays
    inside a slice. Contiguous device groups stand in for slices on the
    CPU mesh (mesh.py build_mesh)."""
    import optax

    from tony_tpu.models import Transformer, TransformerConfig
    from tony_tpu.models.transformer import causal_lm_loss
    from tony_tpu.parallel import init_sharded_state, jit_train_step
    from tony_tpu.parallel.mesh import batch_sharding

    mesh = build_mesh(MeshSpec(dcn_dp=2, dp=2, fsdp=1, tp=2))
    assert dict(mesh.shape)["dcn_dp"] == 2
    cfg = TransformerConfig.tiny()
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.key(0), (8, 32), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}

    def loss_fn(params, b, rng):
        return causal_lm_loss(
            model.apply({"params": params}, b["tokens"]), b["tokens"]), {}

    state, state_sh = init_sharded_state(model, tokens, optax.adam(1e-3),
                                         mesh)
    step = jit_train_step(loss_fn, mesh, state_sh, batch)
    state, m = step(state, batch, jax.random.key(1))
    assert jnp.isfinite(m["loss"])
    # the batch really spreads over dcn_dp x dp: 8 rows / 4 = 2 per group
    sh = batch_sharding(mesh)
    tokens_sharded = jax.device_put(tokens, sh)
    shapes = {s.data.shape for s in tokens_sharded.addressable_shards}
    assert shapes == {(2, 32)}
