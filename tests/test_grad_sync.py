"""Bucketed/overlapped gradient-sync tests on the 8-device virtual mesh.

The acceptance contract of parallel/grad_sync.py: bucketed + accumulated
grads are allclose to the monolithic psum for EVERY bucket size
(including the one-param-spills-bucket edge), the accum step builder is
a drop-in twin of jit_train_step, and the sync dispatch books real
seconds into the telemetry "comms" phase.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tony_tpu import telemetry
from tony_tpu.parallel import (GradSyncSpec, MeshSpec, batch_sharding,
                               build_mesh, bucketed_sync,
                               init_sharded_state, jit_train_step,
                               jit_train_step_accum, monolithic_grads,
                               plan_buckets)
from tony_tpu.parallel.grad_sync import (_build_accum_fn,
                                         stacked_grad_shardings)
from tony_tpu.parallel.sharding import DEFAULT_RULES


class VariedMLP(nn.Module):
    """Several params of varied sizes so bucket plans actually vary."""

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(
            48, kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "mlp")))(x)
        x = nn.relu(x)
        x = nn.Dense(
            16, kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("mlp", "embed")))(x)
        x = nn.relu(x)
        return nn.Dense(8)(x)


def _loss_fn(model):
    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["x"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, batch["y"]).mean()
        return loss, {"acc": (logits.argmax(-1) == batch["y"]).mean()}
    return loss_fn


@pytest.fixture(scope="module")
def rig():
    mesh = build_mesh(MeshSpec(dcn_dp=2, dp=4))     # the 2x4 mesh
    model = VariedMLP()
    # 32 rows: divisible by 8 slices x accum depths up to 4.
    x = jax.random.normal(jax.random.key(0), (32, 12))
    y = jax.random.randint(jax.random.key(1), (32,), 0, 8)
    batch = {"x": x, "y": y}
    state, sh = init_sharded_state(model, x, optax.adamw(1e-2), mesh)
    return mesh, model, batch, state, sh


def test_plan_buckets_order_stable_and_capped():
    descs = [((4, 4), jnp.float32), ((8,), jnp.float32),
             ((2, 2), jnp.float32), ((16,), jnp.float32)]
    plan = plan_buckets(descs, bucket_mb=1)
    # Order-stable: indices appear exactly once, in tree order.
    assert [i for b in plan for i in b] == [0, 1, 2, 3]
    # Everything fits one MiB → one bucket.
    assert plan == [[0, 1, 2, 3]]


def test_plan_buckets_dtype_boundary_and_spill():
    # A dtype change closes the bucket (no silent upcast in the packer).
    descs = [((4,), jnp.float32), ((4,), jnp.bfloat16),
             ((4,), jnp.bfloat16)]
    plan = plan_buckets(descs, bucket_mb=1)
    assert plan == [[0], [1, 2]]
    # One-param-spills edge: a leaf bigger than the whole bucket gets a
    # bucket of its own and never merges with neighbours.
    big = ((1 << 19,), jnp.float32)              # 2 MiB of f32
    small = ((4,), jnp.float32)
    plan = plan_buckets([small, big, small], bucket_mb=1)
    assert plan == [[0], [1], [2]]


@pytest.mark.parametrize("bucket_mb", [1, 32])
@pytest.mark.parametrize("accum", [1, 2, 4])
def test_bucketed_accum_allclose_monolithic_psum(rig, bucket_mb, accum):
    """The acceptance invariant: bucketed+accumulated grads over the 2x4
    mesh match XLA's own monolithic reduction, for every bucket size and
    accumulation depth."""
    mesh, model, batch, state, sh = rig
    loss_fn = _loss_fn(model)
    part_sh = NamedSharding(mesh, P(("dcn_dp", "dp"), None))
    with jax.set_mesh(mesh):
        mono = jax.jit(lambda p, b, r: monolithic_grads(
            loss_fn, p, b, r))(state.params, batch, jax.random.key(2))
        accum_fn = _build_accum_fn(loss_fn, mesh, accum, 8,
                                   ("dcn_dp", "dp"), DEFAULT_RULES)
        stacked, loss, _ = jax.jit(accum_fn)(state.params, batch,
                                             jax.random.key(2))
        got = jax.jit(lambda s: bucketed_sync(
            s, bucket_mb, part_sharding=part_sh))(stacked)
    for a, b in zip(jax.tree.leaves(mono), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-7)


def test_bucketed_sync_spill_bucket_values():
    """The one-param-spills edge end to end: values still equal the
    plain mean when a 2 MiB leaf forces its own bucket."""
    rng = np.random.default_rng(0)
    tree = {"small": jnp.asarray(rng.standard_normal((4, 8)),
                                 jnp.float32),
            "big": jnp.asarray(rng.standard_normal((4, 1 << 19)),
                               jnp.float32),
            "tail": jnp.asarray(rng.standard_normal((4, 3)),
                                jnp.float32)}
    got = bucketed_sync(tree, bucket_mb=1)
    for k, v in tree.items():
        np.testing.assert_allclose(np.asarray(got[k]),
                                   np.asarray(v).mean(0), rtol=1e-6,
                                   atol=1e-7)


def test_accum_step_matches_monolithic_step(rig):
    """jit_train_step_accum is a drop-in twin: same post-step state and
    loss as jit_train_step on the same batch."""
    mesh, model, batch, state, sh = rig
    loss_fn = _loss_fn(model)
    step = jit_train_step(loss_fn, mesh, sh, batch, donate=False)
    s1, m1 = step(state, batch, jax.random.key(3))
    astep = jit_train_step_accum(loss_fn, mesh, sh, batch,
                                 accum_steps=2, bucket_mb=1,
                                 donate=False)
    s2, m2 = astep(state, batch, jax.random.key(3))
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]),
                                              rel=1e-5)
    assert int(s2.step) == int(s1.step) == 1
    assert "acc" in m2       # aux metrics survive the accum path
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_accum_step_records_comms_phase(rig):
    mesh, model, batch, state, sh = rig
    loss_fn = _loss_fn(model)
    telemetry._reset_phase_state()
    astep = jit_train_step_accum(loss_fn, mesh, sh, batch,
                                 accum_steps=2, donate=False)
    with telemetry.step():
        astep(state, batch, jax.random.key(4))
    stats = telemetry.phase_stats()
    telemetry._reset_phase_state()
    assert stats and stats["cum"].get("comms", 0.0) > 0.0
    # ... and comms_phase=False keeps the phase ring clean.
    astep2 = jit_train_step_accum(loss_fn, mesh, sh, batch,
                                  accum_steps=2, donate=False,
                                  comms_phase=False)
    with telemetry.step():
        astep2(state, batch, jax.random.key(4))
    stats = telemetry.phase_stats()
    telemetry._reset_phase_state()
    assert "comms" not in (stats.get("cum") or {})


def test_divisibility_errors_name_the_knob(rig):
    mesh, model, batch, state, sh = rig
    loss_fn = _loss_fn(model)
    astep = jit_train_step_accum(loss_fn, mesh, sh, batch,
                                 accum_steps=3, donate=False)
    with pytest.raises(ValueError, match="accum-steps"):
        astep(state, batch, jax.random.key(0))  # 32 % (8*3) != 0


def test_sync_axes_validation(rig):
    mesh, model, batch, state, sh = rig
    loss_fn = _loss_fn(model)
    with pytest.raises(ValueError, match="not in mesh axes"):
        jit_train_step_accum(loss_fn, mesh, sh, batch,
                             sync_axes=("bogus",))
    with pytest.raises(ValueError, match="pure data-parallel"):
        jit_train_step_accum(loss_fn, mesh, sh, batch,
                             sync_axes=("tp",))


def test_scalar_batch_leaves_replicate(rig):
    """0-d batch leaves (a scale factor riding the batch dict) pass
    through to every microbatch unchanged."""
    mesh, model, batch, state, sh = rig

    def loss_fn(params, b, rng):
        logits = model.apply({"params": params}, b["x"]) * b["scale"]
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, b["y"]).mean()
        return loss, {}

    batch2 = dict(batch, scale=jnp.float32(1.0))
    astep = jit_train_step_accum(loss_fn, mesh, sh, batch2,
                                 accum_steps=2, donate=False)
    _, m = astep(state, batch2, jax.random.key(5))
    assert np.isfinite(float(m["loss"]))


def test_stacked_grad_shardings_prepend_sync_axes(rig):
    mesh, _, _, _, sh = rig
    stacked = stacked_grad_shardings(mesh, sh.params, ("dcn_dp", "dp"))
    for leaf_sh, param_sh in zip(jax.tree.leaves(stacked),
                                 jax.tree.leaves(sh.params)):
        assert leaf_sh.spec[0] == ("dcn_dp", "dp")
        assert tuple(leaf_sh.spec[1:]) == tuple(param_sh.spec)


def test_batch_sharding_memoized(rig):
    """The submit-path small fix: identical (mesh, ndim) requests return
    the SAME NamedSharding object instead of re-constructing per leaf."""
    mesh, _, _, _, _ = rig
    assert batch_sharding(mesh, 1) is batch_sharding(mesh, 1)
    assert batch_sharding(mesh, 2) is not batch_sharding(mesh, 1)


def test_grad_sync_spec_from_conf():
    from tony_tpu.conf import keys as K
    from tony_tpu.conf.config import TonyTpuConfig

    conf = TonyTpuConfig()
    conf.set(K.TRAIN_ACCUM_STEPS, 4)
    conf.set(K.TRAIN_BUCKET_MB, 8)
    conf.set(K.TRAIN_MATMUL_DTYPE, "int8")
    spec = GradSyncSpec.from_conf(conf)
    assert spec == GradSyncSpec(accum_steps=4, bucket_mb=8,
                                matmul_dtype="int8")
    # Defaults: accumulation off, 32 MiB buckets, no quantization.
    assert GradSyncSpec.from_conf(TonyTpuConfig()) == GradSyncSpec()
