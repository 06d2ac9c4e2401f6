"""Fused GroupNorm→ReLU tests: parity against nn.GroupNorm on both the
lax composition and the Pallas apply (interpret mode on CPU — same code
path the TPU kernel runs), gradient parity through the remat'd epilogue,
and the ResNet fused-trunk twin (same params, same numbers)."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import ResNet, ResNetConfig
from tony_tpu.ops import convfuse


def _ref(x, scale, bias, groups, relu=True):
    gn = nn.GroupNorm(num_groups=groups)
    y = gn.apply({"params": {"scale": scale, "bias": bias}}, x)
    return nn.relu(y) if relu else y


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("relu", [True, False])
def test_fused_groupnorm_matches_flax(use_pallas, relu):
    x = jax.random.normal(jax.random.key(0), (2, 9, 9, 16), jnp.float32)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.key(1), (16,))
    bias = 0.1 * jax.random.normal(jax.random.key(2), (16,))
    got = convfuse.fused_groupnorm_relu(x, scale, bias, groups=4,
                                        relu=relu, use_pallas=use_pallas)
    want = _ref(x, scale, bias, 4, relu=relu)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_fused_groupnorm_under_jit_and_grad(use_pallas):
    """Remat'd fused path: grads w.r.t. the activation AND the affine
    params match the unfused flax composition — on the Pallas apply too
    (pallas_call has no reverse-mode rule of its own; the path a TPU
    takes must still train)."""
    x = jax.random.normal(jax.random.key(0), (2, 5, 5, 8), jnp.float32)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.key(1), (8,))
    bias = 0.1 * jax.random.normal(jax.random.key(2), (8,))
    w = jax.random.normal(jax.random.key(3), x.shape)

    g1 = jax.jit(jax.grad(lambda x, s, b: (w * convfuse.fused_groupnorm_relu(
        x, s, b, groups=4, use_pallas=use_pallas)).sum(),
        argnums=(0, 1, 2)))(x, scale, bias)
    g2 = jax.grad(lambda x, s, b: (w * _ref(x, s, b, 4)).sum(),
                  argnums=(0, 1, 2))(x, scale, bias)
    for got, want in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)


def test_fused_groupnorm_channel_edge():
    """groups = channels (the min(norm_groups, C) edge in resnet)."""
    x = jax.random.normal(jax.random.key(0), (1, 4, 4, 4), jnp.float32)
    scale, bias = jnp.ones((4,)), jnp.zeros((4,))
    got = convfuse.fused_groupnorm_relu(x, scale, bias, groups=4)
    want = _ref(x, scale, bias, 4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="divisible"):
        convfuse.fused_groupnorm_relu(x, scale, bias, groups=3)


def test_bf16_dtype_preserved():
    x = jax.random.normal(jax.random.key(0), (2, 4, 4, 8), jnp.bfloat16)
    out = convfuse.fused_groupnorm_relu(x, jnp.ones((8,)),
                                        jnp.zeros((8,)), groups=2)
    assert out.dtype == jnp.bfloat16 and out.shape == x.shape


def test_resnet_fused_trunk_parity():
    """The fused trunk is a numerical twin of the GroupNorm trunk: same
    leaf shapes in the same order, outputs allclose with copied params,
    grads allclose too."""
    x = jax.random.normal(jax.random.key(0), (2, 32, 32, 3), jnp.float32)
    y = jax.random.randint(jax.random.key(1), (2,), 0, 10)
    unfused = ResNet(ResNetConfig.tiny(fused=False))
    fused = ResNet(ResNetConfig.tiny())
    vu = unfused.init(jax.random.key(2), x)
    vf = fused.init(jax.random.key(2), x)
    lu, _ = jax.tree_util.tree_flatten(vu)
    lf, treedef_f = jax.tree_util.tree_flatten(vf)
    assert [l.shape for l in lu] == [l.shape for l in lf]
    vf_copied = jax.tree_util.tree_unflatten(treedef_f, lu)

    ou = unfused.apply(vu, x)
    of = fused.apply(vf_copied, x)
    np.testing.assert_allclose(np.asarray(of), np.asarray(ou),
                               rtol=2e-4, atol=2e-4)

    def loss(variables, model):
        logits = model.apply(variables, x)
        one_hot = jax.nn.one_hot(y, 10)
        return -jnp.mean(jnp.sum(
            jax.nn.log_softmax(logits) * one_hot, axis=-1))

    gu = jax.grad(loss)(vu, unfused)
    gf = jax.grad(loss)(vf_copied, fused)
    for a, b in zip(jax.tree.leaves(gu), jax.tree.leaves(gf)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


def test_resnet_fused_is_default_and_jits():
    cfg = ResNetConfig.tiny()
    assert cfg.fused
    model = ResNet(cfg)
    x = jax.random.normal(jax.random.key(0), (2, 16, 16, 3))
    variables = model.init(jax.random.key(1), x)
    out = jax.jit(lambda v, x: model.apply(v, x))(variables, x)
    assert out.shape == (2, 10) and bool(jnp.isfinite(out).all())
