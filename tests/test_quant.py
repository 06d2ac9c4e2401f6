"""int8/fp8 matmul-path tests: quantization error bounds, straight-
through gradients, the bitwise-off contract, the unsupported-backend
degrade (faults-marked), and the 50-step loss-parity golden against the
unquantized flagship twin."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import flax.linen as nn

from tony_tpu import faults, telemetry
from tony_tpu.ops import quant


@pytest.fixture(autouse=True)
def _clean_quant_state():
    quant._reset_fallback_state()
    yield
    faults.uninstall()
    quant._reset_fallback_state()


def test_quantize_symmetric_roundtrip_error():
    x = jax.random.normal(jax.random.key(0), (16, 64))
    # int8: 8-bit grid -> <1% of range; fp8-e4m3: 3 mantissa bits ->
    # ~6% worst-case relative step near the top of each binade.
    for mode, bound in ((quant.INT8, 0.02), (quant.FP8_E4M3, 0.06)):
        q, scale = quant.quantize_symmetric(x, mode, axis=-1)
        deq = q.astype(jnp.float32) * scale
        err = float(jnp.abs(deq - x).max() / jnp.abs(x).max())
        assert err < bound, (mode, err)
        assert scale.shape == (16, 1)


def test_quantized_matmul_error_bound():
    x = jax.random.normal(jax.random.key(0), (4, 64))
    w = jax.random.normal(jax.random.key(1), (64, 32)) * 0.1
    exact = x @ w
    for mode in quant.MODES:
        got = quant.quantized_matmul(x, w, mode)
        rel = float(jnp.linalg.norm(got - exact)
                    / jnp.linalg.norm(exact))
        assert rel < 0.05, (mode, rel)


def test_straight_through_gradients_are_exact():
    """Backward must be the full-precision matmul gradient, untouched by
    quantization noise — the property the loss-parity gate leans on."""
    x = jax.random.normal(jax.random.key(0), (2, 3, 32))
    w = jax.random.normal(jax.random.key(1), (32, 16))
    gq = jax.grad(lambda x, w: quant.quantized_matmul(x, w, "int8").sum(),
                  argnums=(0, 1))(x, w)
    ge = jax.grad(lambda x, w: (x @ w).sum(), argnums=(0, 1))(x, w)
    for a, b in zip(gq, ge):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_qdense_knob_off_is_bitwise_dense():
    """matmul_dtype unset → QDense replicates nn.Dense exactly (same
    param name, same promote, same dot_general) — the 'disabling the
    knob restores bitwise-identical bf16 behaviour' contract."""
    x = jax.random.normal(jax.random.key(0), (4, 24))
    dense = nn.Dense(16, use_bias=False, dtype=jnp.bfloat16,
                     param_dtype=jnp.float32, name="d")
    qd = quant.QDense(features=16, dtype=jnp.bfloat16,
                      param_dtype=jnp.float32, name="d")
    variables = dense.init(jax.random.key(1), x)
    a = np.asarray(dense.apply(variables, x))
    b = np.asarray(qd.apply(variables, x))
    assert (a == b).all()
    # Same init path too: QDense.init produces the identical kernel.
    v2 = qd.init(jax.random.key(1), x)
    np.testing.assert_array_equal(
        np.asarray(variables["params"]["kernel"]),
        np.asarray(v2["params"]["kernel"]))


def test_resolve_mode_rejects_typos():
    with pytest.raises(ValueError, match="matmul-dtype"):
        quant.resolve_mode("int4")
    assert quant.resolve_mode("") is None
    assert quant.resolve_mode(None) is None
    assert quant.resolve_mode("bf16") is None


@pytest.mark.faults
def test_unsupported_backend_degrades_once_not_fatally():
    """quant.probe fires → the int8 path resolves to None (bf16), the
    fallback is recorded ONCE, rides the telemetry beacon, and the model
    keeps producing the exact Dense numbers — the job never fails."""
    faults.install(faults.parse_spec("quant.probe=first:1"))
    assert quant.resolve_mode("int8") is None
    fb = quant.fallback_events()
    assert list(fb) == ["int8"] and "injected fault" in fb["int8"]
    # Cached: a second resolve neither re-probes nor re-records.
    faults.uninstall()
    assert quant.resolve_mode("int8") is None
    assert quant.fallback_events() == fb
    # The one-time event rides the metrics beacon.
    stats = telemetry.collect_device_stats()
    assert stats.get("quant_fallback") == fb
    # A QDense asked for int8 on the "unsupported" backend produces the
    # bitwise Dense result (degrade, don't die).
    x = jax.random.normal(jax.random.key(0), (4, 24))
    dense = nn.Dense(16, use_bias=False, name="d")
    qd = quant.QDense(features=16, matmul_dtype="int8", name="d")
    variables = dense.init(jax.random.key(1), x)
    assert (np.asarray(dense.apply(variables, x))
            == np.asarray(qd.apply(variables, x))).all()


@pytest.mark.faults
def test_probe_recovers_after_reset():
    faults.install(faults.parse_spec("quant.probe=first:1"))
    assert quant.resolve_mode("int8") is None
    faults.uninstall()
    quant._reset_fallback_state()
    assert quant.resolve_mode("int8") == "int8"
    assert quant.fallback_events() == {}


def _train_losses(cfg, steps, seed=0):
    """One compiled scan of `steps` Adam steps on the tiny flagship;
    returns the per-step loss curve."""
    import functools

    import optax

    from tony_tpu.models import Transformer
    from tony_tpu.models.transformer import causal_lm_loss
    from tony_tpu.parallel import MeshSpec, build_mesh, init_sharded_state
    from tony_tpu.parallel.sharding import DEFAULT_RULES

    mesh = build_mesh(MeshSpec())
    model = Transformer(cfg)
    tokens0 = jax.random.randint(jax.random.key(seed), (2, 32), 0,
                                 cfg.vocab_size)
    state, _ = init_sharded_state(model, tokens0,
                                  optax.adamw(3e-4), mesh,
                                  rng=jax.random.key(7))

    def one_step(state, _):
        # ONE fixed batch: "final < initial" then means the optimizer
        # trained, whatever random stream the installed jax deals — on
        # fresh tokens per step a tiny model's loss hovers at ln(vocab)
        # and the sign of (final − initial) is the RNG's, not training's.
        def loss(p):
            with nn.logical_axis_rules(list(DEFAULT_RULES)):
                return causal_lm_loss(
                    model.apply({"params": p}, tokens0), tokens0)
        l, grads = jax.value_and_grad(loss)(state.params)
        return state.apply_gradients(grads), l

    @functools.partial(jax.jit, donate_argnums=0)
    def run(state):
        return jax.lax.scan(one_step, state, None, length=steps)

    _, losses = run(state)
    return np.asarray(losses)


def test_int8_loss_parity_golden_50_steps():
    """The acceptance gate: the int8 flagship's loss curve stays within
    tolerance of the unquantized golden over the bench window (50
    steps), and both actually train (final < initial)."""
    from tony_tpu.models import TransformerConfig

    base = TransformerConfig.tiny()
    golden = _train_losses(base, steps=50)
    quantized = _train_losses(
        TransformerConfig.tiny(matmul_dtype="int8"), steps=50)
    assert golden[-1] < golden[0]
    assert quantized[-1] < quantized[0]
    # Parity: same curve to quantization-noise tolerance, everywhere.
    np.testing.assert_allclose(quantized, golden, rtol=0.05, atol=0.05)


def test_int8_loss_parity_with_experts():
    """The same gate where the feed-forward is the expert layer: there
    ``matmul_dtype="int8"`` has to reach the grouped matmuls, which are
    most of a sparse model's products, or the knob would quantize the
    attention projections alone and say nothing."""
    from jaxpr_kernels import pallas_calls
    from tony_tpu.models import Transformer
    from tony_tpu.models.moe import MoEConfig

    golden = _train_losses(MoEConfig.tiny_moe(), steps=20)
    cfg = MoEConfig.tiny_moe(matmul_dtype="int8")
    quantized = _train_losses(cfg, steps=20)
    assert golden[-1] < golden[0] and quantized[-1] < quantized[0]
    assert (quantized != golden).any()
    np.testing.assert_allclose(quantized, golden, rtol=0.05, atol=0.05)
    # the experts' forward products are int8 by int8 inside ``moe_gmm``
    tokens = jnp.zeros((2, 32), jnp.int32)
    model = Transformer(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0), tokens)
    jaxpr = jax.make_jaxpr(model.apply)(params, tokens)
    assert "i8[" in str(jaxpr) and pallas_calls(jaxpr)["moe_gmm"]


def test_fp8_path_tracks_golden():
    from tony_tpu.models import TransformerConfig

    golden = _train_losses(TransformerConfig.tiny(), steps=20)
    losses = _train_losses(
        TransformerConfig.tiny(matmul_dtype="fp8_e4m3"), steps=20)
    assert np.isfinite(losses).all()
    # fp8's 3 mantissa bits are noisier than int8 — looser band, same
    # shape: the curve must track the golden, not diverge.
    np.testing.assert_allclose(losses, golden, rtol=0.10, atol=0.10)
