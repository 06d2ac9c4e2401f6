"""The chunked gated delta rule (``ops/kda.py``) against the recurrence it
stands for, token by token: the Mosaic kernels (interpreted on the CPU) and
the ``jax.numpy`` path, forward and every gradient, over several chunks of
64 and two rows, with and without channels whose decay is e^−5 a token (a
chunk then spans e^−320, past float32's range for a factor split from the
chunk's start), and the kernels' pairs inside a sub-chunk against the
``jax.numpy`` path's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

import tony_tpu.ops.kda as ops
from tony_tpu.ops.kda import SUB, kda, log_decays, normed

CHUNK = 64
B, S, H, K, V = 2, 4 * CHUNK, 2, 32, 16
# float32 sums taken in another order (the chunk's WY form against the
# recurrence) read under 5e-6 of each input's largest gradient, the strong
# channels included; q, k and v rounded to bf16 read over 1e-3
# (test_bf16_inputs_fail_the_tolerance)
TOL = 2e-5


def recurrence(q, k, v, x, a, beta):
    """``S_t = (I − β_t k_t k_tᵀ) Diag(e^{g_t}) S_{t−1} + β_t k_t v_tᵀ``, ``o_t
    = S_tᵀ q_t``, a token at a time, q and k normalised and g from x and a as
    the mixer states them."""
    def unit(t):
        return t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    q, k = unit(q) * q.shape[-1] ** -0.5, unit(k)
    g = a[:, None] * jax.nn.softplus(x)

    def token(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        state = jnp.exp(g_t)[..., None] * state
        k_s = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + (b_t[..., None] * k_t)[..., None] \
            * (v_t - k_s)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    state = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]))
    _, o = jax.lax.scan(token, state, tuple(
        jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _inputs(strong: float = 0.0, seed: int = 1, every: int = 1):
    ks = jax.random.split(jax.random.key(seed), 7)
    q = jax.nn.silu(jax.random.normal(ks[0], (B, S, H, K)))
    k = jax.nn.silu(jax.random.normal(ks[1], (B, S, H, K)))
    v = jax.random.normal(ks[2], (B, S, H, V))
    x = jax.random.normal(ks[3], (B, S, H, K)) * 2 - 2
    a = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.5)
    if strong:      # a quarter of the channels: g = −strong on every
        # ``every``-th token, the mild decays of the others elsewhere
        x = x.at[:, ::every, :, :K // 4].set(
            jnp.log(jnp.expm1(strong / -a[:, None])))
    beta = jax.nn.sigmoid(jax.random.normal(ks[5], (B, S, H)))
    w = jax.random.normal(ks[6], (B, S, H, V))
    return (q, k, v, x, a, beta), w


def _gaps(impl, args, w, dtype=jnp.float32):
    """Each output's and gradient's largest gap to the recurrence, over the
    recurrence's largest entry."""
    lo = [t.astype(dtype) for t in args[:3]] + list(args[3:])
    with jax.default_matmul_precision("highest"):
        got = kda(*lo, chunk=CHUNK, impl=impl).astype(jnp.float32)
        want = recurrence(*args)
        grads = jax.grad(lambda *z: jnp.sum(kda(
            *z, chunk=CHUNK, impl=impl).astype(jnp.float32) * w),
            argnums=range(6))(*lo)
        wants = jax.grad(lambda *z: jnp.sum(recurrence(*z) * w),
                         argnums=range(6))(*args)
    out = {"o": float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))}
    for name, g, r in zip(("q", "k", "v", "x", "a", "beta"), grads, wants):
        assert g.shape == r.shape and bool(jnp.all(jnp.isfinite(g))), name
        out[name] = float(jnp.max(jnp.abs(g.astype(jnp.float32) - r))
                          / jnp.max(jnp.abs(r)))
    return out


@pytest.mark.parametrize("impl", ["kernel", "jnp"])
@pytest.mark.parametrize("strong, every", [
    pytest.param(0.0, 1, id="0.0"), pytest.param(5.0, 1, id="5.0"),
    pytest.param(30.0, 1, id="30.0"),
    pytest.param(35.0, 11, id="35.0-every11")])
def test_chunks_match_the_recurrence(impl, strong, every):
    """Without strong channels, with channels that decay by e^−5 a token,
    and by e^−30 (a sub-chunk of 16 then spans e^−450: a factor counted from
    any one token of it would not exist in float32). The last case decays
    them by e^−35 (the seeded model's deepest is −34.6) on every 11th token
    and mildly on the others: 11 is prime to 16, so across the four chunks
    the reference rows of every halving level are strong on some blocks and
    mild on others.

    On every third token the same channels sum to Γ ≈ −770 a chunk, where
    float32 holds Γ to 6e-5: a mild stretch's e^{Γ_t − Γ_j} is then off by
    as much, and both paths read 2e-5 to 4e-5 off the recurrence, as the
    key-at-a-time kernel did. With the decays on a grid that float32 sums
    exactly the kernel reads 5e-6 there: the limit is Γ's, not the
    pairs'."""
    gaps = _gaps(impl, *_inputs(strong, every=every))
    assert max(gaps.values()) < TOL, gaps


def test_strong_decays_are_in_the_inputs():
    """The strong channels decay by e^−5 a token: across a chunk of 64 the
    factor e^{Γ_t − Γ_j} spans e^−315, whose two halves split from the
    chunk's start do not exist in float32."""
    (_, _, _, x, a, _), _ = _inputs(5.0)
    g = log_decays(x, a)
    np.testing.assert_allclose(g[..., :K // 4], -5.0, rtol=1e-5)
    span = float(jnp.sum(g[0, :CHUNK, 0, 0]))
    assert span < -300 and -span > np.log(np.finfo(np.float32).max)


def test_bf16_inputs_fail_the_tolerance():
    """The tolerance is tight enough: q, k and v in bf16, as the cell feeds
    them, read well over it."""
    gaps = _gaps("kernel", *_inputs(), dtype=jnp.bfloat16)
    assert min(gaps[n] for n in ("o", "q", "k", "v")) > 10 * TOL, gaps


def _pairs_of(q32, k32, gam, kernel):
    """``_pairs``' ``M_qk`` (j ≤ t) and ``M_kk`` (j < t) of one chunk, run
    as the kernels run them (in an interpreted Mosaic body)."""
    n = gam.shape[0]

    def body(q_ref, k_ref, g_ref, qk_ref, kk_ref):
        qk_ref[...], kk_ref[...], _ = ops._pairs(
            q_ref[...], k_ref[...], g_ref[...], jnp.bfloat16,
            jax.lax.Precision.DEFAULT, kernel)

    with jax.default_matmul_precision("highest"):
        mqk, mkk = pl.pallas_call(
            body, interpret=True,
            out_shape=[jax.ShapeDtypeStruct((n, n), jnp.float32)] * 2)(
                q32, k32, gam)
    rows, cols = np.tril_indices(n), np.tril_indices(n, -1)
    return np.concatenate([np.asarray(mqk)[rows], np.asarray(mkk)[cols]])


@pytest.mark.parametrize("strong, every", [(0.0, 1), (30.0, 1), (35.0, 3)])
def test_kernel_pairs_are_the_float32_sums(monkeypatch, strong, every):
    """The kernels' pairs (the levels inside each sub-chunk) against the
    ``jax.numpy`` path's (``_within``'s one ``[16, 16, K]`` factor a
    sub-chunk), both in float32 from bf16-valued q and k: the same sums in
    another order, within 1e-6 of the largest entry. The in-sub-chunk
    operands rounded to bf16 read over 1e-4, so the tolerance tells the
    float32 sums from a lower precision."""
    (q, k, _, x, a, _), _ = _inputs(strong, every=every)
    qn, kn = normed(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16))
    q32, k32 = (t[0, :CHUNK, 0].astype(jnp.float32) for t in (qn, kn))
    gam = jnp.cumsum(log_decays(x, a)[0, :CHUNK, 0], axis=0)
    want = _pairs_of(q32, k32, gam, kernel=False)
    scale = np.max(np.abs(want))

    def gap():
        return np.max(np.abs(_pairs_of(q32, k32, gam, True) - want)) / scale

    assert gap() < 1e-6
    # every product of ``_pairs`` on bf16 operands: the pairs between
    # sub-chunks already are, those inside lose float32's bits
    dot = ops._dot
    monkeypatch.setattr(ops, "_dot", lambda a, b, dims, prec: dot(
        a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims, prec))
    assert gap() > 1e-4


def _a_and_inverse(q32, k32, gam, beta):
    """A = diag(β) M_kk (strictly lower) of one chunk as the kernels take it
    (``_pairs``' levels), and ``_inverse(A)``, both in an interpreted Mosaic
    body."""
    n = gam.shape[0]

    def body(q_ref, k_ref, g_ref, b_ref, a_ref, t_ref):
        _, mkk, _ = ops._pairs(q_ref[...], k_ref[...], g_ref[...],
                               jnp.bfloat16, jax.lax.Precision.DEFAULT)
        below = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
            > jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        a_ref[...] = b_ref[...] * jnp.where(below, mkk, 0.0)
        t_ref[...] = ops._inverse(a_ref[...])

    return pl.pallas_call(
        body, interpret=True,
        out_shape=[jax.ShapeDtypeStruct((n, n), jnp.float32)] * 2)(
            q32, k32, gam, beta)


@pytest.mark.parametrize("n", [SUB, 2 * SUB, 4 * SUB])
@pytest.mark.parametrize("strong, every", [(0.0, 1), (30.0, 1), (35.0, 3)])
def test_inverse_is_the_float32_solve(monkeypatch, n, strong, every):
    """``_inverse`` against the triangular solve of ``I + A`` in float32, on
    A as the kernels take it from the strong-decay inputs: one sub-chunk (its
    substitution alone), two and four (one and two factors joining the
    blocks), in the kernel's body and in ``jax.numpy``. Both read within 1e-6
    of the solve, and ``(I + A) T`` within 1e-6 of I; the joins on bf16
    operands read over 1e-5, so the tolerance tells float32 products from a
    lower precision."""
    (q, k, _, x, a, beta), _ = _inputs(strong, every=every)
    qn, kn = normed(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16))
    q32, k32 = (t[0, :n, 0].astype(jnp.float32) for t in (qn, kn))
    gam = jnp.cumsum(log_decays(x, a)[0, :n, 0], axis=0)
    b = beta[0, :n, 0, None]
    eye = jnp.eye(n, dtype=jnp.float32)

    def gaps(a, t):
        with jax.default_matmul_precision("highest"):
            want = jax.scipy.linalg.solve_triangular(eye + a, eye, lower=True)
        unit = jnp.dot(eye + a, t, precision=jax.lax.Precision.HIGHEST)
        return (float(jnp.max(jnp.abs(t - want)) / jnp.max(jnp.abs(want))),
                float(jnp.max(jnp.abs(unit - eye))))

    am, t = _a_and_inverse(q32, k32, gam, b)
    assert float(jnp.max(jnp.abs(am))) > 0.1
    assert max(gaps(am, t)) < 1e-6
    assert max(gaps(am, ops._inverse(am))) < 1e-6
    if n > SUB:
        dot = ops._dot
        monkeypatch.setattr(ops, "_dot", lambda a, b, dims, prec: dot(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims, prec))
        assert min(gaps(am, ops._inverse(am))) > 1e-5


def test_a_row_starts_from_a_zero_state():
    """Two rows are two sequences: a row's output does not depend on the
    other row."""
    (q, k, v, x, a, beta), _ = _inputs()
    both = kda(q, k, v, x, a, beta, chunk=CHUNK, impl="kernel")
    alone = kda(q[1:], k[1:], v[1:], x[1:], a, beta[1:], chunk=CHUNK,
                impl="kernel")
    np.testing.assert_allclose(both[1:], alone, atol=1e-6)


def test_each_kernel_is_traced_once_a_shape(monkeypatch):
    """A step calls the forward kernel in every KDA layer's forward pass and
    again in its recompute, and the backward kernel once a layer. Each
    kernel's body is unrolled in Python, so it is traced once for all the
    calls of one shape (a step's twelve traces took half its trace and
    lowering), and every call keeps the scope it was made in."""
    import tony_tpu.ops.kda as ops

    traced = []
    real = ops._call
    monkeypatch.setattr(ops, "_call", lambda kernel, name, *a, **k: (
        traced.append(name), real(kernel, name, *a, **k))[1])
    # a shape no other test traces: the jit's cache starts empty for it
    (q, k, v, x, a, beta), w = _inputs()
    args = [t[:, :3 * CHUNK] for t in (q, k, v, x)] + [a, beta[:, :3 * CHUNK]]

    def loss(*z):
        out = 0.0
        for layer in range(3):
            with jax.named_scope(f"layer_{layer}"):
                o = jax.checkpoint(lambda *y: kda(*y, chunk=CHUNK,
                                                  impl="kernel"))(*z)
            out = out + jnp.sum(o * w[:, :3 * CHUNK])
        return out

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=range(6)))(*args)
    # six forward calls and three backward ones; jax traces a checkpoint's
    # forward pass and its recompute in two contexts, so two at most
    assert traced.count("kda_fwd") <= 2 and traced.count("kda_bwd") == 1, \
        traced
    text = str(jaxpr)
    assert text.count("name=kda_fwd") == 6 and text.count("name=kda_bwd") == 3
    for layer in range(3):
        assert f"layer_{layer}" in str([
            e.source_info.name_stack for e in jaxpr.jaxpr.eqns])


def test_normed_is_what_the_recurrence_reads():
    (q, k, *_), _ = _inputs()
    qn, kn = normed(q, k)
    np.testing.assert_allclose(jnp.linalg.norm(kn, axis=-1), 1.0, rtol=1e-5)
    np.testing.assert_allclose(jnp.linalg.norm(qn, axis=-1), K ** -0.5,
                               rtol=1e-5)


@pytest.mark.parametrize("shape, chunk, message", [
    ((1, 96, 1, 8), 64, "chunks of 64"),
    ((1, 48, 1, 8), 24, f"sub-chunks of {SUB}")])
def test_shapes_that_are_no_whole_chunks_are_refused(shape, chunk, message):
    z = jnp.zeros(shape)
    with pytest.raises(ValueError, match=message):
        kda(z, z, z, z, jnp.zeros(shape[2:3]), z[..., 0], chunk=chunk)


def test_an_unknown_impl_is_refused():
    z = jnp.zeros((1, 64, 1, 8))
    with pytest.raises(ValueError, match="neither"):
        kda(z, z, z, z, jnp.zeros(1), z[..., 0], impl="triton")
