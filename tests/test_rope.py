"""RoPE as the program computes it (``models/transformer.py _rope``: each
head turned as one row of D columns, ``x·cos' + (x R)·sin'`` with R the
constant swap of the rotated columns' halves) against the formula it
replaced, kept here as the reference: the rotated columns split into two
halves, turned pair by pair and concatenated with the columns that pass
through. Plain RoPE over the whole head (θ 10,000) and Laguna's full-layer
RoPE (half the head, θ 500,000, YaRN from 8,192 by 128), in bf16 and f32,
at positions up to 32,768."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models.transformer import RopeSpec, Yarn, _rope

ROPES = {
    "whole_head": RopeSpec(theta=10000.0),
    "half_head_yarn": RopeSpec(theta=500000.0, rotated=0.5, yarn=Yarn(
        factor=128, original_max_position=8192, beta_fast=32, beta_slow=1,
        attention_factor=1.4852030263919618)),
}
DTYPES = {"bf16": (jnp.bfloat16, 7), "f32": (jnp.float32, 23)}
B, S, H, D = 2, 256, 3, 128


def split_rope(x, positions, rope):
    """The half-split rotation: f32 trig, f32 arithmetic, one cast back."""
    d = x.shape[-1]
    freqs, factor = rope.table(d)
    rot = 2 * freqs.shape[0]
    angles = positions[:, :, None, None].astype(jnp.float32) \
        * freqs[None, None, None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf[..., :rot], 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            xf[..., rot:]], axis=-1).astype(x.dtype)


def _inputs(dtype, seed=0):
    x = jax.random.normal(jax.random.key(seed), (B, S, H, D)).astype(dtype)
    positions = jax.random.randint(jax.random.key(seed + 1), (B, S), 0,
                                   32769)
    # the two ends of the range are there whatever the draw
    positions = positions.at[0, 0].set(0).at[1, -1].set(32768)
    return x, positions


def _ulps(got, want, mantissa_bits):
    """|got − want| in units of the last place of the larger of the two, in
    a format of ``mantissa_bits`` stored bits."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    size = np.maximum(np.abs(got), np.abs(want))
    exponent = np.floor(np.log2(np.maximum(size, np.finfo(np.float32).tiny)))
    return np.abs(got - want) / 2.0 ** (exponent - mantissa_bits)


@pytest.mark.parametrize("rope", sorted(ROPES))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("what", ["forward", "gradient"])
def test_the_whole_row_rotation_matches_the_split_formula(what, dtype, rope):
    """Forward, and the gradient of a weighted sum of it, within one unit in
    the last place of the dtype: both are f32 arithmetic rounded once (the
    backward is the rotation by −θ, not autodiff's two roundings)."""
    dtype, bits = DTYPES[dtype]
    spec = ROPES[rope]
    x, positions = _inputs(dtype)
    if what == "forward":
        got, want = _rope(x, positions, spec), split_rope(x, positions, spec)
    else:
        w = jax.random.normal(jax.random.key(7), x.shape)

        def grad(fn):
            return jax.grad(lambda x: jnp.sum(
                fn(x, positions, spec).astype(jnp.float32) * w))(x)

        got, want = grad(_rope), grad(split_rope)
    assert got.dtype == want.dtype == dtype
    assert _ulps(got, want, bits).max() <= 1.0


@pytest.mark.parametrize("rope", sorted(ROPES))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_position_zero_is_the_identity(dtype, rope):
    """At position 0 every angle is 0: each column comes back as it was,
    times YaRN's factor on the rotated ones."""
    dtype, _ = DTYPES[dtype]
    spec = ROPES[rope]
    x, _ = _inputs(dtype)
    _, factor = spec.table(D)
    rot = int(D * spec.rotated)
    out = _rope(x, jnp.zeros((B, S), jnp.int32), spec)
    want = np.array(x, np.float32)
    want[..., :rot] *= factor
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(jnp.asarray(want).astype(dtype), np.float32))


@pytest.mark.parametrize("rope", sorted(ROPES))
def test_each_rotated_pair_keeps_its_norm(rope):
    """In f32, column j and column j + rot/2 turn as one pair: its norm is
    the input pair's (times YaRN's factor)."""
    spec = ROPES[rope]
    x, positions = _inputs(jnp.float32)
    _, factor = spec.table(D)
    half = int(D * spec.rotated) // 2
    out = np.asarray(_rope(x, positions, spec))
    x = np.asarray(x)

    def pair_norms(a):
        return np.hypot(a[..., :half], a[..., half:2 * half])

    np.testing.assert_allclose(pair_norms(out), factor * pair_norms(x),
                               rtol=2e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_the_pass_through_columns_come_back_unchanged(dtype):
    """Past the rotated half of the head the columns are the input's, bit
    for bit, at every position."""
    dtype, _ = DTYPES[dtype]
    spec = ROPES["half_head_yarn"]
    x, positions = _inputs(dtype)
    out = _rope(x, positions, spec)
    np.testing.assert_array_equal(np.asarray(out[..., D // 2:], np.float32),
                                  np.asarray(x[..., D // 2:], np.float32))
