"""The chunked state-space scan (``ops/ssd.py``) against the recurrence it
stands for, token by token: the Mosaic kernels in interpret mode and the
``jax.numpy`` chunked path, ``y`` and the gradient of every input; a state
carried over a chunk's boundary; rows that do not leak into each other; heads
in packs and alone, a group's heads in blocks of eight, and one group of 64
heads at chunks of 256 against the ``jax.numpy`` path; bf16 inputs'
gradients as close as they were before the shared products were taken once;
the products a grid step runs, counted; a length that is no whole number of
chunks refused."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops import ssd as ssd_module
from tony_tpu.ops.ssd import _block_heads, _pack, carried_states, ssd

IMPLS = ("kernel", "jnp")


def recurrence(x, dt, a, b, c, d):
    """``S_t = a_t S_{t−1} + Δ_t B_t x_tᵀ``, ``y_t = S_tᵀ C_t + D x_t``, one
    token at a time, every row from a zero state."""
    _, _, h, p = x.shape
    per = h // b.shape[2]

    def row(x, dt, b, c):
        def token(state, at):
            x_t, dt_t, b_t, c_t = at
            b_t, c_t = (jnp.repeat(m, per, axis=0) for m in (b_t, c_t))
            state = jnp.exp(dt_t * a)[:, None, None] * state \
                + (dt_t[:, None] * b_t)[:, :, None] * x_t[:, None, :]
            return state, jnp.einsum("hnp,hn->hp", state, c_t) \
                + d[:, None] * x_t

        return jax.lax.scan(token, jnp.zeros((h, b.shape[-1], p)),
                            (x, dt, b, c))[1]

    return jax.vmap(row)(x, dt, b, c)


def inputs(batch=2, s=64, h=4, p=8, g=2, n=16, seed=0, slow=False):
    """Random inputs; ``slow`` gives decays near 1, a memory of hundreds of
    tokens."""
    ks = jax.random.split(jax.random.key(seed), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, s, h)) - 1)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)) - (5 if slow else 0))
    return (jax.random.normal(ks[0], (batch, s, h, p)), dt, a,
            jax.random.normal(ks[3], (batch, s, g, n)) / 2,
            jax.random.normal(ks[4], (batch, s, g, n)) / 2,
            jax.random.normal(ks[5], (h,)))


def close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) or 1.0
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=tol)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("shape", [
    dict(h=4, p=8, g=2),        # two heads a group, worked on side by side
    dict(h=6, p=8, g=2),        # three heads a pack
    dict(h=2, p=128, g=1),      # heads as wide as the lanes: one at a time
    dict(h=4, p=8, g=4),        # a group a head
    dict(h=4, p=64, g=1),       # the cell's kind: two packs of two in a group
    dict(h=8, p=64, g=2),       # and several such groups
    dict(h=16, p=64, g=1)],     # a group of two head blocks
    ids=["pairs", "threes", "wide", "alone", "packs", "groups-of-packs",
         "blocks"])
def test_y_and_every_gradient_match_the_recurrence(impl, shape):
    """Four chunks of 16: the output and the gradients of x, Δ, A, B, C and
    D are those of the token-by-token recurrence."""
    args = inputs(**shape)
    weight = jax.random.normal(jax.random.key(9), args[0].shape)

    def scalar(f):
        return lambda *a: jnp.sum(f(*a) * weight)

    with jax.default_matmul_precision("highest"):
        close(ssd(*args, chunk=16, impl=impl), recurrence(*args))
        got = jax.grad(scalar(lambda *a: ssd(*a, chunk=16, impl=impl)),
                       argnums=range(6))(*args)
        want = jax.grad(scalar(recurrence), argnums=range(6))(*args)
    for g, w in zip(got, want):
        assert np.asarray(w).any()
        close(g, w)


def test_packs_fill_the_lanes():
    assert [_pack(8, 64), _pack(4, 8), _pack(6, 8), _pack(2, 128),
            _pack(1, 8), _pack(8, 48)] == [2, 4, 6, 1, 1, 2]


def test_a_grid_step_holds_at_most_eight_heads():
    """A group of 8 is one block (the grid of 8 groups keeps its steps); one
    group of 64 is eight blocks of 8."""
    assert [_block_heads(8), _block_heads(64), _block_heads(16),
            _block_heads(6), _block_heads(12), _block_heads(1)] == [
        8, 8, 8, 6, 6, 1]


@pytest.mark.parametrize("seed", [0, 1])
def test_one_group_of_64_heads_at_chunks_of_256_is_the_jnp_path(seed):
    """The published Granite 4.0-H mixer's scan: 64 heads of 64 reading one
    group's B and C (state 128), two chunks of 256: the kernels, eight head
    blocks a group, give the ``jax.numpy`` path's output and gradients."""
    args = inputs(batch=1, s=512, h=64, p=64, g=1, n=128, seed=seed)
    weight = jax.random.normal(jax.random.key(9), args[0].shape)

    def scalar(impl):
        return lambda *a: jnp.sum(ssd(*a, chunk=256, impl=impl) * weight)

    with jax.default_matmul_precision("highest"):
        close(ssd(*args, chunk=256, impl="kernel"),
              ssd(*args, chunk=256, impl="jnp"))
        got, want = (jax.grad(scalar(impl), argnums=range(6))(*args)
                     for impl in IMPLS)
    for g, w in zip(got, want):
        assert np.asarray(w).any()
        close(g, w)


@pytest.mark.parametrize("impl", IMPLS)
def test_a_state_is_carried_over_a_chunks_boundary(impl):
    """With decays near 1 a token in the first chunk moves the last chunk's
    output, and by what the recurrence says; the gradient comes back over the
    boundaries too."""
    args = inputs(batch=1, s=64, slow=True, seed=3)
    bumped = (args[0].at[0, 2].add(1.0), *args[1:])
    with jax.default_matmul_precision("highest"):
        moved = ssd(*bumped, chunk=16, impl=impl) - ssd(*args, chunk=16,
                                                        impl=impl)
        want = recurrence(*bumped) - recurrence(*args)
        late = jax.grad(lambda x: jnp.sum(ssd(x, *args[1:], chunk=16,
                                              impl=impl)[0, 48:]))(args[0])
    assert float(jnp.max(jnp.abs(want[0, 48:]))) > 1e-2
    close(moved, want)
    assert float(jnp.max(jnp.abs(late[0, :16]))) > 1e-2


@pytest.mark.parametrize("impl", IMPLS)
def test_rows_do_not_leak_into_each_other(impl):
    """Every row starts from a zero state: a second row's inputs change
    nothing of the first row's output, and its output's gradient reaches
    nothing of the first row's inputs."""
    args = inputs(batch=2, slow=True, seed=5)
    other = inputs(batch=2, slow=True, seed=6)
    mixed = tuple(a if a.ndim == 1 else a.at[1].set(o[1])
                  for a, o in zip(args, other))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_array_equal(
            ssd(*args, chunk=16, impl=impl)[0],
            ssd(*mixed, chunk=16, impl=impl)[0])
        dx = jax.grad(lambda x: jnp.sum(ssd(x, *args[1:], chunk=16,
                                            impl=impl)[1]))(args[0])
    assert not np.asarray(dx[0]).any() and np.asarray(dx[1]).any()


def test_the_hand_over_is_what_the_fault_of_the_cells_tests_removes():
    """``carried_states``: each chunk is handed what the chunks before it
    left, decayed; zeroed, only the first chunk's output stays right."""
    args = inputs(batch=1, s=64, slow=True, seed=7)
    with jax.default_matmul_precision("highest"):
        want = recurrence(*args)
        original = ssd_module.carried_states
        ssd_module.carried_states = lambda left, kept: jnp.zeros_like(left)
        try:
            broken = ssd(*args, chunk=16, impl="jnp")
        finally:
            ssd_module.carried_states = original
    close(broken[0, :16], want[0, :16])
    assert float(jnp.max(jnp.abs(broken[0, 16:] - want[0, 16:]))) > 1e-2
    left = jnp.ones((1, 3, 2, 4, 4))
    kept = jnp.full((1, 3, 2), 0.5)
    np.testing.assert_allclose(
        carried_states(left, kept)[0, :, 0, 0, 0], [0.0, 1.0, 1.5])


def test_bf16_inputs_multiply_in_bf16_and_come_back_in_bf16():
    args = inputs()
    low = tuple(a.astype(jnp.bfloat16) if a.ndim == 4 else a for a in args)
    got = ssd(*low, chunk=16, impl="kernel")
    assert got.dtype == jnp.bfloat16
    close(got.astype(jnp.float32), recurrence(*args), tol=3e-2)


# The largest relative error (norm of the difference over the norm) of each
# gradient, bf16 x, B and C through the kernels against the float32
# recurrence on the same rounded inputs, that the kernels read BEFORE the
# heads' shared products were taken once (commit fdcf86b, interpreted: four
# shapes, seeds 0 to 2). A's gradient is a sum over every token into a few
# numbers and swings with the seed (0.0004 to 0.0073 there).
BF16_GRADIENT_ERRORS = dict(x=0.00298, dt=0.00200, a=0.00726, b=0.00315,
                            c=0.00323, d=0.00248)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("shape", [dict(h=4, p=64, g=1), dict(h=8, p=64, g=2)],
                         ids=["packs", "groups-of-packs"])
def test_bf16_gradients_are_as_close_as_a_product_a_head_left_them(shape,
                                                                   seed):
    """A scaling moved from a rounded operand to a float32 result, or from
    one operand to the other, rounds no worse: every gradient stays within
    what the kernels read with a product a head."""
    args = inputs(**shape, seed=seed)
    weight = jax.random.normal(jax.random.key(9), args[0].shape)
    low = tuple(a.astype(jnp.bfloat16) if a.ndim == 4 else a for a in args)

    def scalar(f):
        return lambda *a: jnp.sum(f(*a).astype(jnp.float32) * weight)

    with jax.default_matmul_precision("highest"):
        want = jax.grad(scalar(recurrence), argnums=range(6))(
            *(a.astype(jnp.float32) for a in low))
    got = jax.grad(scalar(lambda *a: ssd(*a, chunk=16, impl="kernel")),
                   argnums=range(6))(*low)
    for (name, limit), g, w in zip(BF16_GRADIENT_ERRORS.items(), got, want):
        error = float(jnp.linalg.norm(g.astype(jnp.float32) - w)
                      / jnp.linalg.norm(w))
        assert error <= limit, (name, error, limit)


def _tile_products(kernel_call, *shapes):
    """The ``[128, 128]`` tile products of one grid step of a kernel, from
    the ``dot_general``s of its jaxpr (traced, nothing run)."""
    jaxpr = jax.make_jaxpr(kernel_call)(
        *(jax.ShapeDtypeStruct(s, d) for s, d in shapes))
    [call] = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]

    def dots(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from dots(sub)

    tiles = 0
    for eqn in dots(call.params["jaxpr"]):
        (lhs, rhs), (out,) = eqn.invars, eqn.outvars
        (contract, _), _ = eqn.params["dimension_numbers"]
        k = math.prod(lhs.aval.shape[i] for i in contract)
        tiles += math.prod(math.ceil(n / 128) for n in (*out.aval.shape, k))
    return tiles


def test_a_grid_step_takes_a_shared_product_once():
    """At the cell's group (8 heads of 64 in pairs, state 128, chunk 128) the
    backward runs 39 tile products a grid step, two a head (``Mᵀ dY``,
    ``dM``), five a pack and three a group, where a product a head ran 51;
    the forward 17 where 21."""
    q, heads, p, n = 128, 8, 64, 128
    lo, f32 = jnp.bfloat16, jnp.float32
    x, b = ((1, q, heads * p), lo), ((1, q, n), lo)
    scalars, d = ((1, 1, 1, heads, q), f32), ((1, 1, heads * p), f32)
    states = ((1, 1, 1, n, heads * p), f32)
    forward = _tile_products(ssd_module._fwd_call, x, b, b, scalars, scalars,
                             d)
    backward = _tile_products(ssd_module._bwd_call, x, b, b, scalars,
                              scalars, d, states, x)
    assert forward <= 17, forward
    assert backward <= 40, backward


def test_a_length_that_is_no_whole_number_of_chunks_is_refused():
    args = inputs(s=40)
    for impl in (*IMPLS, None):
        with pytest.raises(ValueError, match="whole number of chunks"):
            ssd(*args, chunk=16, impl=impl)
    with pytest.raises(ValueError, match="neither"):
        ssd(*inputs(), chunk=16, impl="scan")
    with pytest.raises(ValueError, match="heads over"):
        ssd(*inputs(h=3, g=2), chunk=16)
