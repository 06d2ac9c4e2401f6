"""The expert layer (models/moe.py) against a plain dense masked sum, and
expert parallelism on the virtual 8-device CPU mesh (SURVEY.md §2.3 — EP is
a first-class requirement, no reference analogue)."""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.extend import core as jax_core

from tony_tpu.models import moe
from tony_tpu.models.moe import (ExpertLayer, ExpertSpec, MoEConfig,
                                 _buffer_rows, _combine, _dispatch,
                                 _gather_sum, _gmm_call, _layout,
                                 _orders_tokens, _tgmm_call, _token_order,
                                 moe_counters, routed_experts,
                                 routing_counters)
from tony_tpu.models.transformer import (Transformer, TransformerConfig,
                                         causal_lm_loss)
from tony_tpu.parallel import MeshSpec, build_mesh, init_sharded_state
from tony_tpu.parallel.sharding import DEFAULT_RULES

D = 24


def _tiles(rows, tile=8):
    """Rows an expert takes of a buffer, by hand: whole tiles, at least one."""
    return max(-(-int(rows) // tile), 1) * tile


def _rules():
    return nn.logical_axis_rules(list(DEFAULT_RULES))


def _spec(**kw):
    base = dict(n_experts=8, top_k=3, width=32, activation="relu",
                tile_rows=8, chunk_tokens=16)
    base.update(kw)
    return ExpertSpec(**base)


def _inputs(spec, tokens=(2, 16), seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(ks[0], (*tokens, D))
    r = jax.random.normal(ks[1], (*tokens, D))
    layer = ExpertLayer(spec, jnp.float32)
    with _rules():
        params = nn.meta.unbox(layer.init(ks[2], r, x))["params"]
    return layer, params, r, x


def dense_masked_sum(spec, p, r, x):
    """The plain form: every held expert over every token, weighted by the
    token's routing weight for it (zero where it was not chosen)."""
    first, count = spec.held or (0, spec.n_experts)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[spec.activation]
    t, rt = x.reshape(-1, D), r.reshape(-1, D)
    top, idx = jax.lax.top_k(rt @ p["router"], spec.top_k)
    w = jax.nn.softmax(top, -1)
    out = 0
    for e in range(count):
        we = jnp.sum(jnp.where(idx == first + e, w, 0), -1)
        ye = (act(t @ p["gate"][e]) * (t @ p["up"][e])) @ p["down"][e]
        out = out + we[:, None] * ye
    return out.reshape(x.shape)


def _same(spec, params, r, x, layer):
    with jax.default_matmul_precision("highest"):
        got = layer.apply({"params": params}, r, x)
        want = dense_masked_sum(spec, params, r, x)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
        grads = [jax.grad(lambda p, r, x: jnp.sum(jnp.sin(f(p, r, x))),
                          argnums=(0, 1, 2))(params, r, x)
                 for f in (lambda p, r, x: layer.apply({"params": p}, r, x),
                           lambda p, r, x: dense_masked_sum(spec, p, r, x))]
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, atol=5e-5, rtol=5e-5), *grads)
    return grads[0]


@pytest.mark.parametrize("kw", [
    dict(), dict(activation="silu"), dict(chunk_tokens=8192),
    dict(held=(2, 4)), dict(held=(6, 2), top_k=6), dict(tile_rows=16),
    dict(n_experts=1, top_k=1)],
    ids=["relu", "silu", "one-chunk", "held-2..5", "held-6..7-k6", "tile16",
         "one-expert"])
def test_expert_layer_is_the_dense_masked_sum(kw):
    """Forward and every gradient (experts, router, both inputs): nothing is
    dropped and nothing densified, whatever is held here."""
    spec = _spec(**kw)
    layer, params, r, x = _inputs(spec)
    _same(spec, params, r, x, layer)


@pytest.mark.parametrize("hot", [1, 3])
def test_no_token_is_dropped_at_any_load(hot):
    """Every token sends its first ``hot`` choices to the same experts: the
    fullest load a router can make. A capacity would drop rows here."""
    spec = _spec()
    layer, params, r, x = _inputs(spec)
    bias = jnp.zeros((D, spec.n_experts)).at[0, :hot].set(50.0)
    params = dict(params, router=params["router"] * 0.01 + bias)
    r = r.at[..., 0].set(1.0)
    _same(spec, params, r, x, layer)
    _, state = layer.apply({"params": params}, r, x,
                           mutable=["intermediates"])
    counters = moe_counters(state["intermediates"])
    assert float(counters["moe_expert_load_max_over_mean"]) >= 8 / 3 - 1e-6


# What 20 tokens a chunk send to experts 2 and 3, the quarter held of eight,
# two choices a token: (tokens that choose 2, tokens that choose 3, the live
# rows of a 56-row buffer in tiles of 8 that this makes).
LOADS = {"none-held": (0, 0, 16), "one-expert": (20, 0, 32),
         "a-tile-more": (20, 10, 40), "fullest": (20, 20, 48)}


@pytest.mark.parametrize("load,segment_rows", [
    ("even", 16), ("none-held", 16), ("one-expert", 16), ("a-tile-more", 16),
    ("fullest", 16), ("fullest", 8), ("a-tile-more", 40), ("fullest", 40)])
def test_a_quarter_held_at_every_load(monkeypatch, load, segment_rows):
    """XLA's passes over the row buffers stop at the live prefix, a segment at
    a time: output and every gradient are the dense masked sum's whether the
    live rows are the padding tiles alone, end on a segment's boundary (32
    rows in segments of 16), a tile past it, or fill the buffer as far as a
    router can (the last segment of 40 starts early and overlaps the first,
    whose rows the passes that work in place must leave alone; segments of 8
    divide the buffer and need no such care)."""
    monkeypatch.setattr(moe, "SEGMENT_ROWS", segment_rows)
    spec = _spec(held=(2, 2), top_k=2, chunk_tokens=20)
    layer, params, r, x = _inputs(spec, tokens=(2, 20))
    assert _buffer_rows(20, spec, 2) == 56
    if load != "even":
        to_2, to_3, live = LOADS[load]
        # Position s reads feature s alone, and the router sends it where the
        # plan says: its other choice is expert 0 or 1, held elsewhere.
        first = jnp.where(jnp.arange(20) < to_2, 2, 0)
        second = jnp.where(jnp.arange(20) < to_3, 3, 1)
        router = jnp.zeros((D, 8)).at[jnp.arange(20), first].set(50.0) \
            .at[jnp.arange(20), second].set(45.0)
        params = dict(params, router=router)
        r = jnp.broadcast_to(jnp.eye(20, D), (2, 20, D)) \
            * jnp.array([1.0, 1.1])[:, None, None]
    _same(spec, params, r, x, layer)
    _, state = layer.apply({"params": params}, r, x,
                           mutable=["intermediates"])
    share = float(moe_counters(
        state["intermediates"])["moe_buffer_rows_live_share"])
    if load != "even":
        assert share == pytest.approx(live / 56)
    assert 16 / 56 - 1e-6 <= share <= 48 / 56 + 1e-6


def test_combine_takes_its_weight_gradient_on_the_row_side():
    """``dweights[t, c] = Σ_d dout[t, d] · y[pos[t, c], d]``: the row side's
    one pass (a row's ``Σ_d dout · y`` beside its ``dy``, then a gather of
    scalars) against a gather of ``y``'s rows a choice, to float32
    rounding."""
    t, k, d, tile = 24, 3, 16, 8
    ks = jax.random.split(jax.random.key(7), 4)
    idx = jax.lax.top_k(jax.random.normal(ks[0], (t, 8)), k)[1]
    rows = _buffer_rows(t, _spec(), 4)
    held, pos, row_pair, row_live, _, n_active, _ = _layout(
        idx, 2, 4, tile, rows)
    y = jax.random.normal(ks[1], (rows, d))
    weights = jax.nn.softmax(jax.random.normal(ks[2], (t, k)))
    dout = jax.random.normal(ks[3], (t, d))
    _, vjp = jax.vjp(lambda y, w: _combine(y, w, pos, held,
                                           _token_order(held, k), row_pair,
                                           row_live, n_active, tile),
                     y, weights)
    dy, dweights = vjp(dout)
    safe = np.minimum(np.asarray(pos), rows - 1)
    want = np.stack([np.sum(np.asarray(dout) * np.asarray(y)[safe[:, c]],
                            axis=-1) for c in range(k)], axis=1)
    want = np.where(np.asarray(held), want, 0.0)
    assert np.asarray(held).any() and not np.asarray(held).all()
    np.testing.assert_allclose(dweights, want, rtol=1e-6, atol=1e-6)
    live = int(n_active[0]) * tile
    row_weight = np.where(np.asarray(row_live),
                          np.asarray(weights).reshape(-1)[row_pair], 0.0)
    np.testing.assert_allclose(
        dy[:live], (np.asarray(dout)[np.asarray(row_pair) // k]
                    * row_weight[:, None])[:live], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("n,k,count,ordered", [
    (256, 10, 8, True), (64, 6, 16, True), (4, 2, 1, True), (8, 3, 4, True),
    (4, 2, 4, False), (8, 6, 8, False), (8, 1, 2, False), (4, 2, 2, False),
    (8, 2, 7, False)])
def test_tokens_are_ordered_where_that_gathers_fewer_rows(monkeypatch, n, k,
                                                          count, ordered):
    """The static rule: a token's expected held pairs and the row back
    against the rows a loop over the choices gathers. Laguna's and
    SmallThinker's shares and an ``ep = 4`` shard of ``tiny_moe`` order their
    tokens; a layer that holds every expert, one choice a token, and half
    or most of two choices keep the loop."""
    monkeypatch.setattr(moe, "TOKEN_SEGMENT_ROWS", 4)
    spec = _spec(n_experts=n, top_k=k)
    assert _orders_tokens(spec, count) is ordered
    idx = jax.lax.top_k(jax.random.normal(jax.random.key(0), (64, n)), k)[1]
    share = float(routing_counters(_spec(n_experts=n, top_k=k,
                                         chunk_tokens=64), idx, 0, count)
                  ["moe_token_rows_gathered_share"])
    assert (share < 1.0) if ordered else (share == 1.0)


# (experts, a token's choices, first held, held, tokens, segment, what the
# router is pushed to: +1 towards the held experts, −1 away from them).
TOKEN_SIDE = {
    "no-pair-held": (8, 3, 2, 4, 24, 1024, -1),
    "every-pair-held": (4, 2, 0, 4, 24, 1024, 0),
    "a-token-with-all-it-can-hold": (8, 3, 2, 4, 24, 1024, +1),
    "prefixes-that-do-not-divide-the-segment": (8, 3, 2, 4, 20, 8, 0),
    "segments-that-do-not-divide-the-chunk": (8, 3, 2, 4, 20, 12, 0),
    "more-held-than-choices": (16, 2, 4, 6, 24, 8, 0),
    "fewer-held-than-choices": (8, 6, 6, 2, 24, 16, +1),
}


@pytest.mark.parametrize("case", sorted(TOKEN_SIDE))
def test_the_token_side_gathers_the_held_pairs(monkeypatch, case):
    """``_gather_sum`` with a token order against the plain definition
    ``Σ_c scale · src[pos]`` and against the loop over every choice (rule:
    ``_orders_tokens``), with NaN in every row of ``src`` past the live ones:
    the forward bit for bit (the held pairs are added in the order the loop
    adds them, and it adds exact zeros between), and the gradients that
    ``_dispatch`` and ``_combine`` make of it to float32 rounding."""
    n, k, first, count, t, segment, push = TOKEN_SIDE[case]
    monkeypatch.setattr(moe, "TOKEN_SEGMENT_ROWS", segment)
    spec = _spec(n_experts=n, top_k=k, held=(first, count))
    tile, d = spec.tile_rows, 16
    ks = jax.random.split(jax.random.key(11), 5)
    here = (jnp.arange(n) >= first) & (jnp.arange(n) < first + count)
    # The first tokens are pushed, the rest route as they fall.
    logits = jax.random.normal(ks[0], (t, n)) + 50.0 * push * here * (
        jnp.arange(t)[:, None] < (t if push < 0 else 3))
    idx = jax.lax.top_k(logits, k)[1]
    rows = _buffer_rows(t, spec, count)
    held, pos, row_pair, row_live, _, n_active, _ = _layout(
        idx, first, count, tile, rows)
    slots = min(k, count)
    mine = np.asarray(held).sum(axis=1)
    assert _orders_tokens(spec, count) == (case != "every-pair-held")
    assert mine.max() == {"no-pair-held": 0}.get(case, slots)
    if push >= 0:
        assert mine.min() < slots or count == n
    order = _token_order(held, slots)
    in_slot, listed, prefix, place = order
    np.testing.assert_array_equal(in_slot.sum(axis=(1, 2)), mine)
    np.testing.assert_array_equal(listed[place], np.arange(t))
    np.testing.assert_array_equal(
        prefix, [(mine > j).sum() for j in range(slots)])
    assert (np.diff(mine[np.asarray(listed)]) <= 0).all()

    live = int(n_active[0]) * tile
    src = jax.random.normal(ks[1], (rows, d)).at[live:].set(jnp.nan)
    weights = jax.nn.softmax(jax.random.normal(ks[2], (t, k)))
    safe = jnp.minimum(pos, rows - 1)

    def plain(src, scale):
        return jnp.sum(jnp.where(held[..., None], src[safe], 0.0)
                       * scale[..., None], axis=1)

    # Bit for bit where a product is exact (weights that are powers of two):
    # the CPU contracts a multiply and an add into one rounding, and which
    # product of a sum it takes differs between the two programs.
    halves = 0.5 ** jax.random.randint(ks[2], (t, k), 0, 4)
    for scale, exact in ((jnp.where(held, weights, 0.0), False),
                         (jnp.where(held, halves, 0.0), True),
                         (held.astype(jnp.float32), True)):
        got = _gather_sum(src, pos, scale, order)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, plain(src, scale), rtol=1e-6,
                                   atol=1e-6)
        if exact:
            np.testing.assert_array_equal(
                got, _gather_sum(src, pos, scale, None))

    # The two custom_vjps that call it, against autodiff of the plain form.
    use = order if _orders_tokens(spec, count) else None
    x = jax.random.normal(ks[3], (t, d))
    dxs = src.at[live:].set(0.0)    # a cotangent is finite everywhere
    _, vjp = jax.vjp(lambda x: _dispatch(x, row_pair // k, pos, held, use), x)
    want = jnp.zeros((t, d)).at[(row_pair // k)[:live]].add(
        jnp.where(row_live[:live, None], dxs[:live], 0.0))
    np.testing.assert_allclose(vjp(dxs)[0], want, rtol=1e-6, atol=1e-6)
    dout = jax.random.normal(ks[4], (t, d))
    out, vjp = jax.vjp(lambda y, w: _combine(
        y, w, pos, held, use, row_pair, row_live, n_active, tile), src,
        weights)
    want, plain_vjp = jax.vjp(
        lambda y, w: plain(y, jnp.where(held, w, 0.0)), dxs, weights)
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    for a, b in zip(vjp(dout), plain_vjp(dout)):
        np.testing.assert_allclose(a[:live] if a.shape[0] == rows else a,
                                   b[:live] if b.shape[0] == rows else b,
                                   rtol=1e-6, atol=1e-6)


def test_an_expert_with_no_rows_gets_a_zero_gradient():
    spec = _spec(top_k=2)
    layer, params, r, x = _inputs(spec)
    bias = jnp.zeros((D, spec.n_experts)).at[0, 5].set(-50.0)
    params = dict(params, router=params["router"] * 0.01 + bias)
    r = r.at[..., 0].set(1.0)
    grads = _same(spec, params, r, x, layer)[0]
    for name in ("gate", "up", "down"):
        assert not np.asarray(grads[name][5]).any()
        assert np.asarray(grads[name][4]).any()


def test_the_shares_add_up():
    """Four shares of two experts each, every one routing over all eight,
    sum to the layer that holds all eight."""
    whole = _spec()
    layer, params, r, x = _inputs(whole)
    want = layer.apply({"params": params}, r, x)
    got = 0
    for first in range(0, 8, 2):
        share = _spec(held=(first, 2))
        part = {k: (v if k == "router" else v[first:first + 2])
                for k, v in params.items()}
        got = got + ExpertLayer(share, jnp.float32).apply(
            {"params": part}, r, x)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_counters_of_a_share():
    spec = _spec(held=(0, 2), top_k=2)
    layer, params, r, x = _inputs(spec, tokens=(4, 64))
    _, state = layer.apply({"params": params}, r, x,
                           mutable=["intermediates"])
    c = {k: float(v) for k, v in moe_counters(
        state["intermediates"]).items()}
    _, idx = jax.lax.top_k(r.reshape(-1, D) @ params["router"], 2)
    held = np.asarray(idx) < 2
    assert c["moe_rows_routed"] == held.sum()
    assert c["moe_rows_unrouted_share"] == pytest.approx(
        1 - held.any(-1).mean())
    load = np.array([(np.asarray(idx) == e).sum() for e in range(2)])
    assert c["moe_expert_load_max_over_mean"] == pytest.approx(
        load.max() / load.mean())
    # By hand: a chunk of 16 tokens, each expert's rows in whole tiles of 8
    # and at least one, over the 6 tiles a chunk's buffer has.
    chunks = np.asarray(idx).reshape(-1, 16 * 2)
    live = [sum(_tiles((chunk == e).sum()) for e in range(2))
            for chunk in chunks]
    assert _buffer_rows(16, spec, 2) == 48 and len(live) == 16
    assert c["moe_buffer_rows_live_share"] == pytest.approx(
        np.mean(live) / 48)
    assert moe_counters({}) == {}


def test_the_live_share_is_one_device_s():
    """Tokens split two ways and experts four ways over devices: a chunk is
    a device's tokens and a buffer holds a device's experts, so the share is
    the mean over the eight (token group, expert group) pairs."""
    spec = _spec(top_k=2, chunk_tokens=8192)
    idx = jax.lax.top_k(jax.random.normal(jax.random.key(3), (64, 8)), 2)[1]
    got = float(routing_counters(spec, idx, 0, 8, token_groups=2,
                                 expert_groups=4)
                ["moe_buffer_rows_live_share"])
    live = [sum(_tiles((np.asarray(idx)[g * 32:(g + 1) * 32] == e).sum())
                for e in (2 * s, 2 * s + 1))
            for g in range(2) for s in range(4)]
    assert got == pytest.approx(np.mean(live) / _buffer_rows(32, spec, 2))


def test_a_dense_step_never_meets_the_expert_layer(monkeypatch):
    """A configuration without experts traces nothing of ``models/moe.py``:
    its compiled step cannot move with that file."""
    def refuse(*args, **kwargs):
        raise AssertionError("the expert layer was traced")

    for name in ("routed_experts", "routing_counters", "_live_rows",
                 "_layout", "_gmm_call", "_tgmm_call"):
        monkeypatch.setattr(moe, name, refuse)
    monkeypatch.setattr(ExpertLayer, "__call__", refuse)
    cfg = TransformerConfig(vocab_size=64, dim=32, n_layers=2, n_heads=2,
                            n_kv_heads=1, mlp_dim=64, max_seq_len=16,
                            dtype=jnp.float32)
    model = Transformer(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.key(0), tokens)["params"]
    text = jax.jit(jax.grad(lambda p: causal_lm_loss(
        model.apply({"params": p}, tokens), tokens))).lower(params).as_text()
    assert "moe" not in text


@pytest.mark.parametrize("transpose", [False, True])
def test_grouped_matmul_kernel(transpose):
    """moe_gmm over a hand-made layout with an empty expert and spare
    tiles: live rows equal their expert's product."""
    idx = jnp.array([[0], [2], [2], [0], [2], [2], [2], [2], [2], [2], [2]],
                    jnp.int32)
    held, pos, row_pair, live, te, na, sizes = _layout(idx, 0, 3, 4, 24)
    assert sizes.tolist() == [2, 0, 9] and int(na[0]) == 5
    assert te.tolist() == [0, 1, 2, 2, 2, 2]
    ks = jax.random.split(jax.random.key(0), 2)
    k, n = (16, 8)
    lhs = jax.random.normal(ks[0], (24, n if transpose else k))
    rhs = jax.random.normal(ks[1], (3, k, n))
    out = _gmm_call(lhs, rhs, te, na, tile_rows=4, transpose_rhs=transpose)
    row_expert = np.repeat(np.asarray(te), 4)
    for r in np.flatnonzero(np.asarray(live)):
        m = rhs[row_expert[r]]
        np.testing.assert_allclose(
            out[r], lhs[r] @ (m.T if transpose else m), atol=1e-5)


@pytest.mark.parametrize("running_sum", [False, True],
                         ids=["from-zero", "from-a-running-sum"])
def test_grouped_weight_gradient_kernel(running_sum):
    """moe_tgmm: an expert's block is the sum over its own rows, zero for an
    expert without rows, whatever lies in the tiles past the live ones. Handed
    a running sum it returns that sum with the same added, and an expert
    without rows keeps its sum to the bit."""
    idx = jnp.array([[0], [2], [2], [0], [2], [2], [2], [2], [2], [2], [2]],
                    jnp.int32)
    _, _, _, live, te, na, sizes = _layout(idx, 0, 3, 4, 24)
    assert sizes.tolist() == [2, 0, 9] and int(na[0]) == 5 < te.shape[0]
    ks = jax.random.split(jax.random.key(0), 3)
    lhs = jax.random.normal(ks[0], (24, 16))
    rhs = jnp.where(live[:, None], jax.random.normal(ks[1], (24, 8)), 0.0)
    rhs = rhs.at[20:].set(jnp.nan)        # unwritten rows: never read
    acc = jax.random.normal(ks[2], (3, 16, 8)) if running_sum else None
    out = _tgmm_call(lhs, rhs, te, na, tile_rows=4, count=3, acc=acc)
    row_expert = np.repeat(np.asarray(te), 4)
    for e in range(3):
        rows = np.flatnonzero(np.asarray(live) & (row_expert == e))
        want = lhs[rows].T @ rhs[rows] + (acc[e] if running_sum else 0.0)
        np.testing.assert_allclose(out[e], want, atol=1e-5)
    if running_sum:
        alone = _tgmm_call(lhs, rhs, te, na, tile_rows=4, count=3)
        np.testing.assert_allclose(out, acc + alone, atol=1e-6, rtol=1e-6)
        np.testing.assert_array_equal(out[1], acc[1])
    else:
        assert not np.asarray(out[1]).any()


def _routed(spec, int8=False, tokens=32, seed=3):
    """``routed_experts`` over hand-made routing, as a function of all that
    takes a gradient: the tokens, the routing weights, the three leaves."""
    first, count = spec.held
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (tokens, D))
    idx = jax.lax.top_k(jax.random.normal(ks[1], (tokens, spec.n_experts)),
                        spec.top_k)[1]
    weights = jax.nn.softmax(jax.random.normal(ks[2], (tokens, spec.top_k)))
    leaves = tuple(jax.random.normal(k, shape) / 4 for k, shape in zip(
        ks[3:], [(count, D, spec.width)] * 2 + [(count, spec.width, D)]))

    def through(spec):
        return lambda x, weights, *leaves: routed_experts(
            spec, x, idx, weights, leaves, first, jnp.float32, int8=int8)

    def dense(x, weights, gate, up, down):
        out = 0
        for e in range(count):
            we = jnp.sum(jnp.where(idx == first + e, weights, 0), -1)
            out = out + we[:, None] * (
                (jax.nn.relu(x @ gate[e]) * (x @ up[e])) @ down[e])
        return out

    return through, dense, (x, weights, *leaves)


@pytest.mark.parametrize("int8", [False, True], ids=["plain", "int8-forward"])
def test_four_chunks_give_the_gradient_one_chunk_gives(int8):
    """A quarter of the experts held, the tokens in four chunks of 8 or in one
    of 32: the result and the gradients of the tokens, the routing weights
    and the three expert leaves are the same (the leaves' summed over the
    chunks inside ``moe_tgmm``, a running sum carried from chunk to chunk),
    and, without quantization, the dense masked sum's."""
    spec = _spec(held=(2, 2), top_k=2, chunk_tokens=8)
    through, dense, args = _routed(spec, int8)

    def value_and_grads(f):
        return jax.value_and_grad(lambda *a: jnp.sum(jnp.sin(f(*a))),
                                  argnums=tuple(range(5)))(*args)

    with jax.default_matmul_precision("highest"):
        four = value_and_grads(through(spec))
        others = [value_and_grads(through(
            dataclasses.replace(spec, chunk_tokens=8192)))]
        if not int8:
            others.append(value_and_grads(dense))
    assert all(np.asarray(g).any() for g in four[1])
    for other in others:
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, atol=2e-5, rtol=2e-5), four, other)


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (tuple, list))
                          else (value,)):
                inner = getattr(inner, "jaxpr", inner)      # a closed one
                if isinstance(inner, jax_core.Jaxpr):
                    yield from _eqns(inner)


def test_the_chunk_loop_s_backward_sums_inside_the_kernel():
    """The backward of a layer of four chunks is one loop over the chunks
    whose carry is the three leaves' running sums: each trip hands each of
    its three ``moe_tgmm`` calls its sum (the fifth operand after the two
    prefetched tables and the two row buffers) in the result's own buffer,
    and no ``add`` of the loop yields anything of a leaf's shape."""
    spec = _spec(held=(2, 2), top_k=2, chunk_tokens=8)
    through, _, args = _routed(spec)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(through(spec)(*a)), argnums=(2, 3, 4)))(*args)
    leaf_shapes = {a.shape for a in args[2:]}
    loops = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "scan"
             and {v.aval.shape for v in e.outvars[:e.params["num_carry"]]}
             == leaf_shapes]
    assert len(loops) == 1, [e.params["num_carry"] for e in loops]
    (loop,) = loops
    assert loop.params["length"] == 4 and loop.params["num_carry"] == 3
    body = list(_eqns(loop.params["jaxpr"].jaxpr))

    def sums(eqns):     # how each moe_tgmm call aliases its operands
        return [e.params["input_output_aliases"] for e in eqns
                if e.primitive.name == "pallas_call"
                and e.params["name"] == "moe_tgmm"]

    assert sums(body) == [((4, 0),)] * 3, sums(body)
    # The forward's loop holds no such call, and nothing outside this one.
    assert sums(_eqns(jaxpr.jaxpr)) == sums(body)
    adds = [e for e in body if e.primitive.name in ("add", "add_any")
            and e.outvars[0].aval.shape in leaf_shapes]
    assert not adds, adds


def test_int8_experts_quantize_the_forward_alone():
    """``matmul_dtype="int8"``: the three forward products are the int8
    ones of the quantized rows and matrices exactly, and the gradients are
    those of the unquantized products at that forward (straight through).
    Another quantized dtype is refused by name."""
    from tony_tpu.ops.quant import quantize_symmetric

    spec = _spec(held=(2, 4))
    layer, params, r, x = _inputs(spec)
    int8 = ExpertLayer(spec, jnp.float32, matmul_dtype="int8")

    def fake(v, axis):          # what int8 keeps of a tensor, as float32
        q, scale = quantize_symmetric(v, "int8", axis)
        return q.astype(jnp.float32) * scale

    def quantized_dense_sum(p, r, x):
        t, rt = x.reshape(-1, D), r.reshape(-1, D)
        top, idx = jax.lax.top_k(rt @ p["router"], spec.top_k)
        w = jax.nn.softmax(top, -1)
        out = 0
        for e in range(4):
            we = jnp.sum(jnp.where(idx == 2 + e, w, 0), -1)
            hidden = jax.nn.relu(fake(t, -1) @ fake(p["gate"][e], 0)) \
                * (fake(t, -1) @ fake(p["up"][e], 0))
            out = out + we[:, None] * (fake(hidden, -1)
                                       @ fake(p["down"][e], 0))
        return out.reshape(x.shape)

    with jax.default_matmul_precision("highest"):
        got = int8.apply({"params": params}, r, x)
        np.testing.assert_allclose(got, quantized_dense_sum(params, r, x),
                                   atol=2e-5, rtol=2e-5)
        plain = layer.apply({"params": params}, r, x)
        gap = float(jnp.linalg.norm(got - plain) / jnp.linalg.norm(plain))
        assert 1e-3 < gap < 5e-2, gap
        g8, g = (jax.grad(lambda p, f=f: jnp.sum(jnp.sin(
            f.apply({"params": p}, r, x))))(params) for f in (int8, layer))
    for name in ("up", "down", "router"):   # gate meets ReLU's flips
        rel = float(jnp.linalg.norm(g8[name] - g[name])
                    / jnp.linalg.norm(g[name]))
        assert 0 < rel < 5e-2, (name, rel)
    with pytest.raises(ValueError, match="int8 path alone"):
        ExpertLayer(spec, jnp.float32, matmul_dtype="fp8_e4m3").apply(
            {"params": params}, r, x)


# ---------------------------------------------------------------------------
# Expert parallelism on a CPU mesh
# ---------------------------------------------------------------------------
def _lm(cfg, mesh, tokens):
    model = Transformer(cfg)
    state, sh = init_sharded_state(model, tokens, optax.adam(3e-3), mesh,
                                   rng=jax.random.key(5))

    def loss_fn(p):
        with _rules():
            return causal_lm_loss(model.apply({"params": p}, tokens), tokens)

    return state, loss_fn


def test_ep4_equals_one_device():
    """The same weights on dp=2 x ep=4 and on dp=8: loss and gradients."""
    cfg = MoEConfig.tiny_moe(attn_impl="flash")
    tokens = jax.random.randint(jax.random.key(0), (8, 32), 0,
                                cfg.vocab_size)
    out = []
    for spec in (MeshSpec(dp=2, ep=4), MeshSpec(dp=8)):
        mesh = build_mesh(spec)
        state, loss_fn = _lm(cfg, mesh, tokens)
        with jax.set_mesh(mesh):
            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(state.params)
        out.append((float(loss), jax.tree.map(np.asarray, grads)))
    assert out[0][0] == pytest.approx(out[1][0], rel=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, atol=2e-6, rtol=2e-5), out[0][1], out[1][1])


def test_moe_transformer_trains_on_ep_mesh():
    """Full train step on a dp×ep mesh: loss finite and decreasing."""
    mesh = build_mesh(MeshSpec(dp=4, ep=2))
    cfg = MoEConfig.tiny_moe()
    tokens = jax.random.randint(jax.random.key(0), (8, 32), 0,
                                cfg.vocab_size)
    state, loss_fn = _lm(cfg, mesh, tokens)

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
    state, _ = _lm(cfg, mesh, jnp.zeros((8, 16), jnp.int32))
    gate = state.params["layer_0"]["moe"]["gate"]
    assert gate.shape[0] == cfg.layer(0).experts.n_experts
    for shard in gate.addressable_shards:
        assert shard.data.shape[0] == gate.shape[0] // mesh.shape["ep"]


def test_ep_sums_the_shares_and_moves_no_rows():
    """Under ep the shards' partial results meet in one reduce-scatter (or
    the all-reduce it folds into); no all-to-all, which needed a capacity."""
    mesh = build_mesh(MeshSpec(dp=4, ep=2))
    cfg = MoEConfig.tiny_moe()
    state, loss_fn = _lm(cfg, mesh, jnp.zeros((8, 16), jnp.int32))
    with jax.set_mesh(mesh):
        txt = jax.jit(jax.grad(loss_fn)).lower(state.params).compile()\
            .as_text()
    assert "reduce-scatter" in txt or "all-reduce" in txt
    assert "all-to-all" not in txt


def test_ep_refuses_a_named_share():
    mesh = build_mesh(MeshSpec(dp=4, ep=2))
    spec = _spec(held=(0, 4))
    layer, params, r, x = _inputs(spec)
    with jax.set_mesh(mesh), pytest.raises(ValueError, match="no share"):
        jax.jit(lambda p: layer.apply({"params": p}, r, x))(params)
