"""The program's ``Transformer`` under Nemotron-H's per-layer description
(layers of one part each: a Mamba-2 mixer, attention without position
embedding, or sigmoid-routed squared-ReLU experts of two matrices beside a
shared expert of its own width) against the benchmark's plain float32
reference of that architecture, loaded by path: tree, loss and every gradient
on seeded random weights at tiny widths; a one-part layer's tree and the
defaults'; the shares of the experts adding up to the uncut layer with the
shared expert counted once; the sigmoid scores' weights; column tiles that
hang over the edge; the mixer's own published draw; the sequence-parallel modes' refusal; and the seeded weights giving the
mixer heads that remember past a chunk."""

import dataclasses
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models.moe import (ExpertLayer, ExpertSpec, SharedExpert,
                                 _col_tile, _gmm_call, _tgmm_call)
from tony_tpu.models.ssm import A_RANGE, DT_FLOOR, DT_RANGE, SSMixer, SSMSpec
from tony_tpu.models.transformer import (LayerSpec, Transformer,
                                         TransformerConfig,
                                         chunked_causal_lm_loss,
                                         layer_counters)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = os.path.join(REPO, "benchmarks", "cells")
TINY = os.path.join(CELLS, "fixtures", "rehearsal_nemotron_h", "configs",
                    "tiny_nem.json")
TRAFFIC = {"global_batch": 2, "seq": 256, "mesh": "dp=1", "loss_chunk": 128}


@pytest.fixture(scope="module")
def bench():
    """The harness's modules by path (``arch``, ``reference``), and the
    architecture's three files through ``arch.load``."""
    sys.path.insert(0, CELLS)
    try:
        import arch
        import reference

        folder = os.path.join(CELLS, "architectures", "nemotron_h")
        mine = {part: arch.load(folder, part) for part in arch.PARTS}
        yield {"harness": reference, "ref": mine["reference"],
               "program": mine["program"], "counts": mine["counts"]}
    finally:
        sys.path.remove(CELLS)


@pytest.fixture(scope="module")
def cfg():
    with open(TINY, encoding="utf-8") as f:
        return json.load(f)


def _program(bench, cfg):
    mcfg = dataclasses.replace(
        bench["program"].model_config(cfg, TRAFFIC, ""), dtype=jnp.float32)
    model = Transformer(mcfg)

    def loss(params, tokens):
        h, sown = model.apply({"params": params}, tokens, return_hidden=True,
                              mutable=["intermediates"])
        return chunked_causal_lm_loss(
            h, params["lm_head"]["kernel"], tokens,
            chunk_size=128), layer_counters(sown["intermediates"])
    return model, mcfg, loss


def _tree(model, tokens):
    shapes = nn.meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                          tokens))["params"]
    return [(tuple(str(k.key) for k in path), leaf.shape) for path, leaf in
            sorted(jax.tree_util.tree_leaves_with_path(shapes),
                   key=lambda t: tuple(str(k.key) for k in t[0]))]


def test_every_layer_of_the_tiny_configuration_is_one_part(bench, cfg):
    _, mcfg, _ = _program(bench, cfg)
    assert cfg["hybrid_override_pattern"] == "MEMEM*EME"
    kinds = [("M" if isinstance(l.mixer, SSMSpec) else
              "*" if l.mixer == "attention" else "E", l.feed_forward,
              l.experts is not None) for l in mcfg.layers]
    assert kinds == [(k, k == "E", k == "E") for k in "MEMEM*EME"]
    mixer, experts = mcfg.layers[0].mixer, mcfg.layers[1].experts
    assert (mixer.n_heads, mixer.head_dim, mixer.n_groups, mixer.state,
            mixer.conv, mixer.chunk) == (8, 8, 2, 16, 4, 32)
    assert (experts.gated, experts.scoring, experts.activation,
            experts.shared_width, experts.width, experts.routed_scale,
            experts.held) == (False, "sigmoid", "relu2", 48, 32, 2.5, (4, 4))
    assert mcfg.layers[5].rope is False


def test_parameter_tree_is_the_references_leaf_for_leaf(bench, cfg):
    model, _, _ = _program(bench, cfg)
    got = _tree(model, jnp.zeros((2, 256), jnp.int32))
    want = [(path, shape) for path, shape, _ in bench["ref"].leaf_specs(cfg)]
    assert got == want
    assert sum(int(np.prod(s)) for _, s in got) == \
        bench["counts"].total_params(cfg)


def test_loss_and_gradients_match_the_reference(bench, cfg):
    harness, ref = bench["harness"], bench["ref"]
    _, _, loss = _program(bench, cfg)
    params = harness.make_params(ref, cfg, harness.seed_key(7))
    tokens = jnp.asarray(harness.token_rows(7, 0, 2, 256, cfg["vocab_size"]))
    with jax.default_matmul_precision("highest"):
        (got, aux), got_g = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(params, tokens)
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p, t: ref.loss_fn(cfg, p, t)))(params, tokens)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    for (path, _, _), g, w in zip(ref.leaf_specs(cfg), harness.flat(got_g),
                                  harness.flat(want_g)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=2e-4,
                                   err_msg="/".join(path))
    # the step's counters: the expert layers' and the mixers' three
    assert set(aux) == {"ssm_dt_mean", "ssm_decay_mean",
                        "ssm_head_rms_max_over_median", "moe_rows_routed",
                        "moe_rows_unrouted_share",
                        "moe_expert_load_max_over_mean",
                        "moe_buffer_rows_live_share",
                        "moe_token_rows_gathered_share"}
    assert 0.0 < float(aux["ssm_decay_mean"]) < 1.0
    assert float(aux["ssm_dt_mean"]) > 0.0


# ---------------------------------------------------------------------------
# A layer's parts
# ---------------------------------------------------------------------------
SSM = SSMSpec(n_heads=4, head_dim=8, n_groups=2, state=16, chunk=16)
EXPERTS = ExpertSpec(n_experts=4, top_k=2, width=32, tile_rows=8,
                     gated=False, activation="relu2", scoring="sigmoid")


def _layer_tree(spec):
    cfg = TransformerConfig.tiny(n_layers=1, layers=(spec,))
    tree = _tree(Transformer(cfg), jnp.zeros((1, 32), jnp.int32))
    return [path[1:] for path, _ in tree if path[0] == "layer_0"]


def test_a_layer_builds_the_parts_it_names_and_no_other():
    """One norm a part that is there; the defaults are attention and the
    dense MLP, under the names they have always had."""
    attn = [("attn", w, "kernel") for w in ("wk", "wo", "wq", "wv")] \
        + [("attn_norm", "scale")]
    mlp = [("mlp", w, "kernel") for w in ("down", "gate", "up")] \
        + [("mlp_norm", "scale")]
    assert _layer_tree(LayerSpec()) == attn + mlp
    assert _layer_tree(LayerSpec(mixer="attention", feed_forward=True)) \
        == attn + mlp
    assert _layer_tree(LayerSpec(feed_forward=False)) == attn
    assert _layer_tree(LayerSpec(mixer=None)) == mlp
    assert _layer_tree(LayerSpec(mixer=SSM, feed_forward=False)) == [
        ("ssm", "A_log"), ("ssm", "D"), ("ssm", "conv_bias"),
        ("ssm", "conv_kernel"), ("ssm", "dt_bias"), ("ssm", "norm"),
        ("ssm", "wdt"), ("ssm", "wo", "kernel"), ("ssm", "wxbc", "kernel"),
        ("ssm", "wz", "kernel"), ("ssm_norm", "scale")]
    assert _layer_tree(LayerSpec(mixer=None, experts=EXPERTS)) == [
        ("mlp_norm", "scale"), ("moe", "down"), ("moe", "router"),
        ("moe", "up")]
    assert LayerSpec() == LayerSpec(mixer="attention", feed_forward=True)


@pytest.mark.parametrize("spec, message", [
    (dict(mixer=None, feed_forward=False), "no part"),
    (dict(mixer="mamba"), "neither"),
    (dict(experts=EXPERTS, feed_forward=False), "names none")])
def test_a_layer_spec_that_names_no_layer_is_refused(spec, message):
    with pytest.raises(ValueError, match=message):
        LayerSpec(**spec)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sequence_parallel_attention_refuses_a_state_space_layer(impl):
    cfg = TransformerConfig.tiny(
        n_layers=2, attn_impl=impl,
        layers=(LayerSpec(), LayerSpec(mixer=SSM, feed_forward=False)))
    with pytest.raises(ValueError, match="layer_1's state-space mixer"):
        Transformer(cfg).init(jax.random.key(0), jnp.zeros((1, 32),
                                                            jnp.int32))


# ---------------------------------------------------------------------------
# The shares add up
# ---------------------------------------------------------------------------
def test_the_shares_add_up_to_the_uncut_reference_layer(bench, cfg):
    """All four shares of the sixteen experts (a deployment's 16 shares of
    128, at the tiny size) and the shared expert ONCE are the reference's
    sparse layer with every expert held."""
    harness, ref = bench["harness"], bench["ref"]
    whole_cfg = dict(cfg, num_hidden_layers=1, hybrid_override_pattern="E",
                     n_routed_experts=16, share={"first_expert_held": 0})
    whole = harness.make_params(ref, whole_cfg, harness.seed_key(11))[
        "layer_0"]
    x = jax.random.normal(jax.random.key(4), (1, 256, cfg["hidden_size"]))
    base = ExpertSpec(n_experts=16, top_k=4, width=32, tile_rows=16,
                      chunk_tokens=128, routed_scale=2.5, gated=False,
                      activation="relu2", scoring="sigmoid")
    with_shared = dataclasses.replace(base, shared_width=48)
    with jax.default_matmul_precision("highest"):
        n = harness.rmsnorm(x, whole["mlp_norm"]["scale"],
                            cfg["layer_norm_epsilon"])
        moe = whole["moe"]
        shared = SharedExpert(with_shared, jnp.float32, jnp.float32,
                              "").apply({"params": moe["shared"]}, n)
        out = x + shared
        for first in range(0, 16, 4):
            share = {k: (moe[k] if k == "router" else moe[k][first:first + 4])
                     for k in ("router", "up", "down")}
            routed = ExpertLayer(
                dataclasses.replace(base, held=(first, 4)),
                jnp.float32).apply({"params": share}, n, n)
            out = out + routed
            # a share's layer is its routed part and the shared expert
            both = ExpertLayer(
                dataclasses.replace(with_shared, held=(first, 4)),
                jnp.float32).apply(
                    {"params": dict(share, shared=moe["shared"])}, n, n)
            np.testing.assert_allclose(both - routed, shared, atol=3e-5)
        want = ref._layer(whole_cfg, whole, x[0], 0)
    assert float(jnp.linalg.norm(shared)) > 0
    np.testing.assert_allclose(out[0], want, atol=2e-4, rtol=2e-5)


def test_sigmoid_scores_weigh_the_chosen_by_their_own_score():
    """Sigmoid scoring: the two largest scores are chosen and each weighs
    its expert by its own score over the chosen's sum; a scoring the layer
    does not know is refused."""
    spec = dataclasses.replace(EXPERTS, tile_rows=8, chunk_tokens=16)
    x = jax.random.normal(jax.random.key(1), (2, 16, 24))
    layer = ExpertLayer(spec, jnp.float32)
    params = nn.meta.unbox(layer.init(jax.random.key(2), x, x))["params"]
    # experts that return their input's first columns: the result is the
    # sum of the chosen experts' weights times a known vector
    held = {k: jnp.zeros_like(v) for k, v in params.items()}
    held["router"] = params["router"]
    held["up"] = held["up"].at[:, :, 0].set(1.0)
    held["down"] = held["down"].at[:, 0, :].set(
        jnp.arange(1.0, 5.0)[:, None])
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x.reshape(-1, 24) @ params["router"])
        got = layer.apply({"params": held}, x, x)[..., 0].reshape(-1)
    top, idx = jax.lax.top_k(scores, 2)
    hidden = jnp.square(jax.nn.relu(jnp.sum(x.reshape(-1, 24), axis=-1)))
    want = hidden * jnp.sum(top * (idx + 1.0), axis=-1) / jnp.sum(top, -1)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="scoring"):
        dataclasses.replace(spec, scoring="tanh")


def test_a_column_tile_may_hang_over_the_edge():
    """1,856 columns have no divisor that is a multiple of 128: the tile is
    the one that covers them in the fewest columns, and what the last tile
    computes past the edge is never written."""
    assert (_col_tile(1856), _col_tile(1856, 512)) == (640, 384)
    assert (_col_tile(2688), _col_tile(2688, 512)) == (896, 384)
    assert (_col_tile(768), _col_tile(2560), _col_tile(3072),
            _col_tile(64)) == (768, 640, 1024, 64)
    ks = jax.random.split(jax.random.key(0), 3)
    rows, k, n = 32, 24, 192            # tiles of 128 over 192 columns
    lhs = jax.random.normal(ks[0], (rows, k))
    w = jax.random.normal(ks[1], (2, k, n))
    dout = jax.random.normal(ks[2], (rows, n))
    tile_expert = jnp.array([0, 0, 1, 1], jnp.int32)
    n_active = jnp.array([4], jnp.int32)
    with jax.default_matmul_precision("highest"):
        out = _gmm_call(lhs, w, tile_expert, n_active, tile_rows=8)
        dw = _tgmm_call(lhs, dout, tile_expert, n_active, tile_rows=8,
                        count=2)
        want = jnp.concatenate([lhs[:16] @ w[0], lhs[16:] @ w[1]])
        want_dw = jnp.stack([lhs[:16].T @ dout[:16], lhs[16:].T @ dout[16:]])
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(dw, want_dw, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# The seeded weights
# ---------------------------------------------------------------------------
def test_the_seeded_mixer_has_heads_that_remember_past_a_chunk(bench):
    """The published configuration's leaves as the benchmark draws them: of
    the 256 heads of its four Mamba layers at least a tenth keep ``a^128 >
    0.1`` (a state handed over a chunk's boundary reaches the loss), and the
    decays spread over (0, 1)."""
    harness, ref = bench["harness"], bench["ref"]
    with open(os.path.join(CELLS, "configs",
                           "nemotron-3-nano-30b-a3b.json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    specs = ref.leaf_specs(cfg)
    key = harness.seed_key(2200000001)

    def leaves(name):
        return [harness.make_leaf(ref, cfg, key, i)
                for i, (path, _, _) in enumerate(specs) if path[-1] == name]

    a = -jnp.exp(jnp.stack(leaves("A_log")))                 # [4, 64]
    bias = jnp.stack(leaves("dt_bias"))
    # a normed token through wdt (std 1/sqrt(d)) is a unit normal
    raw = jax.random.normal(jax.random.key(0), (512, *bias.shape))
    dt = jax.nn.softplus(raw + bias)
    kept = jnp.exp(128 * a * jnp.mean(dt, axis=0))
    assert a.shape == (4, 64)
    assert float(jnp.mean(kept > 0.1)) >= 0.1
    decay = jnp.exp(dt * a)
    assert 0.3 < float(jnp.mean(decay)) < 0.7
    assert float(jnp.mean(decay < 0.1)) > 0.1 \
        and float(jnp.mean(decay > 0.9)) > 0.1


def test_a_mixer_initialises_its_decays_and_steps_as_published():
    """Without a cell's seeded leaves a mixer draws the published ranges: A
    uniform in [1, 16], a head's step softplus(dt_bias) log-uniform in
    [0.001, 0.1], D and the gated norm's scale ones, no conv bias."""
    assert (A_RANGE, DT_RANGE, DT_FLOOR) == ((1.0, 16.0), (1e-3, 0.1), 1e-4)
    mixer = SSMixer(SSMSpec(n_heads=512, head_dim=8, n_groups=2, state=16),
                    dtype=jnp.float32)
    p = nn.meta.unbox(mixer.init(jax.random.key(5),
                                 jnp.zeros((1, 128, 32))))["params"]
    a, dt = np.exp(p["A_log"]), np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert 1.0 <= a.min() < 1.5 and 15.0 < a.max() <= 16.0
    assert abs(a.mean() - 8.5) < 0.6                   # uniform, not in log
    assert 1e-3 <= dt.min() < 1.5e-3 and 0.07 < dt.max() <= 0.1 * (1 + 1e-5)
    assert abs(np.log(dt).mean() - np.log(1e-2)) < 0.15     # uniform in log
    assert np.all(p["D"] == 1) and np.all(p["norm"] == 1) \
        and np.all(p["conv_bias"] == 0)
