"""The program's ``Transformer`` under Laguna's per-layer description (a
leading dense layer, sparse layers with a shared expert beside the routed ones
and a factor on the routed weights, two head counts, plain RoPE and YaRN over
half a head, a per-head output gate, a window narrower than the flash tile)
against the benchmark's plain float32 reference of that architecture, loaded
by path: tree, loss and every gradient on seeded random weights at tiny
widths; the shares of the experts and of the heads adding up to the uncut
layer with the shared expert counted once; the shared expert under an ``ep``
mesh; the YaRN table by hand; and the two configurations the benchmark had
building the programs they built."""

import dataclasses
import json
import math
import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models.moe import ExpertLayer, ExpertSpec, SharedExpert
from tony_tpu.models.transformer import (Attention, LayerSpec, RopeSpec,
                                         Transformer, TransformerConfig, Yarn,
                                         chunked_causal_lm_loss,
                                         layer_counters)
from tony_tpu.parallel import MeshSpec, build_mesh
from tony_tpu.parallel.sharding import DEFAULT_RULES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = os.path.join(REPO, "benchmarks", "cells")
TINY = os.path.join(CELLS, "fixtures", "rehearsal_laguna", "configs",
                    "tiny_lag.json")
TRAFFIC = {"global_batch": 2, "seq": 256, "mesh": "dp=1", "loss_chunk": 128}


@pytest.fixture(scope="module")
def bench():
    """The harness's modules by path (``arch``, ``reference``), and an
    architecture's three files through ``arch.load``."""
    sys.path.insert(0, CELLS)
    try:
        import arch
        import reference

        def files(kind):
            folder = os.path.join(CELLS, "architectures", kind)
            return {part: arch.load(folder, part) for part in arch.PARTS}
        mine = files("laguna")
        yield {"harness": reference, "files": files, "ref": mine["reference"],
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


def test_every_mechanism_is_in_the_tiny_configuration(bench, cfg):
    _, mcfg, _ = _program(bench, cfg)
    assert [(l.window, l.n_heads, l.gate, l.experts is None)
            for l in mcfg.layers] == [(None, 4, True, True),
                                      (64, 6, True, False),
                                      (None, 4, True, False)]
    full, window = mcfg.layers[0].rope, mcfg.layers[1].rope
    assert (full.rotated, full.yarn.factor, window.yarn) == (0.5, 128, None)
    experts = mcfg.layers[1].experts
    assert (experts.shared_width, experts.routed_scale, experts.held) == (
        48, 2.5, (4, 4))
    # a window of 64 under a tile of 128: the sequence's 256 are cut to it
    from tony_tpu.ops.attention import _window_blocks
    assert _window_blocks(64, 1024, 1024) == (128, 128)


def test_parameter_tree_is_the_references_leaf_for_leaf(bench, cfg):
    model, _, _ = _program(bench, cfg)
    tokens = jnp.zeros((2, 256), jnp.int32)
    shapes = nn.meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                          tokens))["params"]
    got = [(tuple(str(k.key) for k in path), leaf.shape) for path, leaf in
           sorted(jax.tree_util.tree_leaves_with_path(shapes),
                  key=lambda t: tuple(str(k.key) for k in t[0]))]
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
    # the step's counters: the expert layers' and the gate's mean
    assert set(aux) == {"attn_gate_mean", "moe_rows_routed",
                        "moe_rows_unrouted_share",
                        "moe_expert_load_max_over_mean",
                        "moe_buffer_rows_live_share",
                        "moe_token_rows_gathered_share"}
    assert 0.3 < float(aux["attn_gate_mean"]) < 0.7


# ---------------------------------------------------------------------------
# The shares add up
# ---------------------------------------------------------------------------
def _uncut(cfg, kind):
    """One sparse layer of ``kind`` with nothing cut: every head, every
    expert held from 0."""
    return dict(cfg, num_hidden_layers=1, layer_types=[kind],
                mlp_layer_types=["sparse"], gating_types=["per_head"],
                num_attention_heads_per_layer=[8], num_key_value_heads=4,
                num_experts=16, share={"first_expert_held": 0})


@pytest.mark.parametrize("kind", ["full_attention", "sliding_attention"])
def test_the_shares_add_up_to_the_uncut_reference_layer(bench, cfg, kind):
    """Both halves of the heads' ``W_o`` parts, all four shares of the
    experts and the shared expert ONCE are the reference's layer with 8 q / 4
    kv heads and all 16 experts held."""
    harness, ref = bench["harness"], bench["ref"]
    whole_cfg = _uncut(cfg, kind)
    whole = harness.make_params(ref, whole_cfg, harness.seed_key(11))[
        "layer_0"]
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    x = jax.random.normal(jax.random.key(4), (1, 256, d))
    tcfg = TransformerConfig(
        vocab_size=8, dim=d, n_layers=1, n_heads=4, n_kv_heads=2,
        head_dim=hd, max_seq_len=4096, norm_eps=cfg["rms_norm_eps"],
        dtype=jnp.float32, attn_impl="flash")
    rope = bench["program"].rope_spec(cfg["rope_parameters"][kind])
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    spec = LayerSpec(window=window, rope=rope, n_heads=4, gate=True)
    positions = jnp.arange(256, dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        n = harness.rmsnorm(x, whole["attn_norm"]["scale"],
                            cfg["rms_norm_eps"])
        h = x
        for half in (0, 1):     # heads 0-3 with kv 0-1, heads 4-7 with 2-3
            q_cols = slice(half * 4 * hd, (half + 1) * 4 * hd)
            kv_cols = slice(half * 2 * hd, (half + 1) * 2 * hd)
            a = whole["attn"]
            part = {"wq": {"kernel": a["wq"]["kernel"][:, q_cols]},
                    "wk": {"kernel": a["wk"]["kernel"][:, kv_cols]},
                    "wv": {"kernel": a["wv"]["kernel"][:, kv_cols]},
                    "wg": {"kernel": a["wg"]["kernel"][:, half * 4:
                                                       (half + 1) * 4]},
                    "wo": {"kernel": a["wo"]["kernel"][q_cols]}}
            h = h + Attention(tcfg, spec).apply({"params": part}, n,
                                                positions)
        m = harness.rmsnorm(h, whole["mlp_norm"]["scale"],
                            cfg["rms_norm_eps"])
        moe = whole["moe"]
        base = ExpertSpec(n_experts=16, top_k=4, width=64, tile_rows=16,
                          chunk_tokens=128, routed_scale=2.5)
        with_shared = dataclasses.replace(base, shared_width=48)
        shared = SharedExpert(with_shared, jnp.float32, jnp.float32,
                              "").apply({"params": moe["shared"]}, m)
        out = h + shared
        for first in range(0, 16, 4):
            share = {k: (moe[k] if k == "router" else moe[k][first:first + 4])
                     for k in ("router", "gate", "up", "down")}
            routed = ExpertLayer(
                dataclasses.replace(base, held=(first, 4)),
                jnp.float32).apply({"params": share}, m, m)
            out = out + routed
            # a share's layer is its routed part and the shared expert
            both = ExpertLayer(
                dataclasses.replace(with_shared, held=(first, 4)),
                jnp.float32).apply(
                    {"params": dict(share, shared=moe["shared"])}, m, m)
            np.testing.assert_allclose(both - routed, shared, atol=3e-5)
        want = ref._layer(whole_cfg, whole, x[0], 0)
    np.testing.assert_allclose(out[0], want, atol=2e-4, rtol=2e-5)


def test_the_shared_expert_is_added_once_under_an_ep_mesh():
    """dp=2 × ep=4 against one device: every ``ep`` shard computes the
    shared expert alike, and the sum of the shards' parts holds it once."""
    spec = ExpertSpec(n_experts=8, top_k=3, width=32, tile_rows=8,
                      chunk_tokens=16, shared_width=40, routed_scale=2.5)
    layer = ExpertLayer(spec, jnp.float32)
    ks = jax.random.split(jax.random.key(2), 2)
    x = jax.random.normal(ks[0], (8, 16, 24))
    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        params = nn.meta.unbox(layer.init(ks[1], x, x))["params"]

    def f(p, x):
        with nn.logical_axis_rules(list(DEFAULT_RULES)):
            return layer.apply({"params": p}, x, x)

    with jax.default_matmul_precision("highest"):
        alone = f(params, x)
        no_shared = ExpertLayer(dataclasses.replace(spec, shared_width=None),
                                jnp.float32).apply(
            {"params": {k: v for k, v in params.items() if k != "shared"}},
            x, x)
        mesh = build_mesh(MeshSpec(dp=2, ep=4))
        with jax.set_mesh(mesh):
            compiled = jax.jit(f).lower(params, x).compile()
            shared_out = compiled(params, x)
    assert "reduce-scatter" in compiled.as_text() \
        or "all-reduce" in compiled.as_text()
    np.testing.assert_allclose(shared_out, alone, atol=2e-5, rtol=2e-5)
    # the shared expert is a real part of the result, counted once: were it
    # inside the shards' parts, four would have come back
    part = np.asarray(alone - no_shared)
    assert np.linalg.norm(part) > 0.1 * np.linalg.norm(np.asarray(alone))
    np.testing.assert_allclose(np.asarray(shared_out) - np.asarray(no_shared),
                               part, atol=4e-5)


# ---------------------------------------------------------------------------
# The YaRN table, by hand
# ---------------------------------------------------------------------------
def test_the_yarn_table_against_numbers_worked_by_hand(bench):
    """The published full-attention RoPE: 64 of 128 columns rotated, θ
    500,000, factor 128 from 8,192. The correction dims are
    64·ln(8192 / (32·2π)) / (2·ln 500000) = 9.04 → 9 and
    64·ln(8192 / 2π) / (2·ln 500000) = 17.49 → 18, so pair 0 keeps its
    frequency 1 and pair 12, a third of the way up the ramp, turns at
    θ^(−24/64) · (2/3 + 1/3 / 128); pair 20 is past the ramp: θ^(−40/64) /
    128."""
    rope = RopeSpec(theta=500000.0, rotated=0.5, yarn=Yarn(
        factor=128, original_max_position=8192, beta_fast=32, beta_slow=1,
        attention_factor=1.4852030263919618))
    freqs, factor = rope.table(128)
    assert freqs.shape == (32,) and factor == 1.4852030263919618
    assert float(freqs[0]) == 1.0
    assert float(freqs[12]) == pytest.approx(
        500000 ** (-24 / 64) * (2 / 3 + 1 / 3 / 128), rel=1e-6)
    assert float(freqs[12]) == pytest.approx(0.004885, rel=1e-3)
    assert float(freqs[20]) == pytest.approx(500000 ** (-40 / 64) / 128,
                                             rel=1e-6)
    # the stated factor is YaRN's own 0.1 ln(factor) + 1
    assert factor == pytest.approx(0.1 * math.log(128) + 1, rel=1e-12)
    # the reference works its own table out, from the config's own keys
    ref = bench["ref"]
    with open(os.path.join(CELLS, "configs", "laguna-s-2.1.json"),
              encoding="utf-8") as f:
        published = json.load(f)["rope_parameters"]
    theirs, scale = ref.rope_table(published["full_attention"], 128)
    np.testing.assert_allclose(theirs, np.asarray(freqs), rtol=1e-6)
    assert scale == factor
    plain, one = ref.rope_table(published["sliding_attention"], 128)
    assert plain.shape == (64,) and one == 1.0
    np.testing.assert_allclose(
        plain, np.asarray(RopeSpec(theta=10000.0).table(128)[0]), rtol=1e-6)


# ---------------------------------------------------------------------------
# What the benchmark's other configurations build is what they built
# ---------------------------------------------------------------------------
def _traced(mcfg):
    """(parameter tree's paths and shapes, the jaxpr of loss and gradients
    with its function addresses taken out)."""
    model = Transformer(mcfg)
    tokens = jnp.zeros((2, 128), jnp.int32)
    shapes = nn.meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                          tokens))["params"]

    def loss(p, t):
        h = model.apply({"params": p}, t, return_hidden=True)
        return chunked_causal_lm_loss(h, p["lm_head"]["kernel"], t,
                                      chunk_size=64)

    tree = sorted((jax.tree_util.keystr(p), leaf.shape) for p, leaf in
                  jax.tree_util.tree_leaves_with_path(shapes))
    text = str(jax.make_jaxpr(jax.value_and_grad(loss))(shapes, tokens))
    return tree, re.sub(r"0x[0-9a-f]+", "", text)


@pytest.mark.parametrize("kind, fixture", [
    ("mistral", "rehearsal/configs/tiny.json"),
    ("smallthinker", "rehearsal_smallthinker/configs/tiny_st.json")])
def test_a_configuration_the_benchmark_had_builds_the_same_program(
        bench, kind, fixture):
    """Every field this architecture added, spelled out at the value the
    older configurations never stated (the config's head counts, plain RoPE
    at the config's θ, no gate, no shared expert, a factor of 1), gives the
    tree and the jaxpr, equation for equation, of the configuration as its
    own ``program.py`` builds it."""
    with open(os.path.join(CELLS, "fixtures", fixture),
              encoding="utf-8") as f:
        old = json.load(f)
    mcfg = bench["files"](kind)["program"].model_config(
        old, dict(TRAFFIC, seq=128), "")
    assert all(l.n_heads is None and l.gate is False
               and isinstance(l.rope, bool)
               and (l.experts is None or l.experts.shared_width is None)
               for l in map(mcfg.layer, range(mcfg.n_layers)))

    def spelled(layer):
        experts = layer.experts and dataclasses.replace(
            layer.experts, shared_width=None, routed_scale=1.0)
        return dataclasses.replace(
            layer, n_heads=mcfg.n_heads,
            rope=RopeSpec(theta=mcfg.rope_theta) if layer.rope else False,
            gate=False, experts=experts)

    out = dataclasses.replace(mcfg, layers=tuple(
        spelled(mcfg.layer(i)) for i in range(mcfg.n_layers)))
    assert _traced(out) == _traced(mcfg)
