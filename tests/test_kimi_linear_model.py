"""The program's ``Transformer`` under Kimi Linear's per-layer description
(KDA mixers and latent attention without RoPE by ``linear_attn_config``, a
dense first layer, then experts under sigmoid scores beside a shared one)
against the benchmark's plain float32 reference of that architecture, loaded
by path: tree, loss and every gradient on seeded random weights at tiny
widths; flash at q/k width 192 and v width 128 against the reference
attention; and the expert shares adding up to the uncut layer."""

import dataclasses
import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models.kda import KDASpec
from tony_tpu.models.transformer import (LayerSpec, Transformer,
                                         TransformerConfig,
                                         chunked_causal_lm_loss,
                                         layer_counters)
from tony_tpu.ops.attention import flash_attention, reference_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = os.path.join(REPO, "benchmarks", "cells")
TINY = os.path.join(CELLS, "fixtures", "rehearsal_kimi_linear", "configs",
                    "tiny_kimi.json")
PUBLISHED = os.path.join(CELLS, "configs", "kimi-linear-48b-a3b.json")
TRAFFIC = {"global_batch": 2, "seq": 256, "mesh": "dp=1", "loss_chunk": 128}


def _load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    """The harness's ``reference`` and the architecture's files through
    ``arch.load``."""
    sys.path.insert(0, CELLS)
    try:
        import arch
        import reference

        folder = os.path.join(CELLS, "architectures", "kimi_linear")
        yield dict({part: arch.load(folder, part) for part in arch.PARTS},
                   harness=reference)
    finally:
        sys.path.remove(CELLS)


@pytest.fixture(scope="module")
def cfg():
    return _load(TINY)


def _program(program, cfg, **replace):
    mcfg = dataclasses.replace(program.model_config(cfg, TRAFFIC, ""),
                               **dict(dict(dtype=jnp.float32), **replace))
    return Transformer(mcfg), mcfg


def _tree(model, tokens):
    shapes = nn.meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                          tokens))["params"]
    return [(tuple(str(k.key) for k in path), leaf.shape) for path, leaf in
            sorted(jax.tree_util.tree_leaves_with_path(shapes),
                   key=lambda t: tuple(str(k.key) for k in t[0]))]


def test_layers_follow_linear_attn_config(bench, cfg):
    for path in (TINY, PUBLISHED):
        c = _load(path)
        _, mcfg = _program(bench["program"], c)
        kinds = ["K" if isinstance(l.mixer, KDASpec) else "M"
                 for l in mcfg.layers]
        assert kinds == list("KKKMK")
        assert [l.experts is None for l in mcfg.layers] == [
            True, False, False, False, False]
    mla = mcfg.layers[3].mixer
    assert (mla.n_heads, mla.qk_nope, mla.qk_rope, mla.v_dim,
            mla.kv_rank) == (32, 128, 64, 128, 512)
    assert mcfg.layers[0].mixer == KDASpec(n_heads=32, head_dim=128, conv=4,
                                           chunk=64)
    experts = mcfg.layers[1].experts
    assert (experts.n_experts, experts.top_k, experts.held,
            experts.scoring, experts.routed_scale, experts.shared_width,
            experts.width) == (256, 8, (0, 8), "sigmoid", 2.446, 1024, 1024)


def test_parameter_tree_is_the_references_leaf_for_leaf(bench, cfg):
    model, _ = _program(bench["program"], cfg)
    got = _tree(model, jnp.zeros((2, 256), jnp.int32))
    want = [(path, shape) for path, shape, _ in
            bench["reference"].leaf_specs(cfg)]
    assert got == want
    assert sum(int(np.prod(s)) for _, s in got) == \
        bench["counts"].total_params(cfg)


def _worst_leaf_gap(bench, cfg, **replace):
    """The program's loss and largest gradient gap to the reference, each
    leaf against its own largest entry, and the step's counters."""
    harness, ref = bench["harness"], bench["reference"]
    model, _ = _program(bench["program"], cfg, **replace)

    def loss(params, tokens):
        h, sown = model.apply({"params": params}, tokens, return_hidden=True,
                              mutable=["intermediates"])
        return chunked_causal_lm_loss(
            h, params["lm_head"]["kernel"], tokens, chunk_size=128), \
            layer_counters(sown["intermediates"])

    params = harness.make_params(ref, cfg, harness.seed_key(7))
    tokens = jnp.asarray(harness.token_rows(7, 0, 2, 256, cfg["vocab_size"]))
    with jax.default_matmul_precision("highest"):
        (got, aux), got_g = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(params, tokens)
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p, t: ref.loss_fn(cfg, p, t)))(params, tokens)
    gaps = {"/".join(path): float(jnp.max(jnp.abs(g - w))
                                  / (jnp.max(jnp.abs(w)) or 1.0))
            for (path, _, _), g, w in zip(ref.leaf_specs(cfg),
                                          harness.flat(got_g),
                                          harness.flat(want_g))}
    return abs(float(got) / float(want) - 1), gaps, aux


def test_loss_and_gradients_match_the_reference(bench, cfg):
    """The KDA mixers (through the chunked ``jax.numpy`` scan, as a CPU
    step runs it), latent attention through the interpreted flash kernels,
    the dense and the sparse feed-forwards: the program's loss, counters and
    every gradient against the plain float32 reference on the benchmark's
    seeded weights. 2e-4 of each leaf's largest gradient: float32 sums taken
    in other orders (a chunk's algebra against the token-by-token
    recurrence) read a few 1e-6; the program with bf16 activations where
    the configuration states float32 reads over 2e-4
    (``test_bf16_activations_fail_the_tolerance``)."""
    loss_gap, gaps, aux = _worst_leaf_gap(bench, cfg)
    assert loss_gap < 2e-6, loss_gap
    assert max(gaps.values()) < 2e-4, max(gaps.items(), key=lambda t: t[1])
    assert {"kda_decay_mean", "kda_log_decay_min", "kda_beta_mean",
            "moe_rows_routed"} <= set(aux)
    assert 0 < float(aux["kda_decay_mean"]) < 1
    assert float(aux["kda_log_decay_min"]) < 0
    assert 0 < float(aux["kda_beta_mean"]) < 1


def test_bf16_activations_fail_the_tolerance(bench, cfg):
    """The tolerances above are tight enough: the same program with bf16
    activations and products fails them."""
    loss_gap, gaps, _ = _worst_leaf_gap(bench, cfg, dtype=jnp.bfloat16)
    assert loss_gap > 2e-6 and max(gaps.values()) > 2e-4, (loss_gap, gaps)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_at_qk_192_and_v_128(causal):
    """The kernels at q/k width 192 and v width 128 (latent attention's), 4
    heads, the default scale 192^−½: output and the three gradients against
    the reference attention (interpret mode); o and dv are v's width, dq and
    dk q's."""
    ks = jax.random.split(jax.random.key(5), 4)
    b, s, h, d, dv = 1, 256, 4, 192, 128
    q = jax.random.normal(ks[0], (b, s, h, d)) * 2
    k = jax.random.normal(ks[1], (b, s, h, d))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    w = jax.random.normal(ks[3], (b, s, h, dv))

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=128,
                               block_k=128)

    def ref(q, k, v):
        return reference_attention(q, k, v, causal=causal)

    with jax.default_matmul_precision("highest"):
        got, want = kernel(q, k, v), ref(q, k, v)
        grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                          argnums=(0, 1, 2))(q, k, v) for f in (kernel, ref)]
    assert got.shape == (b, s, h, dv)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    for g, r, shape in zip(*grads, (q.shape, k.shape, v.shape)):
        assert g.shape == shape
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g / scale, r / scale, atol=2e-5)


def test_flash_refuses_q_and_k_of_different_widths():
    q = jnp.zeros((1, 128, 2, 192))
    with pytest.raises(ValueError, match="q width"):
        flash_attention(q, jnp.zeros((1, 128, 2, 128)),
                        jnp.zeros((1, 128, 2, 128)))


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sequence_parallel_attention_refuses_a_kda_layer(impl):
    cfg = TransformerConfig.tiny(
        n_layers=2, attn_impl=impl,
        layers=(LayerSpec(), LayerSpec(mixer=KDASpec(n_heads=2, head_dim=32,
                                                     chunk=16),
                                       feed_forward=False)))
    with pytest.raises(ValueError, match="layer_1's KDA mixer.*state-space"):
        Transformer(cfg).init(jax.random.key(0), jnp.zeros((1, 32),
                                                            jnp.int32))


def test_the_shares_add_up_to_the_uncut_reference_layer(bench, cfg):
    """All four shares of the sixteen experts (a deployment's 32 shares of
    256, at the tiny size) and the shared expert ONCE are the reference's
    sparse feed-forward with every expert held."""
    harness, ref = bench["harness"], bench["reference"]
    d = cfg["hidden_size"]
    whole = dict(cfg, num_experts=16, share={"first_expert_held": 0})
    key = harness.seed_key(13)
    params = harness.make_params(ref, whole, key)["layer_1"]["moe"]
    m = jax.random.normal(jax.random.key(2), (256, d))
    with jax.default_matmul_precision("highest"):
        want = ref.sparse(whole, params, m)
        shared = ref.by_position_blocks(
            lambda mb: ref.gated_mlp(params["shared"], mb), m)
        scores = jax.nn.sigmoid(m @ params["router"])
        parts = []
        for first in range(0, 16, 4):
            share = dict(cfg, share={"first_expert_held": first})
            held = {k: params[k][first:first + 4]
                    for k in ("gate", "up", "down")}
            parts.append(ref.experts_held(share, held, scores, m))
    np.testing.assert_allclose(shared + sum(parts), want, atol=1e-5,
                               rtol=1e-5)
    # and the program's layer holds its share as the reference does
    model, _ = _program(bench["program"], cfg)
    leaves = _tree(model, jnp.zeros((2, 256), jnp.int32))
    assert (("layer_1", "moe", "gate"), (4, d, 48)) in leaves
