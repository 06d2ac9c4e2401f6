"""The program's ``Transformer`` under Granite 4.0-H's per-layer description
(every layer a mixer, Mamba-2 with one group of B and C or attention without
position embedding, then the dense MLP; the four multipliers; the head tied
to the table) against the benchmark's plain float32 reference of that
architecture, loaded by path: tree, loss and every gradient on seeded random
weights at tiny widths; flash at heads of 64 and the softmax scale 1/64
against the reference attention; and multipliers left unset adding no
operation, on a ``nemotron_h``-shaped tiny model."""

import dataclasses
import json
import os
import re
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models.ssm import SSMSpec
from tony_tpu.models.transformer import (Transformer, TransformerConfig,
                                         chunked_causal_lm_loss,
                                         layer_counters)
from tony_tpu.ops.attention import flash_attention, reference_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = os.path.join(REPO, "benchmarks", "cells")
FIXTURES = os.path.join(CELLS, "fixtures")
TINY = os.path.join(FIXTURES, "rehearsal_granite", "configs", "tiny_g4h.json")
TINY_NEM = os.path.join(FIXTURES, "rehearsal_nemotron_h", "configs",
                        "tiny_nem.json")
TRAFFIC = {"global_batch": 2, "seq": 256, "mesh": "dp=1", "loss_chunk": 128}


def _load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench():
    """The harness's ``reference`` and both architectures' files through
    ``arch.load``."""
    sys.path.insert(0, CELLS)
    try:
        import arch
        import reference

        def files(kind):
            folder = os.path.join(CELLS, "architectures", kind)
            return {part: arch.load(folder, part) for part in arch.PARTS}

        yield {"harness": reference, "granite": files("granitemoehybrid"),
               "nemotron": files("nemotron_h")}
    finally:
        sys.path.remove(CELLS)


@pytest.fixture(scope="module")
def cfg():
    return _load(TINY)


def _program(program, cfg, **replace):
    mcfg = dataclasses.replace(program.model_config(cfg, TRAFFIC, ""),
                               dtype=jnp.float32, **replace)
    model = Transformer(mcfg)
    return model, mcfg


def _tree(model, tokens):
    shapes = nn.meta.unbox(jax.eval_shape(model.init, jax.random.key(0),
                                          tokens))["params"]
    return [(tuple(str(k.key) for k in path), leaf.shape) for path, leaf in
            sorted(jax.tree_util.tree_leaves_with_path(shapes),
                   key=lambda t: tuple(str(k.key) for k in t[0]))]


def test_every_layer_is_a_mixer_then_the_mlp(bench, cfg):
    _, mcfg = _program(bench["granite"]["program"], cfg)
    kinds = [("M" if isinstance(l.mixer, SSMSpec) else "A", l.feed_forward,
              l.experts, l.rope) for l in mcfg.layers]
    assert kinds == [(k, True, None, k == "M") for k in "MMMMMAMMMM"]
    mixer = mcfg.layers[0].mixer
    assert (mixer.n_heads, mixer.head_dim, mixer.n_groups, mixer.state,
            mixer.conv, mixer.chunk) == (16, 8, 1, 16, 4, 32)
    assert (mcfg.embedding_multiplier, mcfg.residual_multiplier,
            mcfg.attention_multiplier, mcfg.logits_scaling,
            mcfg.tie_embeddings, mcfg.head_size) == (12, 0.22, 0.015625, 8,
                                                     True, 16)


def test_parameter_tree_is_the_references_leaf_for_leaf(bench, cfg):
    model, _ = _program(bench["granite"]["program"], cfg)
    got = _tree(model, jnp.zeros((2, 256), jnp.int32))
    want = [(path, shape) for path, shape, _ in
            bench["granite"]["reference"].leaf_specs(cfg)]
    assert got == want
    assert ("lm_head", "kernel") not in [path for path, _ in got]   # tied
    assert sum(int(np.prod(s)) for _, s in got) == \
        bench["granite"]["counts"].total_params(cfg)


def test_loss_and_gradients_match_the_reference(bench, cfg):
    """Every multiplier, the one-group scan, the tied head over the logits'
    divisor: the program's loss, counters and gradients against the plain
    float32 reference on the benchmark's seeded weights."""
    harness, ref = bench["harness"], bench["granite"]["reference"]
    model, mcfg = _program(bench["granite"]["program"], cfg)

    def loss(params, tokens):
        h, sown = model.apply({"params": params}, tokens, return_hidden=True,
                              mutable=["intermediates"])
        return chunked_causal_lm_loss(
            h, params["embedding"].T, tokens, chunk_size=128,
            logits_scaling=mcfg.logits_scaling), \
            layer_counters(sown["intermediates"])

    params = harness.make_params(ref, cfg, harness.seed_key(7))
    tokens = jnp.asarray(harness.token_rows(7, 0, 2, 256, cfg["vocab_size"]))
    with jax.default_matmul_precision("highest"):
        (got, aux), got_g = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(params, tokens)
        want, want_g = jax.jit(jax.value_and_grad(
            lambda p, t: ref.loss_fn(cfg, p, t)))(params, tokens)
        # the full-logits path divides by the same number
        full = model.apply({"params": params}, tokens)
        hidden = model.apply({"params": params}, tokens, return_hidden=True)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    for (path, _, _), g, w in zip(ref.leaf_specs(cfg), harness.flat(got_g),
                                  harness.flat(want_g)):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=2e-4,
                                   err_msg="/".join(path))
    np.testing.assert_allclose(
        full, jnp.einsum("bsd,vd->bsv", hidden, params["embedding"]) / 8,
        rtol=1e-5, atol=1e-5)
    assert set(aux) == {"ssm_dt_mean", "ssm_decay_mean",
                        "ssm_head_rms_max_over_median"}
    assert float(aux["ssm_head_rms_max_over_median"]) >= 1.0


def test_each_multiplier_moves_the_gradient(bench, cfg):
    """None of the four is dead code: the identity in its place moves some
    leaf's gradient by more than a percent of its norm (the one attention
    layer's scale moves the loss itself by a few parts in a million)."""
    harness, ref = bench["harness"], bench["granite"]["reference"]
    params = harness.make_params(ref, cfg, harness.seed_key(3))
    tokens = jnp.asarray(harness.token_rows(3, 0, 2, 256, cfg["vocab_size"]))

    def grads(**replace):
        model, mcfg = _program(bench["granite"]["program"], cfg, **replace)

        def loss(p):
            h = model.apply({"params": p}, tokens, return_hidden=True)
            return chunked_causal_lm_loss(
                h, p["embedding"].T, tokens, chunk_size=128,
                logits_scaling=mcfg.logits_scaling)
        return harness.flat(jax.jit(jax.grad(loss))(params))

    with jax.default_matmul_precision("highest"):
        base = grads()
        for replace in (dict(embedding_multiplier=1.0),
                        dict(residual_multiplier=1.0),
                        dict(attention_multiplier=None),
                        dict(logits_scaling=1.0)):
            moved = max(float(jnp.linalg.norm(g - b) / jnp.linalg.norm(b))
                        for g, b in zip(grads(**replace), base))
            assert moved > 0.01, (replace, moved)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_at_heads_of_64_and_a_scale_of_1_64(causal):
    """The kernels at head width 64 (the row sums fused into the products,
    ``d < 128``), 4 q heads over 2 kv heads, the softmax scale the
    configuration states: output and the three gradients against the
    reference attention at the same scale (interpret mode)."""
    ks = jax.random.split(jax.random.key(5), 4)
    b, s, h, hk, d = 1, 256, 4, 2, 64
    q = jax.random.normal(ks[0], (b, s, h, d)) * 4
    k = jax.random.normal(ks[1], (b, s, hk, d)) * 4
    v = jax.random.normal(ks[2], (b, s, hk, d))
    w = jax.random.normal(ks[3], (b, s, h, d))

    def ref(q, k, v):
        return reference_attention(q, jnp.repeat(k, 2, axis=2),
                                   jnp.repeat(v, 2, axis=2), causal=causal,
                                   scale=1 / 64)

    def kernel(q, k, v):
        return flash_attention(q, k, v, causal=causal, scale=1 / 64,
                               block_q=128, block_k=128)

    with jax.default_matmul_precision("highest"):
        got = kernel(q, k, v)
        want = ref(q, k, v)
        grads = [jax.grad(lambda *a, f=f: jnp.sum(f(*a) * w),
                          argnums=(0, 1, 2))(q, k, v) for f in (kernel, ref)]
        # the scale is the one stated, not head_dim^-1/2
        default = reference_attention(q, jnp.repeat(k, 2, axis=2),
                                      jnp.repeat(v, 2, axis=2), causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    assert float(jnp.max(jnp.abs(default - want))) > 0.1
    for g, r in zip(*grads):
        scale = float(jnp.max(jnp.abs(r)))
        np.testing.assert_allclose(g / scale, r / scale, atol=2e-5)


def test_unset_multipliers_add_no_operation(bench):
    """On a ``nemotron_h``-shaped tiny model: the multipliers at their
    identities, stated or left out, trace the same program and give a
    bit-identical loss and gradient; set, they trace more."""
    harness, ref = bench["harness"], bench["nemotron"]["reference"]
    cfg = _load(TINY_NEM)
    program = bench["nemotron"]["program"]
    params = harness.make_params(ref, cfg, harness.seed_key(11))
    tokens = jnp.asarray(harness.token_rows(11, 0, 2, 256,
                                            cfg["vocab_size"]))

    def traced(**replace):
        model, _ = _program(program, cfg, **replace)

        def loss(p):
            h = model.apply({"params": p}, tokens, return_hidden=True)
            return chunked_causal_lm_loss(h, p["lm_head"]["kernel"], tokens,
                                          chunk_size=128)
        # the program's text, without the addresses of the functions in it
        return (re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(loss)(
                    params))),
                jax.jit(jax.value_and_grad(loss))(params))

    left_out = traced()
    stated = traced(embedding_multiplier=1.0, residual_multiplier=1.0,
                    attention_multiplier=None, logits_scaling=1.0)
    assert TransformerConfig().embedding_multiplier == 1.0
    assert stated[0] == left_out[0]
    (loss_a, grad_a), (loss_b, grad_b) = left_out[1], stated[1]
    assert float(loss_a) == float(loss_b)
    for a, b in zip(jax.tree.leaves(grad_a), jax.tree.leaves(grad_b)):
        np.testing.assert_array_equal(a, b)
    scaled = traced(embedding_multiplier=2.0, residual_multiplier=0.5)
    assert len(scaled[0]) > len(left_out[0])
