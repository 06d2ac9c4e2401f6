"""The program's ``Transformer`` under SmallThinker's per-layer description
(window and position-free full attention mixed by layer, a stated head width,
every layer the expert layer with its router read before attention, one
chip's share of the experts) against the benchmark's plain float32 reference
of that architecture, loaded by path: loss and every gradient, seeded random
weights, tiny widths, float32 on both sides."""

import json
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = os.path.join(REPO, "benchmarks", "cells")
ARCH = os.path.join(CELLS, "architectures", "smallthinker")
TINY = os.path.join(CELLS, "fixtures", "rehearsal_smallthinker", "configs",
                    "tiny_st.json")


@pytest.fixture(scope="module")
def bench():
    """The harness's modules by path (``arch``, ``reference``), and the
    architecture's three files through ``arch.load``."""
    sys.path.insert(0, CELLS)
    try:
        import arch
        import reference
        yield {"arch": arch, "reference": reference,
               "ref": arch.load(ARCH, "reference"),
               "program": arch.load(ARCH, "program"),
               "counts": arch.load(ARCH, "counts")}
    finally:
        sys.path.remove(CELLS)


@pytest.fixture(scope="module")
def cfg():
    with open(TINY, encoding="utf-8") as f:
        return json.load(f)


TRAFFIC = {"global_batch": 2, "seq": 256, "mesh": "dp=1", "loss_chunk": 128}


def _program_loss(bench, cfg, dtype=jnp.float32):
    import dataclasses

    from tony_tpu.models import Transformer
    from tony_tpu.models.transformer import chunked_causal_lm_loss

    mcfg = dataclasses.replace(
        bench["program"].model_config(cfg, TRAFFIC, ""), dtype=dtype,
        attn_block_q=128, attn_block_k=64)
    model = Transformer(mcfg)

    def loss(params, tokens):
        h = model.apply({"params": params}, tokens, return_hidden=True)
        return chunked_causal_lm_loss(h, params["lm_head"]["kernel"], tokens,
                                      chunk_size=128)
    return model, mcfg, loss


def test_parameter_tree_is_the_references_leaf_for_leaf(bench, cfg):
    model, _, _ = _program_loss(bench, cfg)
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
    reference, ref = bench["reference"], bench["ref"]
    _, mcfg, loss = _program_loss(bench, cfg)
    assert [(l.window, l.rope) for l in mcfg.layers] == [
        (None, False), (96, True), (96, True), (96, True)]
    assert mcfg.head_size == 32 != mcfg.dim // mcfg.n_heads
    params = reference.make_params(ref, cfg, reference.seed_key(7))
    tokens = jnp.asarray(reference.token_rows(7, 0, 2, 256,
                                              cfg["vocab_size"]))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(jax.value_and_grad(loss))(params, tokens)
        want = jax.jit(jax.value_and_grad(
            lambda p, t: ref.loss_fn(cfg, p, t)))(params, tokens)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-6)
    flat = reference.flat
    for (path, _, _), g, w in zip(ref.leaf_specs(cfg), flat(got[1]),
                                  flat(want[1])):
        scale = float(jnp.max(jnp.abs(w))) or 1.0
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, atol=2e-4,
                                   err_msg="/".join(path))


def test_four_shares_of_the_experts_sum_to_the_uncut_reference_layer(
        bench, cfg):
    """The shares add up: four ExpertLayers of 16 experts each, every one
    routing over all 64, sum to the reference's layer with all 64 held."""
    from tony_tpu.models.moe import ExpertLayer, ExpertSpec

    ref = bench["ref"]
    d, f, k = 48, 32, 6
    ks = jax.random.split(jax.random.key(3), 6)
    whole = {"router": jax.random.normal(ks[0], (d, 64)) * 3 * d ** -0.5,
             "gate": jax.random.normal(ks[1], (64, d, f)) * d ** -0.5,
             "up": jax.random.normal(ks[2], (64, d, f)) * d ** -0.5,
             "down": jax.random.normal(ks[3], (64, f, d)) * f ** -0.5}
    n = jax.random.normal(ks[4], (1, 128, d))
    m = jax.random.normal(ks[5], (1, 128, d))
    uncut = {"moe_num_active_primary_experts": k}
    with jax.default_matmul_precision("highest"):
        want = ref.experts_held(uncut, whole, n[0] @ whole["router"], m[0])
        got = 0
        for first in range(0, 64, 16):
            spec = ExpertSpec(n_experts=64, top_k=k, width=f,
                              activation="relu", held=(first, 16),
                              route_before_attention=True, tile_rows=8,
                              chunk_tokens=64)
            share = {name: (w if name == "router" else w[first:first + 16])
                     for name, w in whole.items()}
            got = got + ExpertLayer(spec, jnp.float32).apply(
                {"params": share}, n, m)[0]
            # and the reference's own share is the program's
            mine = dict(uncut, share={"first_expert_held": first})
            np.testing.assert_allclose(
                ExpertLayer(spec, jnp.float32).apply(
                    {"params": share}, n, m)[0],
                ref.experts_held(mine, share, n[0] @ whole["router"], m[0]),
                atol=2e-5)
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_a_dense_configuration_is_unchanged():
    """No per-layer description: the parameter tree, the kernels' names and
    the loss of the dense decoder are what they were (a LayerSpec of defaults
    is the layer the loop built before it read one)."""
    from jaxpr_kernels import pallas_calls
    from tony_tpu.models import Transformer, TransformerConfig, causal_lm_loss
    from tony_tpu.models.transformer import LayerSpec

    base = TransformerConfig.tiny(attn_impl="flash")
    spelled = TransformerConfig.tiny(attn_impl="flash", head_dim=16,
                                     layers=(LayerSpec(),) * 2)
    tokens = jax.random.randint(jax.random.key(0), (2, 64), 0, 256)
    outs = []
    for c in (base, spelled):
        model = Transformer(c)
        params = nn.meta.unbox(model.init(jax.random.key(1), tokens))[
            "params"]
        assert sorted(params["layer_0"]) == ["attn", "attn_norm", "mlp",
                                             "mlp_norm"]
        loss = lambda p, m=model: causal_lm_loss(       # noqa: E731
            m.apply({"params": p}, tokens), tokens)
        outs.append((params, float(loss(params)), str(jax.make_jaxpr(
            jax.grad(loss))(params)), pallas_calls(jax.make_jaxpr(
                jax.grad(loss))(params))))
    assert jax.tree.all(jax.tree.map(lambda a, b: bool((a == b).all()),
                                     outs[0][0], outs[1][0]))
    assert outs[0][1] == outs[1][1]
    assert outs[0][2] == outs[1][2]         # the same program, op for op
    assert set(outs[0][3]) == {"flash_fwd", "flash_dq", "flash_dkv"}
    with pytest.raises(ValueError, match="layer specs"):
        TransformerConfig.tiny(layers=(LayerSpec(),))
