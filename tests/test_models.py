"""Model zoo on the 8-device virtual mesh: shapes, sharding, learning."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tony_tpu.models import (MnistMLP, ResNet, ResNetConfig, Transformer,
                             TransformerConfig)
from tony_tpu.models.mlp import classification_loss
from tony_tpu.models.transformer import causal_lm_loss
from tony_tpu.parallel import (MeshSpec, build_mesh, init_sharded_state,
                               jit_train_step)


def test_transformer_forward_shapes():
    cfg = TransformerConfig.tiny()
    model = Transformer(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    import flax.linen as nn
    from tony_tpu.parallel.sharding import DEFAULT_RULES
    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        variables = model.init(jax.random.key(0), tokens)
        logits = model.apply(variables, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_llama3_8b_preset_takes_overrides():
    """A depth or vocabulary cut is ONE call (it used to raise TypeError:
    the preset passed n_layers/vocab_size twice); no width moves."""
    cfg = TransformerConfig.llama3_8b(n_layers=2, vocab_size=32064,
                                      max_seq_len=8192)
    assert (cfg.n_layers, cfg.vocab_size) == (2, 32064)
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.mlp_dim,
            cfg.rope_theta) == (4096, 32, 8, 14336, 500000.0)
    full = TransformerConfig.llama3_8b()
    assert (full.n_layers, full.vocab_size) == (32, 128256)


def test_chunked_loss_matches_full_loss():
    """chunked_causal_lm_loss == causal_lm_loss(full logits) — value AND
    gradients — including a chunk size that doesn't divide the shifted
    sequence (pad path) and a padding mask."""
    import flax.linen as nn
    from tony_tpu.models.transformer import chunked_causal_lm_loss
    from tony_tpu.parallel.sharding import DEFAULT_RULES

    # xla attention: this test is about the LOSS math; the Pallas kernel
    # (covered in test_ops) runs in interpret mode on CPU and would
    # dominate the runtime of every one of these 4 compiles.
    cfg = TransformerConfig.tiny(attn_impl="xla")
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.key(0), (2, 23), 0,
                                cfg.vocab_size)
    mask = (jax.random.uniform(jax.random.key(1), (2, 23)) > 0.2)
    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        params = nn.meta.unbox(
            model.init(jax.random.key(2), tokens))["params"]

    def full(p, m):
        with nn.logical_axis_rules(list(DEFAULT_RULES)):
            return causal_lm_loss(model.apply({"params": p}, tokens),
                                  tokens, mask=m)

    def chunked(p, m):
        with nn.logical_axis_rules(list(DEFAULT_RULES)):
            h = model.apply({"params": p}, tokens, return_hidden=True)
        return chunked_causal_lm_loss(h, p["lm_head"]["kernel"], tokens,
                                      chunk_size=8, mask=m)

    for m in (None, mask):
        lf, gf = jax.jit(jax.value_and_grad(full))(params, m)
        lc, gc = jax.jit(jax.value_and_grad(chunked))(params, m)
        np.testing.assert_allclose(lc, lf, atol=1e-5, rtol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, atol=1e-4, rtol=1e-4), gc, gf)


@pytest.mark.parametrize("attn_impl,variants", [
    ("xla", (dict(remat=False), dict(remat=True))),
    # What the remat keeps of the flash forward (its tagged o and lse under
    # None, nothing under "nothing_saveable") are the values a re-run gives.
    ("flash", (dict(remat=False), dict(remat=True, remat_policy=None),
               dict(remat=True, remat_policy="nothing_saveable"))),
])
def test_selective_remat_is_numerically_inert(attn_impl, variants):
    """remat and remat_policy change memory/recompute scheduling only — loss
    and gradients must be bit-comparable to full remat and to no remat (a
    numerics change would be a bug)."""
    import flax.linen as nn
    from tony_tpu.parallel.sharding import DEFAULT_RULES

    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0, 256)
    results = []
    for variant in variants:
        cfg = TransformerConfig.tiny(attn_impl=attn_impl, **variant)
        model = Transformer(cfg)
        with nn.logical_axis_rules(list(DEFAULT_RULES)):
            params = model.init(jax.random.key(1), tokens)["params"]

            def loss_fn(p):
                with nn.logical_axis_rules(list(DEFAULT_RULES)):
                    return causal_lm_loss(
                        model.apply({"params": p}, tokens), tokens)
            l, g = jax.jit(jax.value_and_grad(loss_fn))(params)
        results.append((float(l), g))
    for l, g in results[1:]:
        np.testing.assert_allclose(l, results[0][0], rtol=1e-6)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5,
                                                    atol=1e-6),
            g, results[0][1])


@pytest.mark.parametrize("remat_policy,fwd_per_layer",
                         [(None, 1), ("nothing_saveable", 2)])
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_block_remat_keeps_the_flash_forward_outputs(family, remat_policy,
                                                     fwd_per_layer):
    """With no policy named the block's remat keeps the flash forward's o
    and lse, so the gradient runs ``flash_fwd`` once a layer; a named policy
    is honoured to the letter, and "nothing_saveable" runs it twice. dq and
    dkv run once a layer either way."""
    import flax.linen as nn
    from jaxpr_kernels import pallas_calls
    from tony_tpu.models.moe import MoEConfig
    from tony_tpu.parallel.sharding import DEFAULT_RULES

    kw = dict(attn_impl="flash", remat=True, remat_policy=remat_policy)
    cfg = (TransformerConfig.tiny if family == "dense"
           else MoEConfig.tiny_moe)(**kw)
    model, loss_of = Transformer(cfg), causal_lm_loss
    tokens = jax.random.randint(jax.random.key(0), (2, 32), 0,
                                cfg.vocab_size)
    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        params = nn.meta.unbox(
            jax.eval_shape(model.init, jax.random.key(1), tokens))["params"]
        calls = pallas_calls(jax.make_jaxpr(jax.grad(
            lambda p: loss_of(model.apply({"params": p}, tokens), tokens)))(
                params))
    flash = {k: v for k, v in calls.items() if k.startswith("flash")}
    assert flash == {"flash_fwd": fwd_per_layer * cfg.n_layers,
                     "flash_dq": cfg.n_layers, "flash_dkv": cfg.n_layers}
    # The expert layer's grouped matmuls are named calls too (gate, up and
    # down a layer): forward, the block's recompute, and each chunk's own.
    assert set(calls) - set(flash) == (
        set() if family == "dense" else {"moe_gmm", "moe_tgmm"})
    if family == "moe":
        assert calls["moe_tgmm"] == 3 * cfg.n_layers


def test_transformer_trains_sharded_tp_fsdp():
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    cfg = TransformerConfig.tiny(attn_impl="flash")
    model = Transformer(cfg)
    tokens = jax.random.randint(jax.random.key(0), (8, 32), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens}

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["tokens"])
        return causal_lm_loss(logits, batch["tokens"]), {}

    state, state_sh = init_sharded_state(model, tokens, optax.adam(1e-3),
                                         mesh)
    # lm_head should shard vocab over tp and embed over fsdp.
    from jax.sharding import PartitionSpec as P
    lm = state.params["lm_head"]["kernel"]
    assert lm.sharding.spec == P("fsdp", "tp")
    step = jit_train_step(loss_fn, mesh, state_sh, batch)
    losses = []
    for i in range(10):
        state, m = step(state, batch, jax.random.key(i))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert np.isfinite(losses).all()


def test_transformer_ring_attention_seq_parallel():
    """Long-context path: sequence sharded over sp, ring attention inside
    the model, loss identical to the flash path."""
    mesh_sp = build_mesh(MeshSpec(dp=2, sp=4))
    cfg_ring = TransformerConfig.tiny(attn_impl="ring")
    cfg_flash = TransformerConfig.tiny(attn_impl="xla")
    tokens = jax.random.randint(jax.random.key(0), (2, 64), 0, 256)

    import flax.linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P
    from tony_tpu.parallel.sharding import DEFAULT_RULES

    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        variables = Transformer(cfg_flash).init(jax.random.key(1), tokens)
    variables = nn.meta.unbox(variables)

    ref_logits = jax.jit(Transformer(cfg_flash).apply)(variables, tokens)

    # Ring path: tokens sharded over sp on the seq dim; params replicated;
    # the model's internal ring_attention runs inside shard_map.
    def fwd(params, tokens):
        return Transformer(cfg_ring).apply({"params": params}, tokens)

    ring_fn = jax.shard_map(
        fwd, mesh=mesh_sp,
        in_specs=(P(), P(("dp", "fsdp"), "sp")),
        out_specs=P(("dp", "fsdp"), "sp", None), check_vma=False)
    ring_logits = jax.jit(ring_fn)(variables["params"], tokens)
    np.testing.assert_allclose(ring_logits, ref_logits, atol=2e-4,
                               rtol=2e-4)


def test_mnist_mlp_learns():
    mesh = build_mesh(MeshSpec(dp=4, tp=2))
    model = MnistMLP(hidden=64)
    x = jax.random.normal(jax.random.key(0), (64, 28, 28, 1))
    w = jax.random.normal(jax.random.key(1), (784, 10))
    labels = jnp.argmax(x.reshape(64, -1) @ w, axis=-1)
    batch = {"x": x, "y": labels}

    def loss_fn(params, batch, rng):
        logits = model.apply({"params": params}, batch["x"])
        acc = jnp.mean((jnp.argmax(logits, -1) == batch["y"]).astype(
            jnp.float32))
        return classification_loss(logits, batch["y"]), {"acc": acc}

    state, state_sh = init_sharded_state(model, x, optax.adam(1e-2), mesh)
    step = jit_train_step(loss_fn, mesh, state_sh, batch)
    first = last = None
    for i in range(30):
        state, m = step(state, batch, jax.random.key(i))
        if first is None:
            first = float(m["loss"])
        last = float(m["loss"])
    assert last < first * 0.5


def test_ring_config_init_outside_shard_map():
    """Regression: ring/ulysses models must init via init_sharded_state
    (no bound sp axis there — _sp_offset falls back to 0)."""
    mesh = build_mesh(MeshSpec(dp=4, tp=2))
    cfg = TransformerConfig.tiny(attn_impl="ring")
    tokens = jnp.zeros((4, 16), jnp.int32)
    # init traces the model with the xla-equivalent single-shard semantics.
    import flax.linen as nn
    from tony_tpu.parallel.sharding import DEFAULT_RULES
    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        variables = Transformer(cfg).init(jax.random.key(0), tokens)
    assert "params" in variables


def test_resnet_init_sharded_on_fsdp_mesh():
    """Regression: the 3-channel stem conv must not claim a sharded
    in-channel axis."""
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    cfg = ResNetConfig.tiny()
    x = jnp.ones((4, 32, 32, 3))
    state, state_sh = init_sharded_state(ResNet(cfg), x, optax.adam(1e-3),
                                         mesh)
    assert int(state.step) == 0


def test_resnet_forward_and_grad():
    cfg = ResNetConfig.tiny()
    model = ResNet(cfg)
    x = jnp.ones((2, 32, 32, 3))
    import flax.linen as nn
    from tony_tpu.parallel.sharding import DEFAULT_RULES
    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        variables = model.init(jax.random.key(0), x)
        logits = model.apply(variables, x)
    assert logits.shape == (2, cfg.num_classes)

    def loss(params):
        out = model.apply({"params": params}, x)
        return jnp.mean(out ** 2)

    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        # jit: eager per-op dispatch of a conv net on the virtual mesh
        # costs >10 s of pure Python; one compiled program is ~1 s.
        g = jax.jit(jax.grad(loss))(nn.meta.unbox(variables)["params"])
    flat = jax.tree.leaves(g)
    assert all(np.isfinite(leaf).all() for leaf in flat)
