"""Ahead-of-time TPU compiles that need no chip.

The CPU suite runs the Pallas kernels in interpret mode, where a kernel
lowers to ordinary HLO: it cannot see what XLA:TPU and Mosaic refuse. With
libtpu installed, ``jax.experimental.topologies`` describes a v5e 2x2 host
and ``jit(...).lower(...).compile()`` runs the real TPU compiler against it
from this CPU host. Two refusals are pinned here, both found that way
before the first chip run:

- a Mosaic kernel under a multi-device mesh ("Mosaic kernels cannot be
  automatically partitioned") — the flash kernels must arrive at the
  compiler per shard (``compat.per_shard``);
- a block the TPU lowering rejects at every real shape (the GroupNorm
  apply's folded-affine vectors at batch > 1).

Small shapes: the pair costs a few seconds.
"""

import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tony_tpu.ops.attention import _interpret, flash_attention
from tony_tpu.ops.convfuse import fused_groupnorm_relu
from tony_tpu.parallel import MeshSpec, build_mesh
from tony_tpu.parallel.mesh import BATCH_AXES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# A TPU executable that an earlier run left in the shared compile cache
# cannot be deserialised on a CPU host; jax warns and compiles again.
pytestmark = pytest.mark.filterwarnings(
    "ignore:Error reading persistent compilation cache entry")


@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu here: nothing to ask
        pytest.skip(f"no TPU compiler on this host: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices


@pytest.fixture(scope="module")
def kernel_operands():
    """chip_smoke.py's reading of a compiled module's Mosaic kernels — one
    implementation for the smoke and for this guard."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.kernel_operands


def _abstract(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


FLASH_SHAPE = (4, 256, 4, 2, 128)        # b, s, h, hk, d


@pytest.fixture(scope="module")
def flash_grad_hlo(v5e_devices):
    """The flash forward and backward, compiled for ``fsdp=2,tp=2``."""
    mesh = build_mesh(MeshSpec(dp=1, fsdp=2, tp=2), devices=v5e_devices)
    b, s, h, hk, d = FLASH_SHAPE
    spec = P(BATCH_AXES, None, "tp", None)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    args = (_abstract((b, s, h, d), jnp.bfloat16, mesh, spec),
            _abstract((b, s, hk, d), jnp.bfloat16, mesh, spec),
            _abstract((b, s, hk, d), jnp.bfloat16, mesh, spec))
    with jax.set_mesh(mesh):
        # Keyed on the bound mesh's devices, not on this host's backend.
        assert _interpret() is False
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *args).compile()
    return compiled.as_text()


def test_flash_fwd_bwd_compiles_per_shard_for_fsdp2_tp2(flash_grad_hlo,
                                                        kernel_operands):
    b, s, h, hk, d = FLASH_SHAPE
    calls = kernel_operands(flash_grad_hlo)
    assert len(calls) == 3, calls                     # fwd, dq, dkv
    for c in calls:
        # Each device gets [B/fsdp, H/tp, S, D] — never the global arrays,
        # and nothing gathered on the way in.
        assert [b // 2, h // 2, s, d] in c["shapes"], c
        assert [b // 2, hk // 2, s, d] in c["shapes"], c
        assert [b, h, s, d] not in c["shapes"], c
        assert not [p for p in c["producers"]
                    if p.startswith("all-gather")], c


def test_flash_kernels_carry_their_names(flash_grad_hlo, kernel_operands):
    """The compiled module names the three Mosaic calls after the kernels
    (``pallas_call(name=...)``), and holds no other: the device trace
    shows the same names, which is what the per-kernel rooflines select
    by."""
    names = sorted(c["name"] for c in kernel_operands(flash_grad_hlo))
    assert len(names) == 3, names
    for name, kernel in zip(names, ("flash_dkv", "flash_dq", "flash_fwd")):
        assert name.startswith(kernel), names


def _mosaic_kernels(lowered_text):
    """Each Mosaic call's kernel in a lowered module, as MLIR text without
    source locations (a kernel carries its source's file and lines, which
    any edit above it moves), in the module's order."""
    import base64
    import json

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    kernels = []
    for config in re.findall(r'tpu_custom_call.*?backend_config = "([^"]*)"',
                             lowered_text):
        body = json.loads(config.replace("\\22", '"'))[
            "custom_call_config"]["body"]
        with mlir.make_ir_context() as ctx:
            ctx.allow_unregistered_dialects = True
            kernels.append(ir.Module.parse(base64.b64decode(body)).operation
                           .get_asm(enable_debug_info=False))
    return kernels


# sha256 (first 16 hex digits) of the flash fwd, dq and dkv kernels where q,
# k and v share one width, lowered for one v5e, before v took a width of its
# own: the cells' three kinds (heads of 128 at 2 q heads a kv head, heads of
# 64 at 4, a window of 512).
EQUAL_WIDTH_KERNELS = {
    (2, 128, None): ("c21fa71365848af5", "9c51f52ca05e66bb",
                     "5f0923ac4514c10e"),
    (1, 64, None): ("1b17efc79c3f677e", "772bc073480343c0",
                    "5cfeb4a00b5bc5d2"),
    (2, 128, 512): ("326333d136e50b13", "af9e3d9952d4b90e",
                    "09981f96b0db628f"),
}


@pytest.mark.parametrize("hk, d, window", sorted(
    EQUAL_WIDTH_KERNELS, key=str))
def test_flash_at_equal_widths_lowers_to_the_same_kernels(v5e_devices, hk,
                                                          d, window):
    """A v of its own width leaves every kernel at q, k and v of one width
    as it was, text for text."""
    import hashlib

    mesh = build_mesh(MeshSpec(), devices=v5e_devices[:1])
    rows = P(BATCH_AXES)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window).astype(
            jnp.float32).sum()

    args = [_abstract((1, 2048, n, d), jnp.bfloat16, mesh, rows)
            for n in (4, hk, hk)]
    with jax.set_mesh(mesh):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *args).as_text()
    got = tuple(hashlib.sha256(k.encode()).hexdigest()[:16]
                for k in _mosaic_kernels(text))
    assert got == EQUAL_WIDTH_KERNELS[(hk, d, window)], got


GN_SHAPE = (8, 56, 64)       # ResNet-50 stage-1 activation, batch > 1


@pytest.fixture(scope="module")
def groupnorm_hlo(v5e_devices):
    mesh = build_mesh(MeshSpec(), devices=v5e_devices)       # dp=4
    b, hw, c = GN_SHAPE
    x = _abstract((b, hw, hw, c), jnp.bfloat16, mesh,
                  P(BATCH_AXES, None, None, None))
    vec = _abstract((c,), jnp.float32, mesh, P())

    def fn(x, scale, bias):
        return fused_groupnorm_relu(x, scale, bias, groups=32)

    with jax.set_mesh(mesh):
        compiled = jax.jit(fn).lower(x, vec, vec).compile()
    return compiled.as_text()


def test_groupnorm_apply_compiles_at_a_resnet50_shape(groupnorm_hlo,
                                                      kernel_operands):
    b, hw, c = GN_SHAPE
    calls = kernel_operands(groupnorm_hlo)
    assert len(calls) == 1, calls
    # x flattened to [B/dp, H·W, C]; a and b as [B/dp, 1, C].
    assert calls[0]["shapes"] == [[b // 4, hw * hw, c], [b // 4, 1, c],
                                  [b // 4, 1, c]], calls


def test_groupnorm_kernel_carries_its_name(groupnorm_hlo, kernel_operands):
    call, = kernel_operands(groupnorm_hlo)
    assert call["name"].startswith("groupnorm_relu"), call


def _compiled_step_text(v5e_devices, params, batch, loss_fn, tx):
    """``compiled.as_text()`` of ``jit_train_step`` over ``params`` (f32
    leaves, replicated) for one v5e, by the step's recording door."""
    from tony_tpu.parallel import jit_train_step
    from tony_tpu.parallel.train import TrainState

    mesh = build_mesh(MeshSpec(), devices=v5e_devices[:1])
    replicated = NamedSharding(mesh, P())
    shapes = jax.eval_shape(lambda: TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params), tx=tx))
    state_sh = jax.tree.map(lambda _: replicated, shapes)
    a_state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=replicated), shapes)
    a_batch = {k: _abstract(v.shape, v.dtype, mesh, P(BATCH_AXES, None))
               for k, v in batch.items()}
    a_rng = _abstract((2,), jnp.uint32, mesh, P())
    step = jit_train_step(loss_fn, mesh, state_sh, a_batch)
    return step.lower(a_state, a_batch, a_rng).compile().as_text()


def test_train_step_scopes_reach_the_compiled_op_names(v5e_devices):
    """``jit_train_step`` puts ``tony.loss_and_grad`` and
    ``tony.optimizer`` into the ``op_name`` of what it compiles, and jax
    stamps ``jvp``/``transpose`` inside the first: forward, backward and
    optimizer are three disjoint prefixes in a device trace's metadata."""
    import optax

    def loss_fn(params, batch, rng):
        return jnp.tanh(batch["x"] @ params["w"]).sum(), {}

    hlo = _compiled_step_text(
        v5e_devices, {"w": jax.ShapeDtypeStruct((256, 256), jnp.float32)},
        {"x": jax.ShapeDtypeStruct((8, 256), jnp.float32)}, loss_fn,
        optax.adamw(1e-3))
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    in_grad = {n for n in op_names if "/tony.loss_and_grad/" in n}
    in_opt = {n for n in op_names if "/tony.optimizer/" in n}
    assert in_grad and in_opt and not in_grad & in_opt
    assert any("transpose(jvp(" in n for n in in_grad), in_grad
    assert any("jvp(" in n and "transpose(" not in n for n in in_grad)


def test_no_weight_gradient_product_carries_its_leaf_s_update(v5e_devices):
    """``TrainState.apply_gradients`` keeps each leaf's AdamW update out of
    the product that makes the leaf's gradient: one ``[2048, 1024] ×
    [1024, 4096]`` product, an MLP's, through ``jit_train_step`` and
    ``optax.adamw``, compiles with no fusion of the backward that holds
    ``tony.optimizer`` operations. Without the barrier the same step gives
    ``with_update`` ``['fusion.8']``: the weight gradient's product with
    the update as its epilogue (PR 37)."""
    import optax

    from tony_tpu.profiling import scopes

    def loss_fn(params, batch, rng):
        h = batch["x"] @ params["w"].astype(jnp.bfloat16)
        return jnp.square(h.astype(jnp.float32)).mean(), {}

    hlo = _compiled_step_text(
        v5e_devices, {"w": jax.ShapeDtypeStruct((1024, 4096), jnp.float32)},
        {"x": jax.ShapeDtypeStruct((2048, 1024), jnp.bfloat16)}, loss_fn,
        optax.adamw(1e-3, weight_decay=0.1))
    record = scopes.step_scopes(hlo)
    assert record["with_update"] == []
    assert record["scopes"].get("optimizer/-"), record["scopes"].keys()
    assert "opt-barrier" not in hlo


# SmallThinker's attention at its published widths: 28 query heads of 128 in
# groups of 7 a key/value head, a window of 4,096 in a sequence of 16,384.
WINDOW_SHAPE = (1, 16384, 28, 4, 128)        # b, s, h, hk, d


def test_windowed_flash_compiles_at_published_widths(v5e_devices,
                                                     kernel_operands):
    """The three windowed kernels pass Mosaic at the real tile sizes (the
    band's grid axis, the clamped index maps, g = 7), under names of their
    own."""
    mesh = build_mesh(MeshSpec(), devices=v5e_devices[:1])
    b, s, h, hk, d = WINDOW_SHAPE
    spec = P(BATCH_AXES, None, "tp", None)

    def loss(q, k, v):
        return flash_attention(q, k, v, window=4096).astype(
            jnp.float32).sum()

    args = (_abstract((b, s, h, d), jnp.bfloat16, mesh, spec),
            _abstract((b, s, hk, d), jnp.bfloat16, mesh, spec),
            _abstract((b, s, hk, d), jnp.bfloat16, mesh, spec))
    with jax.set_mesh(mesh):
        hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *args).compile().as_text()
    names = sorted(c["name"] for c in kernel_operands(hlo))
    assert len(names) == 3, names
    for name, kernel in zip(names, ("flash_win_dkv", "flash_win_dq",
                                    "flash_win_fwd")):
        assert name.startswith(kernel), names


@pytest.mark.parametrize("matmul_dtype", ["", "int8"])
def test_expert_layer_compiles_at_published_widths(v5e_devices,
                                                   kernel_operands,
                                                   matmul_dtype):
    """The grouped matmuls pass Mosaic at SmallThinker's widths (hidden
    2560, experts of 768, 16 of 64 held, 6 a token, row tiles of 256): the
    scalar-prefetched expert table, the clamped row tiles, a [2560, 768]
    matrix double-buffered under the raised VMEM limit; and with int8
    operands and their scales in the forward products."""
    import flax.linen as nn

    from tony_tpu.models.moe import ExpertLayer, ExpertSpec

    mesh = build_mesh(MeshSpec(), devices=v5e_devices[:1])
    spec = ExpertSpec(n_experts=64, top_k=6, width=768, activation="relu",
                      held=(0, 16), route_before_attention=True,
                      chunk_tokens=2048)
    layer = ExpertLayer(spec, jnp.bfloat16, matmul_dtype=matmul_dtype)
    x = _abstract((1, 4096, 2560), jnp.bfloat16, mesh,
                  P(BATCH_AXES, None, None))
    tiny = jnp.zeros((1, 8, 2560), jnp.bfloat16)
    shapes = jax.eval_shape(lambda: nn.meta.unbox(
        layer.init(jax.random.key(0), tiny, tiny))["params"])
    params = jax.tree.map(
        lambda a: _abstract(a.shape, a.dtype, mesh, P()), shapes)

    def loss(p, r, x):
        return layer.apply({"params": p}, r, x).astype(jnp.float32).sum()

    with jax.set_mesh(mesh):
        hlo = jax.jit(jax.grad(loss, argnums=(0, 2))).lower(
            params, x, x).compile().as_text()
    calls = kernel_operands(hlo)
    names = {c["name"].split(".")[0] for c in calls}
    assert names == {"moe_gmm", "moe_tgmm"}, names
    # The two chunks' weight gradients are summed inside moe_tgmm: the loop
    # over chunks holds three calls, each handed its running sum as a fifth
    # operand whose buffer the result takes (XLA honours the alias: nothing
    # copies a leaf), and no fusion adds two leaves.
    sums = [c for c in calls if c["name"].startswith("moe_tgmm")]
    assert [len(c["shapes"]) for c in sums] == [5] * 3, sums
    leaf = r"f32\[16,(?:2560,768|768,2560)\]"
    assert len(re.findall(
        rf"= {leaf}\S* custom-call\(.*output_to_operand_aliasing", hlo)) == 3
    assert not re.findall(rf"= {leaf}\S* copy\(", hlo)
    assert not re.findall(
        rf"= {leaf}\S* fusion\({leaf}\S* %[\w.\-]+, {leaf}", hlo)


def test_the_token_side_gathers_a_slot_for_its_tokens(v5e_devices):
    """At Laguna's widths (256 router outputs, 10 a token, 8 experts held,
    hidden 3,072, two chunks of 8,192 tokens) a ``_gather_sum`` is, compiled:
    one loop over the slots whose inner loop gathers a segment of rows a
    trip, and ONE gather that yields ``[8192, 3072]`` (the sums back in the
    tokens' order, the cast inside), where a loop over the choices had ten.
    The layer's forward and backward hold two: combine's forward and
    dispatch's transpose (the combine that the chunk's backward recomputes
    is dead)."""
    import flax.linen as nn

    from tony_tpu.models import moe
    from tony_tpu.models.moe import ExpertLayer, ExpertSpec

    mesh = build_mesh(MeshSpec(), devices=v5e_devices[:1])
    spec = ExpertSpec(n_experts=256, top_k=10, width=1024, held=(0, 8),
                      routed_scale=2.5)
    layer = ExpertLayer(spec, jnp.bfloat16)
    x = _abstract((2, 8192, 3072), jnp.bfloat16, mesh,
                  P(BATCH_AXES, None, None))
    tiny = jnp.zeros((1, 8, 3072), jnp.bfloat16)
    shapes = jax.eval_shape(lambda: nn.meta.unbox(
        layer.init(jax.random.key(0), tiny, tiny))["params"])
    params = jax.tree.map(
        lambda a: _abstract(a.shape, a.dtype, mesh, P()), shapes)

    def loss(p, r, x):       # the forward's result is read: it stays
        return jnp.square(layer.apply({"params": p}, r, x).astype(
            jnp.float32)).sum()

    with jax.set_mesh(mesh):
        hlo = jax.jit(jax.grad(loss, argnums=(0, 2))).lower(
            params, x, x).compile().as_text()
    gathers = re.findall(
        r"= bf16\[(\d+),3072\]\S* fusion\(.*kind=kCustom.*op_name=\"[^\"]*"
        r"/gather\"", hlo)
    assert gathers.count("8192") == 2, gathers
    # One loop over the slots around one over a slot's segments.
    assert gathers.count(str(moe.TOKEN_SEGMENT_ROWS)) == 2, gathers


def _results(line):
    """[(dtype, dims, minor-to-major layout)] of an instruction line's
    result, every element of a tuple."""
    from tony_tpu.profiling import scopes

    at = scopes._DEFINITION.match(line).end()
    end = scopes._closing(line, at) + 1 if line[at] == "(" \
        else line.index(" ", at)
    return [(dtype, [int(n) for n in dims.split(",") if n],
             [int(n) for n in layout.split(",") if n])
            for dtype, dims, layout in re.findall(
                r"(\w+)\[([\d,]*)\]\{([\d,]*)", line[at:end])]


def test_rope_turns_whole_rows_of_q_and_k(v5e_devices):
    """One attention block at Mistral-7B's head widths (4,096 wide, 32 q and
    8 kv heads of 128, RoPE over the whole head) over 2 × 1,024 tokens,
    forward, the remat's recompute and backward as a cell's step runs them,
    compiled for one v5e: no ``tony.attn.rope`` instruction over the tokens
    has a minor dimension of half a head, none is an f32 copy or a
    concatenate, and no fusion outside every layer's scope is left around q
    or k. The half-split rotation gave q and k sequence-minor f32 copies,
    two halves of 64 columns, a concatenate, and ``subtract_convert``
    fusions cloned without an ``op_name``."""
    import flax.linen as nn

    from tony_tpu.models.transformer import (Attention, LayerSpec,
                                             TransformerConfig,
                                             remat_policy_of)
    from tony_tpu.parallel.sharding import DEFAULT_RULES
    from tony_tpu.profiling import scopes

    b, s, dim, h, hk, d = 2, 1024, 4096, 32, 8, 128
    cfg = TransformerConfig(dim=dim, n_heads=h, n_kv_heads=hk, head_dim=d,
                            n_layers=1, rope_theta=1e6, max_seq_len=s,
                            layers=(LayerSpec(),))
    block = nn.remat(Attention, prevent_cse=True,
                     policy=remat_policy_of(cfg))(cfg, cfg.layer(0))
    mesh = build_mesh(MeshSpec(), devices=v5e_devices[:1])
    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        shapes = jax.eval_shape(lambda: nn.meta.unbox(block.init(
            jax.random.key(0), jnp.zeros((1, 8, dim), jnp.bfloat16),
            jnp.zeros((1, 8), jnp.int32)))["params"])
    params = jax.tree.map(lambda a: _abstract(a.shape, a.dtype, mesh, P()),
                          shapes)

    def loss(p, x, positions):
        y = block.apply({"params": p}, x, positions)
        return jnp.square(y.astype(jnp.float32)).mean()

    def grads(p, x, positions):
        with jax.named_scope("tony.loss_and_grad"):
            return jax.grad(loss, argnums=(0, 1))(p, x, positions)

    with jax.set_mesh(mesh), nn.logical_axis_rules(list(DEFAULT_RULES)):
        hlo = jax.jit(grads).lower(
            params, _abstract((b, s, dim), jnp.bfloat16, mesh, P()),
            _abstract((b, s), jnp.int32, mesh, P())).compile().as_text()
    record = scopes.step_scopes(hlo)
    rope = {name for key, names in record["scopes"].items()
            if key.endswith("/tony.attn.rope") for name in names}
    unscoped = set(record["scopes"].get("other/-", ()))
    assert {key.split("/")[0] for key in record["scopes"]
            if key.endswith("/tony.attn.rope")} == {
                "forward", "recompute", "backward"}, record["scopes"].keys()
    seen = 0
    for line in hlo.splitlines():
        found = scopes._instruction(line)
        if not found or found[0] not in rope | unscoped:
            continue
        name, opcode = found[:2]
        over_tokens = [(dtype, dims[layout[0]]) for dtype, dims, layout
                       in _results(line) if s in dims and layout]
        if name in unscoped:
            assert not (opcode == "fusion" and over_tokens), line
            continue
        seen += bool(over_tokens)
        assert all(minor != d // 2 for _, minor in over_tokens), line
        assert not (opcode == "copy" and any(
            dtype == "f32" for dtype, _ in over_tokens)), line
        assert opcode != "concatenate" and not (
            over_tokens and re.search(r"pad|concatenate", name)), line
    assert seen, "no RoPE instruction over the tokens"


# The cell ``lagS.seq8k``'s whole step as the benchmark builds it: the bytes
# XLA:TPU gives the compiled program on one v5e (arguments + outputs −
# aliased + temporaries), which the chip's `step_hbm_gb_per_chip.lagS` reads
# to the digit. A change of the program's schedule moves it: say so in
# PERF.md and put the new number here.
LAGS_STEP_BYTES = 13_021_602_816


def _cell_step(v5e_devices, kind, config, traffic="seq8k-2rows"):
    """(bytes of the compiled step on one v5e, its Mosaic calls by name) of
    a cell's whole step as the benchmark builds it: the architecture's
    ``program.build`` through ``jit_train_step`` at the cell's traffic."""
    import collections
    import json
    import sys

    import flax.linen as nn
    import optax

    from tony_tpu.parallel import jit_train_step
    from tony_tpu.parallel.mesh import batch_sharding
    from tony_tpu.parallel.sharding import DEFAULT_RULES, param_shardings
    from tony_tpu.parallel.train import TrainState

    cells = os.path.join(REPO, "benchmarks", "cells")
    sys.path.insert(0, cells)
    try:
        import arch
        program = arch.load(os.path.join(cells, "architectures", kind),
                            "program")
    finally:
        sys.path.remove(cells)
    with open(os.path.join(cells, "configs", config + ".json"),
              encoding="utf-8") as f:
        cfg = json.load(f)
    with open(os.path.join(cells, "traffic", traffic + ".json"),
              encoding="utf-8") as f:
        traffic = json.load(f)
    mesh = build_mesh(MeshSpec.from_string(traffic["mesh"]),
                      devices=v5e_devices[:1])
    model, loss_fn = program.build(cfg, traffic, "")
    tx = optax.adamw(cfg["train"]["adamw"]["learning_rate"],
                     weight_decay=0.1)
    tokens = jnp.zeros((traffic["global_batch"], traffic["seq"]), jnp.int32)

    def boxed_init(rng):
        params = model.init(rng, tokens)["params"]
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(params), tx=tx)

    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        abstract = jax.eval_shape(boxed_init, jax.random.key(0))
    state_sh = param_shardings(mesh, abstract, DEFAULT_RULES)
    a_state = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        nn.meta.unbox(abstract), state_sh)
    a_batch = {"tokens": jax.ShapeDtypeStruct(
        tokens.shape, jnp.int32, sharding=batch_sharding(mesh, 1))}
    a_rng = jax.ShapeDtypeStruct((), jax.random.key(0).dtype,
                                 sharding=NamedSharding(mesh, P()))
    step = jit_train_step(loss_fn, mesh, state_sh, {"tokens": tokens})
    compiled = step.lower(a_state, a_batch, a_rng).compile()
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    return total, collections.Counter(re.findall(
        r"%((?:flash|moe|ssd|kda)_[a-z_]+)[.\d]* = ", compiled.as_text()))


@pytest.mark.timeout_s(900)
def test_the_laguna_cell_s_step_compiles_for_one_v5e_and_fits(v5e_devices):
    """Laguna-S-2.1's share at published widths (672,125,952 parameters:
    10.75 GB of state) through ``jit_train_step`` at 2 × 8,192 tokens: the
    step passes XLA:TPU and Mosaic (heads of 24 and 36 over 4, windowed
    calls at 512 × 512 tiles, grouped matmuls over 2,560 of 67,584 rows),
    fits the chip's 16 GB, and holds the Mosaic calls a step needs: 2 full
    and 3 windowed layers' fwd, dq and dkv, and per sparse layer 9
    ``moe_gmm`` and 3 ``moe_tgmm`` in the two chunks' loops."""
    total, names = _cell_step(v5e_devices, "laguna", "laguna-s-2.1")
    assert total == LAGS_STEP_BYTES, total
    assert total < 16e9 and total > 0.25 * 16e9
    assert names == {"flash_fwd": 2, "flash_dq": 2, "flash_dkv": 2,
                     "flash_win_fwd": 3, "flash_win_dq": 3,
                     "flash_win_dkv": 3, "moe_gmm": 36, "moe_tgmm": 12}


# ``nem30b.seq8k``'s step, as ``step_hbm_gb_per_chip.nem30b`` reads it (the
# head RMS counter's reduction is the 194,048 B over 11,321,815,040).
NEM30B_STEP_BYTES = 11_322_009_088


@pytest.mark.timeout_s(900)
def test_the_nemotron_cell_s_step_compiles_for_one_v5e_and_fits(v5e_devices):
    """Nemotron-3-Nano-30B-A3B's share at published widths (666,962,944
    parameters: 10.67 GB of state) through ``jit_train_step`` at 2 × 8,192
    tokens: the step passes XLA:TPU and Mosaic (the scan over 64 heads of 64
    in 8 groups with a state of 128, groups of 16 q heads a kv head, grouped
    matmuls at 1,856 columns in tiles that hang over the edge), fits the
    chip, and holds the Mosaic calls a step needs: a Mamba layer's
    ``ssd_fwd`` twice (the differentiated forward runs in the forward pass
    and in the block's recompute) and ``ssd_bwd`` once, one full flash
    layer, and per sparse layer 6 ``moe_gmm`` and 2 ``moe_tgmm`` in the two
    chunks' loops."""
    total, names = _cell_step(v5e_devices, "nemotron_h",
                              "nemotron-3-nano-30b-a3b")
    assert total == NEM30B_STEP_BYTES, total
    assert total < 16e9 and total > 0.25 * 16e9
    assert names == {"ssd_fwd": 8, "ssd_bwd": 4, "flash_fwd": 1,
                     "flash_dq": 1, "flash_dkv": 1, "moe_gmm": 24,
                     "moe_tgmm": 8}


def test_the_scan_kernels_compile_at_published_widths(v5e_devices,
                                                      kernel_operands):
    """The scan's forward and backward pass Mosaic at the cell's shape (2
    rows of 8,192 tokens, 64 heads of 64 in 8 groups, state 128, chunks of
    128, bf16): heads in pairs on whole lane tiles, the transposed decays,
    the state in VMEM scratch. The forward gives y and each chunk's entering
    state, and nothing of a ``[chunks, Q, Q]`` shape is an operand or a
    result of either call."""
    from tony_tpu.ops.ssd import ssd

    mesh = build_mesh(MeshSpec(), devices=v5e_devices[:1])
    rows = P(BATCH_AXES)
    b, s, h, p, g, n = 2, 8192, 64, 64, 8, 128
    args = (_abstract((b, s, h, p), jnp.bfloat16, mesh, rows),
            _abstract((b, s, h), jnp.float32, mesh, rows),
            _abstract((h,), jnp.float32, mesh, P()),
            _abstract((b, s, g, n), jnp.bfloat16, mesh, rows),
            _abstract((b, s, g, n), jnp.bfloat16, mesh, rows),
            _abstract((h,), jnp.float32, mesh, P()))

    def loss(*a):
        return ssd(*a, impl="kernel").astype(jnp.float32).sum()

    with jax.set_mesh(mesh):
        hlo = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(
            *args).compile().as_text()
    calls = {c["name"].split(".")[0]: c for c in kernel_operands(hlo)}
    assert set(calls) == {"ssd_fwd", "ssd_bwd"}, set(calls)
    assert "f32[2,64,8,128,512]" in hlo        # the entering states
    for call in calls.values():
        assert not any(shape[-2:] == [128, 128] for shape in call["shapes"])
    assert not re.findall(r"\[2,64,[\d,]*128,128\]", hlo)


# ``g4hm.seq8k``'s step, as ``step_hbm_gb_per_chip.g4hm`` reads it.
G4HM_STEP_BYTES = 12_103_374_336


@pytest.mark.timeout_s(900)
def test_the_granite_cell_s_step_compiles_for_one_v5e_and_fits(v5e_devices):
    """Granite 4.0-H Micro's share at published widths (772,160,448
    parameters: 12.35 GB of state at 16 B) through ``jit_train_step`` at 2 ×
    8,192 tokens: the step passes XLA:TPU and Mosaic (the scan over one
    group of 64 heads of 64 in chunks of 256, eight head blocks a grid;
    flash at heads of 64 and the scale 1/64), fits the chip, and holds a
    Mamba layer's ``ssd_fwd`` twice and ``ssd_bwd`` once, nine times, and
    one full flash layer."""
    total, names = _cell_step(v5e_devices, "granitemoehybrid",
                              "granite-4.0-h-micro")
    assert total == G4HM_STEP_BYTES, total
    assert total < 16e9 and total > 0.25 * 16e9
    assert names == {"ssd_fwd": 18, "ssd_bwd": 9, "flash_fwd": 1,
                     "flash_dq": 1, "flash_dkv": 1}


# ``m7b.seq2k``'s step, as ``step_hbm_gb_per_chip`` reads it: the same before
# and after v took a width of its own in the flash kernels.
M7B_STEP_BYTES = 12_528_216_576


@pytest.mark.timeout_s(900)
def test_the_mistral_cell_s_step_compiles_for_one_v5e_and_fits(v5e_devices):
    """Mistral-7B-v0.3's two layers at published widths through
    ``jit_train_step`` at 8 × 2,048 tokens: two flash layers at heads of 128
    (32 q over 8 kv heads), the step's bytes unchanged by latent attention's
    widths."""
    total, names = _cell_step(v5e_devices, "mistral", "mistral-7b-v0.3",
                              "seq2k")
    assert total == M7B_STEP_BYTES, total
    assert names == {"flash_fwd": 2, "flash_dq": 2, "flash_dkv": 2}


# ``kimiL.seq32k``'s step, as ``step_hbm_gb_per_chip.kimiL`` reads it: its
# peak in a KDA layer's backward.
KIMIL_STEP_BYTES = 15_308_228_608


@pytest.mark.timeout_s(900)
def test_the_kimi_linear_cell_s_step_compiles_for_one_v5e_and_fits(
        v5e_devices):
    """Kimi-Linear-48B-A3B's share at published widths (602,433,408
    parameters: 9.64 GB of state) through ``jit_train_step`` at 1 × 32,768
    tokens: the step passes XLA:TPU and Mosaic (the KDA kernels over 32
    heads of 128 in chunks of 64; flash at q and k 192 and v 128; grouped
    matmuls over 8 of 256 experts), fits the chip under 15.5 GB, and holds a
    KDA layer's ``kda_fwd`` twice (the forward and the block's recompute)
    and ``kda_bwd`` once, four times; one flash layer; per sparse layer 9
    ``moe_gmm`` and 3 ``moe_tgmm`` in the eight chunks' loops."""
    total, names = _cell_step(v5e_devices, "kimi_linear",
                              "kimi-linear-48b-a3b", "seq32k")
    assert total == KIMIL_STEP_BYTES, total
    assert total < 15.5e9 and total > 0.25 * 16e9
    assert names == {"kda_fwd": 8, "kda_bwd": 4, "flash_fwd": 1,
                     "flash_dq": 1, "flash_dkv": 1, "moe_gmm": 36,
                     "moe_tgmm": 12}
