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


def test_flash_fwd_bwd_compiles_per_shard_for_fsdp2_tp2(v5e_devices,
                                                        kernel_operands):
    mesh = build_mesh(MeshSpec(dp=1, fsdp=2, tp=2), devices=v5e_devices)
    b, s, h, hk, d = 4, 256, 4, 2, 128
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
    calls = kernel_operands(compiled.as_text())
    assert len(calls) == 3, calls                     # fwd, dq, dkv
    for c in calls:
        # Each device gets [B/fsdp, H/tp, S, D] — never the global arrays,
        # and nothing gathered on the way in.
        assert [b // 2, h // 2, s, d] in c["shapes"], c
        assert [b // 2, hk // 2, s, d] in c["shapes"], c
        assert [b, h, s, d] not in c["shapes"], c
        assert not [p for p in c["producers"]
                    if p.startswith("all-gather")], c


def test_groupnorm_apply_compiles_at_a_resnet50_shape(v5e_devices,
                                                      kernel_operands):
    mesh = build_mesh(MeshSpec(), devices=v5e_devices)       # dp=4
    b, hw, c = 8, 56, 64             # ResNet-50 stage-1 activation, batch>1
    x = _abstract((b, hw, hw, c), jnp.bfloat16, mesh,
                  P(BATCH_AXES, None, None, None))
    vec = _abstract((c,), jnp.float32, mesh, P())

    def fn(x, scale, bias):
        return fused_groupnorm_relu(x, scale, bias, groups=32)

    with jax.set_mesh(mesh):
        compiled = jax.jit(fn).lower(x, vec, vec).compile()
    calls = kernel_operands(compiled.as_text())
    assert len(calls) == 1, calls
    # x flattened to [B/dp, H·W, C]; a and b as [B/dp, 1, C].
    assert calls[0]["shapes"] == [[b // 4, hw * hw, c], [b // 4, 1, c],
                                  [b // 4, 1, c]], calls
