"""Tests of the yardstick itself. Not collected by ``pytest tests/``; run

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/cells/test_cells.py -q

- the trace reduction on a small recorded (synthetic) trace;
- the operation and byte counts against a hand count;
- the control: the program with its int8 matmul path on comes out not
  correct;
- the planted faults: a run driven past the harness's look for a chip, with
  the timed step broken underneath, comes out not correct.

The last two run the whole of ``train.run_cell`` at the rehearsal table's tiny
size with the kernels interpreted; the limits they are held to were read at
that size (``fixtures/rehearsal/limits``), as the cells' limits were read at
theirs on the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, ROOT]
# Four virtual devices for the sharded cell; set before anything imports jax.
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import arch  # noqa: E402
import counts  # noqa: E402
import trace_reduce  # noqa: E402

REHEARSAL = os.path.join(HERE, "fixtures", "rehearsal")


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# Trace reduction
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_trace(
        load(os.path.join(HERE, "fixtures", "synthetic_trace.json")))


def test_busy_union_and_idle_share(reduced):
    # device 0: busy [0,1000) + [1200,1500) of window 1500; device 1: busy
    # [0,600) + [1000,1500) of window 1500. Averages over the two.
    assert reduced["devices"] == 2
    assert reduced["window_s"] == pytest.approx(1500e-9)
    assert reduced["busy_s"] == pytest.approx((1300 + 1100) / 2 * 1e-9)
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert idle == pytest.approx(1 - 1200 / 1500)


def test_kernel_time_by_name_is_self_time(reduced):
    ops = reduced["ops"]
    # the loop's own time is what its body does not cover: 1000 - 900
    assert ops["while.1 while (f32[8])"] == [0.5, pytest.approx(50e-9)]
    assert ops["fusion.1 fusion f32[8,8]"] == [1.0, pytest.approx(450e-9)]
    flash = [k for k in ops if "tpu_custom_call" in k]
    assert len(flash) == 1 and ops[flash[0]][1] == pytest.approx(100e-9)


def test_exposed_collective_time(reduced):
    # Both collectives run while nothing else does (the enclosing loop does
    # not count as compute over its body).
    assert reduced["collective_s"] == pytest.approx(200e-9)
    assert reduced["collective_exposed_s"] == pytest.approx(200e-9)
    overlapped = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [["all-reduce.1 all-reduce f32[8]", 0,
                                        100]]},
        {"name": "XLA Ops", "events": [["fusion.1 fusion f32[8]", 50,
                                        100]]}]}]}
    # a collective half covered by a fusion that outlasts it
    got = trace_reduce.reduce_trace(overlapped)
    assert got["collective_exposed_s"] == pytest.approx(50e-9)


def test_idle_gaps_go_to_the_host_annotation(reduced):
    gaps = reduced["idle_gaps"]
    # device 0's gap [1000,1200): dispatch covers 150 of it, wait 50;
    # device 1's gap [600,1000): dispatch covers 50, wait none.
    assert gaps == {"bench.dispatch": pytest.approx(300e-9)}
    bd = trace_reduce.breakdown(reduced)
    assert bd["idle_gaps"][0][0] == "bench.dispatch"
    assert len(bd["device_ops"]) <= 10


def test_short_name_tells_the_kernels_apart():
    fwd = ('%attn.8 = (bf16[8,32,2048,128]{3,2,1,0:T(8,128)(2,1)}, '
           'f32[8,32,8,2048]{3,2,1,0:T(8,128)}) custom-call(bf16[8,32,2048,'
           '128]{3,2,1,0} %a, bf16[8,8,2048,128]{3,2,1,0} %b, bf16[8,8,2048,'
           '128]{3,2,1,0} %c), custom_call_target="tpu_custom_call", '
           'operand_layout_constraints={bf16[8,32,2048,128]{3,2,1,0}, '
           'bf16[8,8,2048,128]{3,2,1,0}, bf16[8,8,2048,128]{3,2,1,0}}, '
           'frontend_attributes={kernel_metadata={}}')
    assert trace_reduce.short_name(fwd) == (
        "attn.8 tpu_custom_call (bf16[8,32,2048,128], f32[8,32,8,2048]) "
        "operands=3")
    assert trace_reduce.short_name(
        "%all-reduce.10 = bf16[4,8192,6144]{2,1,0} all-reduce(bf16[4,8192,"
        "6144]{2,1,0} %x), replica_groups={}") == \
        "all-reduce.10 all-reduce bf16[4,8192,6144]"


# ---------------------------------------------------------------------------
# Counts, against a hand count for mistral-7b-v0.3 at seq2k
# ---------------------------------------------------------------------------
def test_counts_against_a_hand_count():
    cfg = load(os.path.join(HERE, "configs", "mistral-7b-v0.3.json"))
    traffic = load(os.path.join(HERE, "traffic", "seq2k.json"))
    dense = arch.load(arch.find(cfg, "mistral-7b-v0.3.json", HERE), "counts")
    # one layer: wq 4096*4096 + wk, wv 2 * 4096*1024 + wo 4096*4096
    # + gate, up, down 3 * 4096*14336 = 218,103,808
    layer = 16777216 + 2 * 4194304 + 16777216 + 3 * 58720256
    assert layer == 218103808
    head = 4096 * 32768                                   # 134,217,728
    assert dense.matmul_params(cfg) == 2 * layer + head == 570425344
    assert dense.total_params(cfg) == 570425344 + head + 5 * 4096
    # forward per token: 2 * 570,425,344 + 2 layers * 2 * 2048 * 4096
    fwd = 1140850688 + 33554432
    assert dense.model_flops_per_token(cfg, 2048) == 3 * fwd == 3523215360
    shape = dense.flash_shard_shape(cfg, traffic)
    assert shape == (8, 32, 8, 2048, 128)
    assert dense.flash_calls(cfg, traffic) == [(shape, {"window": None}, 2)]
    # forward kernel: 2 matmuls * 2 * 8*32*2048*2048*128 / 2
    assert counts.flash_call_flops("fwd", shape) == 274877906944
    assert counts.flash_call_flops("dq", shape) == 1.5 * 274877906944
    assert counts.flash_call_flops("dkv", shape) == 2 * 274877906944
    # forward bytes: q and o 2 * 2*8*32*2048*128, k and v 2 * 2*8*8*2048*128,
    # f32 statistics 4*8*32*2048
    assert counts.flash_call_bytes("fwd", shape) == \
        2 * 134217728 + 2 * 33554432 + 2097152
    seconds, binds = counts.flash_call_min_seconds(
        "fwd", shape, counts.peaks("TPU v5 lite"))
    assert binds == "flops"
    assert seconds == pytest.approx(274877906944 / 197e12)
    with pytest.raises(KeyError):
        counts.peaks("TPU v9")
    four = dense.flash_shard_shape(
        load(os.path.join(HERE, "configs", "codestral-22b.json")),
        load(os.path.join(HERE, "traffic", "seq8k.json")))
    assert four == (2, 24, 4, 8192, 128)


# ---------------------------------------------------------------------------
# The control and the planted faults, through train.run_cell
# ---------------------------------------------------------------------------
def run(tmp_path, control="", break_step=None, workload="tiny.b4"):
    import train

    table = load(os.path.join(REHEARSAL, "table.json"))
    cell, = [w for w in table["workloads"] if w["name"] == workload]
    config = os.path.join(REHEARSAL, "configs", cell["config"] + ".json")
    opts = argparse.Namespace(
        config=config,
        architecture=arch.find(load(config), config, REHEARSAL),
        traffic=os.path.join(REHEARSAL, "traffic", cell["traffic"] + ".json"),
        limits=os.path.join(REHEARSAL, "limits", cell["name"] + ".json"),
        chips=cell["chips"], seed=2147483659, seconds=0.3, trace=0,
        out=str(tmp_path), rehearsal=True, control=control)
    result, _ = train.run_cell(opts, break_step=break_step)
    return result


def failing(result):
    return [k for k, c in result["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]


# ``tiny_tied.b4`` is the cell of the architecture that lives under the
# fixtures alone: what the harness does for ``tiny.b4`` it does for it with no
# line of its own.
ONE_DEVICE = ["tiny.b4", "tiny_tied.b4"]


@pytest.mark.parametrize("workload", ONE_DEVICE)
def test_a_sound_run_is_correct(tmp_path, workload):
    result = run(tmp_path, workload=workload)
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("workload", ONE_DEVICE)
def test_the_int8_control_is_not_correct(tmp_path, workload):
    result = run(tmp_path, control="int8", workload=workload)
    assert not result["correct"]
    assert "grad_sample_diff" in failing(result)


def unchanged_state(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch, rng):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch, rng)
        return state, metrics
    return broken


def half_batch(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch, rng):
        tokens = batch["tokens"]
        half = tokens[:tokens.shape[0] // 2]
        twice = jax.device_put(jnp.concatenate([half, half]),
                               tokens.sharding)
        return step(state, {"tokens": twice}, rng)
    return broken


# On fsdp=2 the exchange between chips left out is each data shard stepping
# on the mean over its own half of the rows: what device 0 then holds is what
# ``half_batch`` gives, so the sharded cell plants that.
@pytest.mark.parametrize("workload, fault, caught_by", [
    ("tiny.b4", unchanged_state, "change_norm_gap"),
    ("tiny.b4", half_batch, "grad_norm_gap"),
    ("tiny.b4-4dev", half_batch, "grad_norm_gap"),
    ("tiny_tied.b4", half_batch, "grad_norm_gap")],
    ids=["state_unchanged", "half_batch", "exchange_left_out",
         "half_batch_tied"])
def test_a_planted_fault_is_not_correct(tmp_path, workload, fault,
                                        caught_by):
    result = run(tmp_path, break_step=fault, workload=workload)
    assert not result["correct"]
    assert caught_by in failing(result)


def test_the_sharded_cell_is_correct(tmp_path):
    result = run(tmp_path, workload="tiny.b4-4dev")
    assert result["correct"], result["checks"]
    assert result["device"]["count"] == 4
