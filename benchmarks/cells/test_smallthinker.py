"""Tests of the ``smallthinker`` architecture's files. Not collected by
``pytest tests/``; run

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/cells/test_smallthinker.py -q

- its ``counts.py`` against hand counts at the published widths (parameters,
  operations a token, the band's pairs, what a grouped matmul call needs),
  loaded without JAX;
- its readers on a recorded (synthetic) reduced trace: the windowed and the
  full flash calls told apart by name, the grouped matmuls against their
  needs, no reading over 100 %, nothing read where nothing is named;
- a tiny configuration of the architecture under a rehearsal table of its own
  (``fixtures/rehearsal_smallthinker``): a sound run is ``correct``, the int8
  control and a planted fault are not (through ``train.run_cell``, as
  ``test_cells.py`` does for the dense decoder).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, ROOT]

import arch  # noqa: E402
import counts  # noqa: E402
import run as harness  # noqa: E402

ARCH = os.path.join(HERE, "architectures", "smallthinker")
REHEARSAL = os.path.join(HERE, "fixtures", "rehearsal_smallthinker")


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load(os.path.join(HERE, "configs", "smallthinker-21b-a3b.json"))


@pytest.fixture(scope="module")
def traffic():
    return load(os.path.join(HERE, "traffic", "seq16k.json"))


@pytest.fixture(scope="module")
def st():
    return arch.load(ARCH, "counts")


# ---------------------------------------------------------------------------
# Counts, by hand
# ---------------------------------------------------------------------------
def test_the_configuration_finds_its_architecture_and_states_its_cut(cfg):
    path = os.path.join(HERE, "configs", "smallthinker-21b-a3b.json")
    assert arch.find(cfg, path, HERE) == ARCH
    for part in arch.PARTS:
        assert os.path.isfile(os.path.join(ARCH, part + ".py"))
    for key in ("source", "published", "reduced", "assumed", "deployment"):
        assert cfg[key], key
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entry, = [c for c in bench["configs"]
              if c["name"] == "smallthinker-21b-a3b"]
    assert set(entry["reduced"]) <= set(cfg["reduced"])
    assert cfg["published"]["moe_num_primary_experts"] == 64
    assert cfg["moe_num_active_primary_experts"] == 6       # never cut


def test_parameters_against_a_hand_count(cfg, st):
    # attention: wq, wo 2 * 2560*3584 + wk, wv 2 * 2560*512
    assert st.attention_params(cfg) == 2 * 9175040 + 2 * 1310720 == 20971520
    assert st.expert_params(cfg) == 3 * 2560 * 768 == 5898240
    # a layer here: attention + router 2560*64 + two norms + 16 experts
    layer = 20971520 + 163840 + 5120 + 16 * 5898240
    assert layer == 115512320
    table = 37984 * 2560                                    # 97,239,040
    assert st.total_params(cfg) == 4 * layer + 2 * table + 2560 == 656529920


def test_operations_a_token_against_a_hand_count(cfg, st):
    # weights that multiply a token, forward: per layer attention + router +
    # 6 * 16/64 = 1.5 experts in expectation; the head's 37,984 rows
    assert st.experts_a_token_here(cfg) == 1.5
    weights = 4 * (20971520 + 163840 + 1.5 * 5898240) + 97239040
    assert weights == 217169920
    # pairs a head: one full triangle 16384^2/2, three bands s*w - w^2/2
    band = 16384 * 4096 - 4096 * 4096 // 2
    assert counts.causal_pairs(16384, 4096) == band == 58720256
    assert band / (16384 * 16384 / 2) == 0.4375             # "44 %"
    pairs = 134217728 + 3 * band
    attention = 2 * 2 * 3584 * pairs / 16384                # QK^T and PV
    assert attention == 271581184
    assert st.model_flops_per_token(cfg, 16384) == \
        3 * (2 * weights + attention) == 2117763072
    # a sequence inside the window: every layer the triangle
    assert st.model_flops_per_token(cfg, 2048) == 3 * (
        2 * weights + 4 * 2 * 2 * 3584 * (2048 * 2048 / 2) / 2048)


def test_flash_calls_are_one_full_and_three_windowed(cfg, traffic, st):
    shape = (2, 28, 4, 16384, 128)
    assert st.flash_shard_shape(cfg, traffic) == shape
    assert st.flash_calls(cfg, traffic) == [
        (shape, {"window": None}, 1), (shape, {"window": 4096}, 3)]
    short = dict(traffic, seq=4096)         # the window covers the sequence
    assert st.flash_calls(cfg, short) == [
        ((2, 28, 4, 4096, 128), {"window": None}, 4)]


def test_grouped_matmul_needs_against_a_hand_count(cfg, traffic, st):
    needs = st.moe_gmm_needs(cfg, traffic)
    # 32,768 tokens in chunks of 8,192; of a chunk's 49,152 pairs a quarter
    assert needs["chunks_a_layer"] == 4
    assert needs["rows_a_call"] == 8192 * 6 * 16 / 64 == 12288
    assert needs["flops_a_call"] == 2 * 12288 * 2560 * 768 == 48318382080
    rows_bytes = 2 * 12288 * (2560 + 768)                   # bf16 in and out
    assert needs["bytes_a_call"] == {
        "gmm": rows_bytes + 2 * 16 * 2560 * 768,            # 144,703,488
        "tgmm": rows_bytes + 4 * 16 * 2560 * 768}           # 207,618,048
    peak = counts.peaks("TPU v5 lite")
    assert st.moe_call_min_seconds("gmm", needs, peak) == (
        48318382080 / 197e12, "flops")
    assert st.moe_call_min_seconds("tgmm", needs, peak) == (
        207618048 / 819e9, "bytes")
    # a device with fewer tokens than a chunk routes them all at once
    few = st.moe_gmm_needs(cfg, dict(traffic, global_batch=1, seq=4096))
    assert (few["chunks_a_layer"], few["rows_a_call"]) == (1, 6144)


def test_the_parent_loads_the_counts_without_jax():
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import arch, run; "
            "c = arch.load(sys.argv[2], 'counts'); "
            "assert callable(c.total_params) and callable(c.flash_calls) "
            "and callable(c.model_flops_per_token) "
            "and callable(c.moe_gmm_needs); "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'tony_tpu'))]; assert not bad, bad")
    done = subprocess.run([sys.executable, "-c", code, HERE, ARCH],
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


# ---------------------------------------------------------------------------
# The readers, on a recorded reduced trace
# ---------------------------------------------------------------------------
def _run(cfg, traffic, ops, busy_s=1.0):
    return {"worker": {"trace": {"ops": ops, "busy_s": busy_s,
                                 "window_s": busy_s / 0.999, "steps": 1},
                       "device": {"kind": "TPU v5 lite", "count": 1},
                       "window": {"tokens": 32768 * 40, "seconds": 40.0,
                                  "step_s_p50": 1.0},
                       "compiled_bytes_per_device": 15386714624},
            "architecture": ARCH, "config": cfg, "traffic": traffic}


def _kernel(name, result, operands):
    return f"{name} tpu_custom_call {result} operands={operands}"


@pytest.fixture(scope="module")
def readers():
    return {m.NAME: m for m in harness.load_metrics()}


def test_readers_tell_windowed_from_full_and_stay_under_100(cfg, traffic,
                                                            readers):
    peak = counts.peaks("TPU v5 lite")
    shape = (2, 28, 4, 16384, 128)
    least = {(k, w): counts.flash_call_min_seconds(k, shape, peak, w)[0]
             for k in ("fwd", "dq", "dkv") for w in (None, 4096)}
    ops = {}
    for k in ("fwd", "dq", "dkv"):      # full at 80 %, windowed at 50 %
        ops[_kernel(f"flash_{k}.1", "bf16[2]", 3)] = [
            1.0, least[k, None] / 0.8]
        for i in (2, 3, 4):
            ops[_kernel(f"flash_win_{k}.{i}", "bf16[2]", 3)] = [
                1.0, least[k, 4096] / 0.5]
    needs = arch.load(ARCH, "counts").moe_gmm_needs(cfg, traffic)
    ops[_kernel("moe_gmm.7", "bf16[2]", 4)] = [
        144.0, 144 * needs["flops_a_call"] / 197e12 / 0.6]
    ops[_kernel("moe_tgmm.9", "f32[2]", 4)] = [
        48.0, 48 * needs["bytes_a_call"]["tgmm"] / 819e9 / 0.6]
    ops["fusion.1 fusion f32[8]"] = [1.0, 0.3]
    run = _run(cfg, traffic, ops)
    assert readers["flash_win_roofline"].read(run) == pytest.approx(50.0)
    full_s = sum(least[k, None] for k in ("fwd", "dq", "dkv"))
    win_s = 3 * sum(least[k, 4096] for k in ("fwd", "dq", "dkv"))
    assert readers["flash_roofline.st21b"].read(run) == pytest.approx(
        100 * (full_s + win_s) / (full_s / 0.8 + win_s / 0.5))
    assert readers["moe_gmm_roofline"].read(run) == pytest.approx(60.0)
    took = ops[_kernel("moe_gmm.7", "bf16[2]", 4)][1] \
        + ops[_kernel("moe_tgmm.9", "f32[2]", 4)][1]
    assert readers["moe_gmm_share_of_busy"].read(run) == pytest.approx(
        100 * took)
    assert "flops" in readers["moe_gmm_roofline"].note(run)
    assert readers["device_idle_share.st21b"].read(run) == pytest.approx(0.1)
    assert readers["step_s_p50.st21b"].read(run) == 1.0
    assert readers["step_hbm_gb_per_chip.st21b"].read(run) == 15.386714624
    assert readers["mfu.st21b"].read(run) == pytest.approx(
        100 * 2117763072 * 32768 / 197e12)
    for name in ("flash_win_roofline", "flash_roofline.st21b",
                 "moe_gmm_roofline", "moe_gmm_share_of_busy", "mfu.st21b"):
        assert 0 < readers[name].read(run) <= 100


@pytest.mark.parametrize("name", [
    "mfu", "step_s_p50", "step_s_p95", "step_hbm_gb_per_chip",
    "device_idle_share", "data_wait_share", "orchestrator_s", "user_boot_s",
    "compile_cache_misses", "boot_pre_import_s", "boot_init_state_s",
    "boot_compile_s"])
def test_a_lent_reader_is_the_reader_there(name, readers):
    """``<name>.st21b`` is ``<name>``'s reader and its table entry but for
    the name and the cell, so the two read one quantity."""
    lent, there = readers[name + ".st21b"], readers[name]
    assert lent.read.__code__ == there.read.__code__     # one source
    assert (lent.UNIT, lent.SOURCE, lent.LAYER, lent.MOVES) == (
        there.UNIT, there.SOURCE, there.LAYER, there.MOVES)
    assert hasattr(lent, "note") == hasattr(there, "note")
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine, theirs = ({k: v for k, v in e.items() if k not in
                     ("name", "workloads")} for e in bench["per_layer"]
                    if e["name"] in (name + ".st21b", name))
    assert mine == theirs


def test_nothing_named_is_nothing_read(cfg, traffic, readers):
    """A program that lacks the kernels (the parent, under this PR's
    benchmark files): the readers return nothing and do not raise."""
    bare = _run(cfg, traffic, {"fusion.1 fusion f32[8]": [1.0, 0.3]})
    untraced = _run(cfg, traffic, {})
    untraced["worker"]["trace"] = {}
    for run in (bare, untraced):
        for name in ("flash_win_roofline", "flash_roofline.st21b",
                     "moe_gmm_roofline", "moe_gmm_share_of_busy"):
            assert readers[name].read(run) is None
    dense = dict(bare, architecture=os.path.join(HERE, "architectures",
                                                 "mistral"),
                 config=load(os.path.join(HERE, "configs",
                                          "mistral-7b-v0.3.json")))
    assert readers["moe_gmm_roofline"].read(dense) is None
    assert readers["flash_win_roofline"].read(dense) is None


def test_every_new_entry_has_its_reader_and_its_cell():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    names = {m.NAME: m for m in harness.load_metrics()}
    mine = [e for e in bench["per_layer"]
            if e.get("workloads") == ["st21b.seq16k"]]
    assert len(mine) == 16
    for entry in mine:
        module = names[entry["name"]]
        assert (module.UNIT, module.SOURCE, module.LAYER, module.MOVES) == (
            entry["unit"], entry["source"], entry["layer"], entry["moves"])
    # every end-to-end metric the cell reports has layers under it here
    assert {e["moves"] for e in mine} == {"tokens_per_s_per_chip", "setup_s"}
    cell, config = harness.find_cell(bench, "st21b.seq16k")
    assert cell["chips"] == 1 and config["name"] == "smallthinker-21b-a3b"
    for kind in ("traffic", "limits"):
        name = cell["traffic"] if kind == "traffic" else cell["name"]
        assert os.path.isfile(os.path.join(HERE, kind, name + ".json"))


# ---------------------------------------------------------------------------
# The tiny cell through train.run_cell: sound, control, planted fault
# ---------------------------------------------------------------------------
def run(tmp_path, control="", break_step=None):
    import train

    table = load(os.path.join(REHEARSAL, "table.json"))
    cell, = table["workloads"]
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


def test_a_sound_run_of_the_tiny_cell_is_correct(tmp_path):
    result = run(tmp_path)
    assert result["correct"], result["checks"]
    assert arch.find(load(os.path.join(
        REHEARSAL, "configs", "tiny_st.json")), "tiny_st.json",
        REHEARSAL) == ARCH


def test_the_int8_control_is_not_correct(tmp_path):
    result = run(tmp_path, control="int8")
    assert not result["correct"]
    assert failing(result)


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


def unchanged_state(step):
    import jax
    import jax.numpy as jnp

    def broken(state, batch, rng):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch, rng)
        return state, metrics
    return broken


@pytest.mark.parametrize("fault, caught_by", [
    (unchanged_state, "change_norm_gap"), (half_batch, "grad_norm_gap")],
    ids=["state_unchanged", "half_batch"])
def test_a_planted_fault_is_not_correct(tmp_path, fault, caught_by):
    result = run(tmp_path, break_step=fault)
    assert not result["correct"]
    assert caught_by in failing(result)
