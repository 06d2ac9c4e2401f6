"""Tests of the ``laguna`` architecture's files. Not collected by
``pytest tests/``; run

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/cells/test_laguna.py -q

- its ``counts.py`` against hand counts at the published widths (parameters
  by kind of layer, operations a token, the band's pairs, the two kinds of
  flash call at their own head counts, what a grouped matmul call needs with
  the running sum a weight-gradient call reads), loaded without JAX;
- its readers on a recorded (synthetic) reduced trace: windowed and full
  flash calls held to their own layers' shapes, the grouped matmuls against
  their needs, no reading over 100 %, nothing read where nothing is named;
- a tiny configuration of the architecture under a rehearsal table of its own
  (``fixtures/rehearsal_laguna``): a sound run is ``correct``, the int8
  control and a planted fault are not (through ``train.run_cell``, as
  ``test_smallthinker.py`` does).
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
import test_smallthinker as sparse  # noqa: E402 — its planted faults

ARCH = os.path.join(HERE, "architectures", "laguna")
REHEARSAL = os.path.join(HERE, "fixtures", "rehearsal_laguna")
CELL = "lagS.seq8k"
LENT = ["mfu", "step_s_p50", "step_s_p95", "step_hbm_gb_per_chip",
        "device_idle_share", "data_wait_share", "orchestrator_s",
        "user_boot_s", "compile_cache_misses", "boot_pre_import_s",
        "boot_init_state_s", "boot_compile_s"]
KERNEL_READERS = {"flash_roofline.lagS": "flash_roofline.st21b",
                  "flash_win_roofline.lagS": "flash_win_roofline",
                  "moe_gmm_roofline.lagS": "moe_gmm_roofline",
                  "moe_gmm_share_of_busy.lagS": "moe_gmm_share_of_busy"}
# one kernel, full and windowed calls each held to its own kind of layer
BY_KIND = ["flash_fwd_roofline.lagS", "flash_dq_roofline.lagS",
           "flash_dkv_roofline.lagS", "flash_fwd_calls_per_step.lagS"]


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load(os.path.join(HERE, "configs", "laguna-s-2.1.json"))


@pytest.fixture(scope="module")
def traffic():
    return load(os.path.join(HERE, "traffic", "seq8k-2rows.json"))


@pytest.fixture(scope="module")
def lag():
    return arch.load(ARCH, "counts")


# ---------------------------------------------------------------------------
# Counts, by hand
# ---------------------------------------------------------------------------
def test_the_configuration_finds_its_architecture_and_states_its_cut(cfg):
    path = os.path.join(HERE, "configs", "laguna-s-2.1.json")
    assert arch.find(cfg, path, HERE) == ARCH
    for part in arch.PARTS:
        assert os.path.isfile(os.path.join(ARCH, part + ".py"))
    for key in ("source", "published", "reduced", "assumed", "deployment",
                "share"):
        assert cfg[key], key
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entry, = [c for c in bench["configs"] if c["name"] == "laguna-s-2.1"]
    assert set(entry["reduced"]) <= set(cfg["reduced"])
    assert entry["source"] == cfg["source"]
    # every width as published, and what is never cut
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"],
            cfg["shared_expert_intermediate_size"], cfg["sliding_window"],
            cfg["num_experts_per_tok"], cfg["moe_routed_scaling_factor"]) \
        == (3072, 128, 12288, 1024, 1024, 512, 10, 2.5)
    assert cfg["published"]["num_experts"] == 256
    assert cfg["max_position_embeddings"] == 1048576


def test_the_catalog_s_numbers_are_the_file_s_but_for_the_cuts(cfg):
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, the keys listed in ``reduced`` apart."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog, encoding="utf-8") as f:
        row, = [r for r in map(json.loads, f) if r["name"] == "Laguna-S-2.1"]
    assert row["source_url"] == cfg["source"]
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) - {"arithmetic", "total"}


def test_parameters_against_a_hand_count(cfg, lag):
    # full layer, 24 heads: wq, wo 2 * 3072*3072 + wk, wv 2 * 3072*512 + wg
    assert lag.attention_params(cfg, 24) == \
        2 * 9437184 + 2 * 1572864 + 73728 == 22093824
    # window layer, 36 heads: 2 * 3072*4608 + 2 * 3072*512 + 3072*36
    assert lag.attention_params(cfg, 36) == \
        2 * 14155776 + 2 * 1572864 + 110592 == 31567872
    assert lag.gated_mlp_params(cfg, 12288) == 113246208
    assert lag.gated_mlp_params(cfg, 1024) == 9437184
    assert lag.sparse_side_params(cfg) == 786432 + 9437184 == 10223616
    layer0 = 22093824 + 113246208 + 6144
    window = 31567872 + 10223616 + 8 * 9437184 + 6144
    layer4 = 22093824 + 10223616 + 8 * 9437184 + 6144
    assert (layer0, window, layer4) == (135346176, 117295104, 107821056)
    table = 12544 * 3072                                    # 38,535,168
    assert lag.total_params(cfg) == \
        layer0 + 3 * window + layer4 + 2 * table + 3072 == 672125952
    # the heads whole: the cut the issue's arithmetic rules out
    whole = dict(cfg, num_attention_heads_per_layer=[48, 72, 72, 72, 48],
                 num_key_value_heads=8)
    assert lag.total_params(whole) == 811017216


def test_operations_a_token_against_a_hand_count(cfg, lag):
    # weights that multiply a token, forward
    assert lag.experts_a_token_here(cfg) == 10 * 8 / 256 == 0.3125
    attention = 2 * 22093824 + 3 * 31567872                 # 138,891,264
    sparse = 4 * (10223616 + 0.3125 * 9437184)              # 52,690,944
    weights = attention + 113246208 + sparse + 38535168
    assert weights == 343363584
    # pairs a head: the triangle 8192^2/2, the band s*w - w^2/2
    band = 8192 * 512 - 512 * 512 // 2
    assert counts.causal_pairs(8192, 512) == band == 4063232
    assert round(band / (8192 * 8192 / 2), 4) == 0.1211     # "12 %"
    pairs = 2 * 2 * (2 * 3072 * 33554432 + 3 * 4608 * band) / 8192
    assert pairs == 128090112
    assert lag.model_flops_per_token(cfg, 8192) == \
        3 * (2 * weights + pairs) == 2444451840
    # a sequence inside the window: every layer the triangle
    assert lag.model_flops_per_token(cfg, 512) == 3 * (
        2 * weights + 2 * 2 * (2 * 3072 + 3 * 4608) * (512 * 512 / 2) / 512)


def test_flash_calls_differ_in_their_heads(cfg, traffic, lag):
    full, banded = (2, 24, 4, 8192, 128), (2, 36, 4, 8192, 128)
    assert lag.flash_calls(cfg, traffic) == [
        (full, {"window": None}, 2), (banded, {"window": 512}, 3)]
    short = dict(traffic, seq=512)          # the window covers the sequence
    assert lag.flash_calls(cfg, short) == [
        ((2, 24, 4, 512, 128), {"window": None}, 2),
        ((2, 36, 4, 512, 128), {"window": None}, 3)]


def test_grouped_matmul_needs_against_a_hand_count(cfg, traffic, lag):
    needs = lag.moe_gmm_needs(cfg, traffic)
    # 16,384 tokens in chunks of 8,192; of a chunk's 81,920 pairs 8 / 256
    assert needs["chunks_a_layer"] == 2
    assert needs["rows_a_call"] == 8192 * 10 * 8 / 256 == 2560
    assert needs["flops_a_call"] == 2 * 2560 * 3072 * 1024 == 16106127360
    rows_bytes = 2 * 2560 * (3072 + 1024)                   # bf16 in and out
    leaf = 8 * 3072 * 1024
    assert needs["bytes_a_call"] == {
        "gmm": rows_bytes + 2 * leaf,                       # 71,303,168
        # the running sum read and the result written, both float32
        "tgmm": rows_bytes + 4 * leaf + 4 * leaf}           # 222,298,112
    peak = counts.peaks("TPU v5 lite")
    # 2,560 rows against eight [3072, 1024] matrices: the bytes bind both
    assert lag.moe_call_min_seconds("gmm", needs, peak) == (
        71303168 / 819e9, "bytes")
    assert lag.moe_call_min_seconds("tgmm", needs, peak) == (
        222298112 / 819e9, "bytes")


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
def _run(cfg, traffic, ops, busy_s=0.6):
    return {"worker": {"trace": {"ops": ops, "busy_s": busy_s,
                                 "window_s": busy_s / 0.999, "steps": 1},
                       "device": {"kind": "TPU v5 lite", "count": 1},
                       "window": {"tokens": 16384 * 60, "seconds": 40.0,
                                  "step_s_p50": 0.65},
                       "compiled_bytes_per_device": 12746787840},
            "architecture": ARCH, "config": cfg, "traffic": traffic}


def _kernel(name, result, operands):
    return f"{name} tpu_custom_call {result} operands={operands}"


@pytest.fixture(scope="module")
def readers():
    return {m.NAME: m for m in harness.load_metrics()}


def test_each_kind_of_call_is_held_to_its_own_layers_shape(cfg, traffic,
                                                           readers):
    peak = counts.peaks("TPU v5 lite")
    full, banded = (2, 24, 4, 8192, 128), (2, 36, 4, 8192, 128)
    least = {k: (counts.flash_call_min_seconds(k, full, peak, None)[0],
                 counts.flash_call_min_seconds(k, banded, peak, 512)[0])
             for k in ("fwd", "dq", "dkv")}
    ops = {}
    for k in ("fwd", "dq", "dkv"):      # full at 80 %, windowed at 40 %
        for i in (1, 2):
            ops[_kernel(f"flash_{k}.{i}", "bf16[2]", 3)] = [
                1.0, least[k][0] / 0.8]
        for i in (3, 4, 5):
            ops[_kernel(f"flash_win_{k}.{i}", "bf16[2]", 3)] = [
                1.0, least[k][1] / 0.4]
    needs = arch.load(ARCH, "counts").moe_gmm_needs(cfg, traffic)
    ops[_kernel("moe_gmm.7", "bf16[2]", 4)] = [
        72.0, 72 * needs["bytes_a_call"]["gmm"] / 819e9 / 0.6]
    ops[_kernel("moe_tgmm.9", "f32[2]", 5)] = [
        24.0, 24 * needs["bytes_a_call"]["tgmm"] / 819e9 / 0.6]
    ops["fusion.1 fusion f32[8]"] = [1.0, 0.3]
    run = _run(cfg, traffic, ops)
    assert readers["flash_win_roofline.lagS"].read(run) == pytest.approx(40.0)
    full_s = 2 * sum(v[0] for v in least.values())
    win_s = 3 * sum(v[1] for v in least.values())
    assert readers["flash_roofline.lagS"].read(run) == pytest.approx(
        100 * (full_s + win_s) / (full_s / 0.8 + win_s / 0.4))
    for k in ("fwd", "dq", "dkv"):
        assert readers[f"flash_{k}_roofline.lagS"].read(run) == pytest.approx(
            100 * (2 * least[k][0] + 3 * least[k][1])
            / (2 * least[k][0] / 0.8 + 3 * least[k][1] / 0.4))
    assert readers["flash_fwd_calls_per_step.lagS"].read(run) == 5.0
    assert "5 calls in 1 steps" in readers[
        "flash_fwd_calls_per_step.lagS"].note(run)
    assert readers["moe_gmm_roofline.lagS"].read(run) == pytest.approx(60.0)
    assert "bytes" in readers["moe_gmm_roofline.lagS"].note(run)
    took = ops[_kernel("moe_gmm.7", "bf16[2]", 4)][1] \
        + ops[_kernel("moe_tgmm.9", "f32[2]", 5)][1]
    assert readers["moe_gmm_share_of_busy.lagS"].read(run) == pytest.approx(
        100 * took / 0.6)
    assert readers["device_idle_share.lagS"].read(run) == pytest.approx(0.1)
    assert readers["step_s_p50.lagS"].read(run) == 0.65
    assert readers["step_hbm_gb_per_chip.lagS"].read(run) == 12.74678784
    assert readers["mfu.lagS"].read(run) == pytest.approx(
        100 * 2444451840 * 16384 * 60 / 40 / 197e12)
    for name in (*KERNEL_READERS, *BY_KIND[:3], "mfu.lagS"):
        assert 0 < readers[name].read(run) <= 100


@pytest.mark.parametrize("mine, there", [
    *((name + ".lagS", name) for name in LENT), *KERNEL_READERS.items()])
def test_a_lent_reader_is_the_reader_there(mine, there, readers):
    """``<name>.lagS`` is the reader and the table entry of the metric it
    borrows but for the name and the cell, so the two read one quantity."""
    lent, theirs = readers[mine], readers[there]
    assert lent.read.__code__ == theirs.read.__code__       # one source
    assert (lent.UNIT, lent.SOURCE, lent.LAYER, lent.MOVES) == (
        theirs.UNIT, theirs.SOURCE, theirs.LAYER, theirs.MOVES)
    assert hasattr(lent, "note") == hasattr(theirs, "note")
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    a, b = ({k: v for k, v in e.items() if k not in ("name", "workloads")}
            for e in bench["per_layer"] if e["name"] in (mine, there))
    assert a == b


def test_nothing_named_is_nothing_read(cfg, traffic, readers):
    """A program that lacks the kernels, or an untraced run: the readers
    return nothing and do not raise."""
    bare = _run(cfg, traffic, {"fusion.1 fusion f32[8]": [1.0, 0.3]})
    untraced = _run(cfg, traffic, {})
    untraced["worker"]["trace"] = {}
    for run in (bare, untraced):
        for name in (*KERNEL_READERS, *BY_KIND):
            assert readers[name].read(run) is None


def test_every_new_entry_has_its_reader_and_its_cell():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    names = {m.NAME: m for m in harness.load_metrics()}
    mine = [e for e in bench["per_layer"] if e.get("workloads") == [CELL]]
    assert len(mine) == 20
    assert {e["name"] for e in mine} == {n + ".lagS" for n in LENT} \
        | set(KERNEL_READERS) | set(BY_KIND)
    for entry in mine:
        module = names[entry["name"]]
        assert (module.UNIT, module.SOURCE, module.LAYER, module.MOVES) == (
            entry["unit"], entry["source"], entry["layer"], entry["moves"])
    # every end-to-end metric the cell reports has layers under it here
    assert {e["moves"] for e in mine} == {"tokens_per_s_per_chip", "setup_s"}
    # appended, nothing before them touched: the table's last 20 entries
    assert bench["per_layer"][-20:] == mine
    assert bench["workloads"][-1]["name"] == CELL
    cell, config = harness.find_cell(bench, CELL)
    assert cell["chips"] == 1 and config["name"] == "laguna-s-2.1"
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
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


def test_a_sound_run_of_the_tiny_cell_is_correct(tmp_path):
    result = run(tmp_path)
    assert result["correct"], result["checks"]
    assert arch.find(load(os.path.join(
        REHEARSAL, "configs", "tiny_lag.json")), "tiny_lag.json",
        REHEARSAL) == ARCH


def test_the_int8_control_is_not_correct(tmp_path):
    result = run(tmp_path, control="int8")
    assert not result["correct"]
    assert sparse.failing(result)


@pytest.mark.parametrize("fault, caught_by", [
    (sparse.unchanged_state, "change_norm_gap"),
    (sparse.half_batch, "grad_norm_gap")],
    ids=["state_unchanged", "half_batch"])
def test_a_planted_fault_is_not_correct(tmp_path, fault, caught_by):
    result = run(tmp_path, break_step=fault)
    assert not result["correct"]
    assert caught_by in sparse.failing(result)
