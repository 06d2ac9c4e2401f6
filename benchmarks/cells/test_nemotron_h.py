"""Tests of the ``nemotron_h`` architecture's files. Not collected by
``pytest tests/``; run

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/cells/test_nemotron_h.py -q

- its ``counts.py`` against hand counts at the published widths (parameters
  by kind of layer, operations a token with the scan's products, the one kind
  of flash call, what a grouped matmul call of two-matrix experts needs, what
  a scan call needs at the cell's shape and at one small shape), loaded
  without JAX;
- its readers on a recorded (synthetic) reduced trace: ``ssd_roofline.nem30b``
  reads the ``ssd_*`` calls against ``ssd_needs`` and nothing else, each flash
  kernel is read by its own name at groups of 16, no reading over 100 %,
  nothing read where nothing is named;
- a tiny configuration of the architecture under a rehearsal table of its own
  (``fixtures/rehearsal_nemotron_h``): a sound run is ``correct``; the int8
  control, the two planted faults of every cell and a third of this cell's
  own, the scan's state not handed from chunk to chunk, are not (through
  ``train.run_cell``, as ``test_smallthinker.py`` does).
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

ARCH = os.path.join(HERE, "architectures", "nemotron_h")
REHEARSAL = os.path.join(HERE, "fixtures", "rehearsal_nemotron_h")
CONFIG = "nemotron-3-nano-30b-a3b"
CELL = "nem30b.seq8k"
LENT = {name + ".nem30b": name for name in (
    "mfu", "step_s_p50", "step_s_p95", "step_hbm_gb_per_chip",
    "device_idle_share", "data_wait_share", "orchestrator_s", "user_boot_s",
    "compile_cache_misses", "boot_pre_import_s", "boot_init_state_s",
    "boot_compile_s", "moe_gmm_roofline", "moe_gmm_share_of_busy")}
LENT["flash_roofline.nem30b"] = "flash_roofline.st21b"
LENT["flash_fwd_calls_per_step.nem30b"] = "flash_fwd_calls_per_step.lagS"
MINE = ["ssd_roofline.nem30b", "ssd_share_of_busy.nem30b"]
BY_KIND = {f"flash_{kind}_roofline.nem30b": kind
           for kind in ("fwd", "dq", "dkv")}      # through flash_by_kind


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load(os.path.join(HERE, "configs", CONFIG + ".json"))


@pytest.fixture(scope="module")
def traffic():
    return load(os.path.join(HERE, "traffic", "seq8k-2rows.json"))


@pytest.fixture(scope="module")
def nem():
    return arch.load(ARCH, "counts")


# ---------------------------------------------------------------------------
# Counts, by hand
# ---------------------------------------------------------------------------
def test_the_configuration_finds_its_architecture_and_states_its_cut(cfg):
    path = os.path.join(HERE, "configs", CONFIG + ".json")
    assert arch.find(cfg, path, HERE) == ARCH
    for part in arch.PARTS:
        assert os.path.isfile(os.path.join(ARCH, part + ".py"))
    for key in ("source", "published", "reduced", "assumed", "deployment",
                "share", "train"):
        assert cfg[key], key
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entry, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert set(entry["reduced"]) == set(cfg["reduced"]) - {"arithmetic",
                                                           "total"}
    assert entry["source"] == cfg["source"]
    # every width as published, and what is never cut
    assert (cfg["hidden_size"], cfg["mamba_num_heads"], cfg["mamba_head_dim"],
            cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"],
            cfg["chunk_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"],
            cfg["moe_shared_expert_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"]) == (
        2688, 64, 64, 8, 128, 4, 128, 32, 2, 128, 1856, 3712, 6, 2.5)
    assert cfg["published"] == {
        "num_hidden_layers": 52, "n_routed_experts": 128,
        "vocab_size": 131072, "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    # the cut is the published pattern's first nine letters
    assert cfg["published"]["hybrid_override_pattern"].startswith(
        cfg["hybrid_override_pattern"])
    assert len(cfg["hybrid_override_pattern"]) == cfg["num_hidden_layers"] == 9


def test_the_catalog_s_numbers_are_the_file_s_but_for_the_cuts(cfg):
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, the keys listed in ``reduced`` apart."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog, encoding="utf-8") as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"]
    assert row["source_url"] == cfg["source"]
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) - {"arithmetic", "total"}
    assert all(cfg["published"][k] == row["config"][k] for k in differ)


def test_parameters_against_a_hand_count(cfg, nem):
    # W_in [2688, 4096 z + 6144 x B C + 64 dt] + W_out [4096, 2688]
    assert nem.mamba_matmul_params(cfg) == 27697152 + 11010048 == 38707200
    # + conv taps 4*6144 + bias 6144 + A_log, D, dt_bias + W_norm + norm
    assert nem.mamba_params(cfg) == \
        38707200 + 24576 + 6144 + 192 + 4096 + 2688 == 38744896
    assert nem.attention_matmul_params(cfg) == \
        2 * 11010048 + 1376256 == 23396352
    assert nem.relu2_mlp_params(cfg, 1856) == 9977856
    assert nem.sparse_side_params(cfg) == 344064 + 19955712 == 20299776
    sparse_layer = 20299776 + 2688 + 8 * 9977856
    assert sparse_layer == 20302464 + 79822848
    table = 16384 * 2688
    assert nem.total_params(cfg) == \
        4 * 38744896 + 23396352 + 2688 + 4 * sparse_layer \
        + 2 * table + 2688 == 666962944
    # sixteen held: the cut the issue's arithmetic rules out
    assert nem.total_params(dict(cfg, n_routed_experts=16)) == 986254336


def test_operations_a_token_against_a_hand_count(cfg, nem):
    assert nem.experts_a_token_here(cfg) == 6 * 8 / 128 == 0.375
    # the scan's forward products a token and layer: C B^T a group, M X a
    # head, the state left and the state read out
    scan = 2 * 128 * 128 * 8 + 2 * 128 * 64 * 64 + 4 * 128 * 64 * 64
    assert nem.scan_flops_a_token(cfg) == {
        "fwd": scan, "bwd": 6 * 128 * 128 * 8 + 4 * 128 * 64 * 64
        + 10 * 128 * 64 * 64}
    assert scan == 3407872
    weights = 4 * (38707200 + 4 * 6144) + 23396352 \
        + 4 * (20299776 + 0.375 * 9977856) + 16384 * 2688
    assert weights == 318529536
    pairs = 2 * 2 * 32 * 128 * (8192 * 8192 / 2) / 8192     # 67,108,864
    assert nem.model_flops_per_token(cfg, 8192) == \
        3 * (2 * weights + pairs + 4 * scan) == 2153398272


def test_flash_calls_are_one_kind(cfg, traffic, nem):
    assert nem.flash_calls(cfg, traffic) == [
        ((2, 32, 2, 8192, 128), {"window": None}, 1)]


def test_grouped_matmul_needs_against_a_hand_count(cfg, traffic, nem):
    needs = nem.moe_gmm_needs(cfg, traffic)
    assert needs["chunks_a_layer"] == 2
    assert needs["rows_a_call"] == 8192 * 6 * 8 / 128 == 3072
    assert needs["flops_a_call"] == 2 * 3072 * 2688 * 1856
    rows_bytes = 2 * 3072 * (2688 + 1856)
    leaf = 8 * 2688 * 1856
    assert needs["bytes_a_call"] == {"gmm": rows_bytes + 2 * leaf,
                                     "tgmm": rows_bytes + 8 * leaf}
    assert needs["calls_a_chunk_and_layer"] == {"gmm": 6, "tgmm": 2}
    peak = counts.peaks("TPU v5 lite")
    # 3,072 rows against eight [2688, 1856] matrices: the operations bind
    # gmm (0.156 ms against 0.132), the float32 sums' bytes tgmm
    assert nem.moe_call_min_seconds("gmm", needs, peak) == (
        2 * 3072 * 2688 * 1856 / 197e12, "flops")
    assert nem.moe_call_min_seconds("tgmm", needs, peak) == (
        (rows_bytes + 8 * leaf) / 819e9, "bytes")


def test_scan_needs_against_a_hand_count(cfg, traffic, nem):
    needs = nem.ssd_needs(cfg, traffic)
    assert needs["tokens_a_call"] == 16384
    assert needs["flops_a_call"] == {"fwd": 16384 * 3407872,
                                     "bwd": 16384 * 8126464}
    # a token: x, y 8,192 B each, B, C 2,048 B each, two decay vectors 256 B
    # each, the entering state 4 * 128 * 4096 / 128 = 16,384 B
    assert needs["bytes_a_call"] == {
        "fwd": 16384 * (16384 + 4096 + 512 + 16384),
        "bwd": 16384 * (24576 + 8192 + 1024 + 16384)}
    assert needs["calls_a_layer"] == {"fwd": 2, "bwd": 1}
    peak = counts.peaks("TPU v5 lite")
    assert nem.ssd_call_min_seconds("fwd", needs, peak) == (
        16384 * 37376 / 819e9, "bytes")
    assert nem.ssd_call_min_seconds("bwd", needs, peak) == (
        16384 * 50176 / 819e9, "bytes")
    # one small shape: 2 heads of 8 in 1 group, state 16, chunks of 32, one
    # row of 64 tokens
    small = dict(cfg, mamba_num_heads=2, mamba_head_dim=8, n_groups=1,
                 ssm_state_size=16, chunk_size=32)
    needs = nem.ssd_needs(small, {"global_batch": 1, "seq": 64,
                                  "mesh": "dp=1"})
    assert needs["flops_a_call"]["fwd"] == 64 * (
        2 * 32 * 16 + 2 * 32 * 8 * 2 + 4 * 16 * 8 * 2)
    assert needs["bytes_a_call"]["fwd"] == 64 * (
        2 * 2 * 16 + 2 * 2 * 16 + 2 * 4 * 2 + 4 * 16 * 16 // 32)


def test_the_parent_loads_the_counts_without_jax():
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import arch, run; "
            "c = arch.load(sys.argv[2], 'counts'); "
            "assert callable(c.total_params) and callable(c.flash_calls) "
            "and callable(c.model_flops_per_token) "
            "and callable(c.moe_gmm_needs) and callable(c.ssd_needs); "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'tony_tpu'))]; assert not bad, bad")
    done = subprocess.run([sys.executable, "-c", code, HERE, ARCH],
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ARCH, "reference.py"), encoding="utf-8") as f:
        text = f.read()
    assert "tony_tpu" not in text and "pallas" not in text
    assert "lax.scan(token" in text        # the recurrence, token by token


# ---------------------------------------------------------------------------
# The readers, on a recorded reduced trace
# ---------------------------------------------------------------------------
def _run(cfg, traffic, ops, busy_s=0.55, architecture=ARCH):
    return {"worker": {"trace": {"ops": ops, "busy_s": busy_s,
                                 "window_s": busy_s / 0.999, "steps": 1},
                       "device": {"kind": "TPU v5 lite", "count": 1},
                       "window": {"tokens": 16384 * 70, "seconds": 40.0,
                                  "step_s_p50": 0.56},
                       "compiled_bytes_per_device": 11350111744},
            "architecture": architecture, "config": cfg, "traffic": traffic}


def _kernel(name, result, operands):
    return f"{name} tpu_custom_call {result} operands={operands}"


@pytest.fixture(scope="module")
def readers():
    return {m.NAME: m for m in harness.load_metrics()}


def test_the_scan_s_readers_read_the_ssd_calls_and_nothing_else(
        cfg, traffic, nem, readers):
    peak = counts.peaks("TPU v5 lite")
    needs = nem.ssd_needs(cfg, traffic)
    fwd = nem.ssd_call_min_seconds("fwd", needs, peak)[0]
    bwd = nem.ssd_call_min_seconds("bwd", needs, peak)[0]
    ops = {     # forward at 50 % of its roofline, backward at 25 %
        _kernel("ssd_fwd.3", "(bf16[2], f32[2])", 6): [8.0, 8 * fwd / 0.5],
        _kernel("ssd_bwd.1", "(bf16[2], bf16[2])", 8): [4.0, 4 * bwd / 0.25],
        # none of these is the scan's: another kernel, an XLA fusion whose
        # name begins alike, a Mosaic call of another prefix
        _kernel("moe_gmm.7", "bf16[2]", 4): [24.0, 0.01],
        "ssd_fwd_fusion.2 fusion f32[8]": [1.0, 0.2],
        _kernel("flash_fwd.1", "(bf16[2], f32[2])", 3): [1.0, 0.01],
        "fusion.1 fusion f32[8]": [1.0, 0.3],
    }
    run = _run(cfg, traffic, ops)
    took = 8 * fwd / 0.5 + 4 * bwd / 0.25
    assert readers["ssd_roofline.nem30b"].read(run) == pytest.approx(
        100 * (8 * fwd + 4 * bwd) / took)
    note = readers["ssd_roofline.nem30b"].note(run)
    assert "(8.0, 'bytes')" in note and "(4.0, 'bytes')" in note
    assert readers["ssd_share_of_busy.nem30b"].read(run) == pytest.approx(
        100 * took / 0.55)
    assert readers["mfu.nem30b"].read(run) == pytest.approx(
        100 * 2153398272 * 16384 * 70 / 40 / 197e12)
    assert readers["step_hbm_gb_per_chip.nem30b"].read(run) == 11.350111744
    for name in (*MINE, "mfu.nem30b"):
        assert 0 < readers[name].read(run) <= 100


def test_each_flash_kernel_is_read_by_its_own_name(cfg, traffic, readers):
    """The one attention layer's three kernels, each held to groups of 16 q
    heads a kv head; the forward counted once a step, twice where the
    block's remat runs it again."""
    peak = counts.peaks("TPU v5 lite")
    shape = (2, 32, 2, 8192, 128)
    least = {k: counts.flash_call_min_seconds(k, shape, peak, None)[0]
             for k in BY_KIND.values()}
    at = {"fwd": 0.6, "dq": 0.8, "dkv": 0.7}
    ops = {_kernel(f"flash_{k}.{i}", "(bf16[2], f32[2])", 3):
           [1.0, least[k] / at[k]] for i, k in enumerate(at)}
    ops[_kernel("ssd_fwd.3", "(bf16[2], f32[2])", 6)] = [8.0, 0.01]
    ops["flash_fwd_fusion.2 fusion f32[8]"] = [1.0, 0.2]
    run = _run(cfg, traffic, ops)
    for name, kind in BY_KIND.items():
        assert readers[name].read(run) == pytest.approx(100 * at[kind])
        assert "1 calls" in readers[name].note(run)
    assert readers["flash_roofline.nem30b"].read(run) == pytest.approx(
        100 * sum(least.values()) / sum(least[k] / at[k] for k in at))
    assert readers["flash_fwd_calls_per_step.nem30b"].read(run) == 1.0
    ops[_kernel("flash_fwd.9", "(bf16[2], f32[2])", 3)] = [1.0, 0.01]
    assert readers["flash_fwd_calls_per_step.nem30b"].read(
        _run(cfg, traffic, ops)) == 2.0


def test_nothing_named_is_nothing_read(cfg, traffic, readers):
    """A program that lacks the kernels, an untraced run, an architecture
    without a scan: the readers return nothing and do not raise."""
    bare = _run(cfg, traffic, {"fusion.1 fusion f32[8]": [1.0, 0.3]})
    untraced = _run(cfg, traffic, {})
    untraced["worker"]["trace"] = {}
    other = _run(load(os.path.join(HERE, "configs", "laguna-s-2.1.json")),
                 traffic, {_kernel("ssd_fwd.3", "bf16[2]", 6): [8.0, 0.1]},
                 architecture=os.path.join(HERE, "architectures", "laguna"))
    for run in (bare, untraced, other):
        assert readers["ssd_roofline.nem30b"].read(run) is None
    for run in (bare, untraced):
        assert readers["ssd_share_of_busy.nem30b"].read(run) is None
        for name in ("flash_roofline.nem30b", "moe_gmm_roofline.nem30b",
                     "moe_gmm_share_of_busy.nem30b", *BY_KIND,
                     "flash_fwd_calls_per_step.nem30b"):
            assert readers[name].read(run) is None


@pytest.mark.parametrize("mine, there", sorted(LENT.items()))
def test_a_lent_reader_is_the_reader_there(mine, there, readers):
    """``<name>.nem30b`` is the reader and the table entry of the metric it
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


def test_every_new_entry_has_its_reader_and_its_cell():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    names = {m.NAME: m for m in harness.load_metrics()}
    mine = [e for e in bench["per_layer"] if e.get("workloads") == [CELL]]
    assert len(mine) == 21
    assert {e["name"] for e in mine} == set(LENT) | set(MINE) | set(BY_KIND)
    for entry in mine:
        module = names[entry["name"]]
        assert (module.UNIT, module.SOURCE, module.LAYER, module.MOVES) == (
            entry["unit"], entry["source"], entry["layer"], entry["moves"])
    # every end-to-end metric the cell reports has layers under it here
    assert {e["moves"] for e in mine} == {"tokens_per_s_per_chip", "setup_s"}
    # appended in one block after the cells that were there (a later cell's
    # entries may follow: none of these three says "last")
    first = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][first:first + 21] == mine
    assert [w["name"] for w in bench["workloads"]][:5] == [
        "m7b.seq2k", "m7b.seq32k", "st21b.seq16k", "lagS.seq8k", CELL]
    assert [c["name"] for c in bench["configs"]][3] == CONFIG
    cell, config = harness.find_cell(bench, CELL)
    assert cell["chips"] == 1 and config["name"] == CONFIG
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    for kind in ("traffic", "limits"):
        name = cell["traffic"] if kind == "traffic" else cell["name"]
        assert os.path.isfile(os.path.join(HERE, kind, name + ".json"))
    limits = load(os.path.join(HERE, "limits", CELL + ".json"))
    assert limits["set_from"].startswith("my chip runs, PR 34")


# ---------------------------------------------------------------------------
# The tiny cell through train.run_cell: sound, control, planted faults
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
        chips=cell["chips"], seed=2147499497, seconds=0.3, trace=0,
        out=str(tmp_path), rehearsal=True, control=control)
    result, _ = train.run_cell(opts, break_step=break_step)
    return result


def test_a_sound_run_of_the_tiny_cell_is_correct(tmp_path):
    result = run(tmp_path)
    assert result["correct"], result["checks"]
    assert arch.find(load(os.path.join(
        REHEARSAL, "configs", "tiny_nem.json")), "tiny_nem.json",
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


def test_a_scan_that_does_not_hand_its_state_on_is_not_correct(
        tmp_path, monkeypatch):
    """This cell's own fault: every chunk of the scan starts from a zero
    state (off the TPU the program's scan is the ``jax.numpy`` chunked path,
    whose hand-over is ``carried_states``). The seeded mixer has heads that
    remember past a chunk, so the comparison sees it."""
    import jax.numpy as jnp

    from tony_tpu.ops import ssd

    monkeypatch.setattr(ssd, "carried_states",
                        lambda left, kept: jnp.zeros_like(left))
    result = run(tmp_path)
    assert not result["correct"]
    assert "grad_sample_diff" in sparse.failing(result)
