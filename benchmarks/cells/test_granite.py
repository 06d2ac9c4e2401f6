"""Tests of the ``granitemoehybrid`` architecture's files. Not collected by
``pytest tests/``; run

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/cells/test_granite.py -q

- the configuration's file against the catalog row and its own cut: every
  key as published but those ``reduced`` lists, and the parameter sum that
  ``reduced``'s arithmetic states;
- its ``counts.py`` against hand counts at the published widths (parameters
  by kind of layer, operations a token with the scan's products, the one kind
  of flash call at heads of 64, what a scan call needs at one group and
  chunks of 256), loaded without JAX;
- its readers: each ``….g4hm`` is a reader that is there under the cell's
  name, ``ssd_roofline.g4hm`` holds the ``ssd_*`` calls to granite's
  ``ssd_needs``;
- a tiny configuration under a rehearsal table of its own
  (``fixtures/rehearsal_granite``): a sound run is ``correct``; the int8
  control, the two planted faults of every cell and the scan's state not
  handed from chunk to chunk are not (through ``train.run_cell``).
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

ARCH = os.path.join(HERE, "architectures", "granitemoehybrid")
REHEARSAL = os.path.join(HERE, "fixtures", "rehearsal_granite")
CONFIG = "granite-4.0-h-micro"
CELL = "g4hm.seq8k"
LENT = {name + ".g4hm": name + ".nem30b" for name in (
    "mfu", "step_s_p50", "step_s_p95", "step_hbm_gb_per_chip",
    "device_idle_share", "data_wait_share", "orchestrator_s", "user_boot_s",
    "compile_cache_misses", "boot_pre_import_s", "boot_init_state_s",
    "boot_compile_s", "flash_roofline", "ssd_roofline", "ssd_share_of_busy")}
LENT.update({name + ".g4hm": name for name in (
    "ssm_proj_share_of_busy", "ssm_conv_share_of_busy",
    "ssm_gate_norm_share_of_busy", "ssm_scan_xla_share_of_busy",
    "mlp_share_of_busy", "unscoped_share_of_busy")})


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
def g4h():
    return arch.load(ARCH, "counts")


# ---------------------------------------------------------------------------
# The configuration's file and its cut
# ---------------------------------------------------------------------------
def test_the_configuration_finds_its_architecture_and_states_its_cut(cfg):
    path = os.path.join(HERE, "configs", CONFIG + ".json")
    assert arch.find(cfg, path, HERE) == ARCH
    for key in ("source", "published", "reduced", "assumed", "deployment",
                "share", "train"):
        assert cfg[key], key
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entry, = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert set(entry["reduced"]) == set(cfg["reduced"]) - {"arithmetic",
                                                           "total"}
    assert entry["source"] == cfg["source"]
    assert cfg["published"]["layer_types"][:10] == cfg["layer_types"]
    assert len(cfg["layer_types"]) == cfg["num_hidden_layers"] == 10
    assert cfg["layer_types"].count("attention") == 1
    assert cfg["train"]["adamw"]["learning_rate"] == 3e-4


def test_the_catalog_s_numbers_are_the_file_s_but_for_the_cuts(cfg):
    """Every key of the catalog row's ``config`` is in the file under the
    same name with the same value, the keys listed in ``reduced`` apart. The
    catalog of public architectures (one JSON row a model) is the file
    ``MODEL_CATALOG`` names."""
    catalog = os.environ.get("MODEL_CATALOG", "")
    if not os.path.isfile(catalog):
        pytest.skip("MODEL_CATALOG names no catalog file")
    with open(catalog, encoding="utf-8") as f:
        row, = [r for r in map(json.loads, f)
                if r["name"] == "granite-4.0-h-micro"]
    assert row["source_url"] == cfg["source"]
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) - {"arithmetic", "total"}
    assert all(cfg["published"][k] == row["config"][k] for k in differ)


def test_the_parameter_sum_is_the_arithmetic_s(cfg, g4h):
    """``reduced``'s arithmetic, by hand, is ``total_params``."""
    total = cfg["reduced"]["total"]
    assert "772,160,448" in total
    assert g4h.total_params(cfg) == 772160448
    mamba, attention = 76182976, 60821504
    assert "76,182,976" in cfg["reduced"]["arithmetic"]
    assert "60,821,504" in cfg["reduced"]["arithmetic"]
    assert 9 * mamba + attention == 746468288
    assert 746468288 + 12544 * 2048 + 2048 == 772160448
    # the whole vocabulary, the cut the arithmetic rules out
    assert g4h.total_params(dict(cfg, vocab_size=100352)) == 951991232


def test_parameters_against_a_hand_count(cfg, g4h):
    # wz [2048, 4096] + wxbc [2048, 4352] + wdt [2048, 64] + wo [4096, 2048]
    # + conv taps 4 x 4352 and bias 4352 + A_log, D, dt_bias + gated norm +
    # the mixer's norm
    assert g4h.mamba_params(cfg) == 8388608 + 8912896 + 131072 + 8388608 \
        + 17408 + 4352 + 192 + 4096 + 2048 == 25849280
    assert g4h.attention_matmul_params(cfg) == 2 * 4194304 + 2 * 1048576
    assert g4h.mlp_params(cfg) == 3 * 2048 * 8192 == 50331648
    assert g4h.mamba_params(cfg) + g4h.mlp_params(cfg) + 2048 == 76182976
    assert g4h.head_dim(cfg) == 64


def test_operations_a_token_against_a_hand_count(cfg, g4h):
    # the scan's forward products a token and layer at one group and chunks
    # of 256: C B^T once, M X a head, the state left and the state read out
    scan = 2 * 256 * 128 * 1 + 2 * 256 * 64 * 64 + 4 * 128 * 64 * 64
    assert g4h.scan_flops_a_token(cfg) == {
        "fwd": scan, "bwd": 6 * 256 * 128 + 4 * 256 * 64 * 64
        + 10 * 128 * 64 * 64}
    assert scan == 4259840
    weights = 9 * (25821184 + 4 * 4352) + 10485760 + 10 * 50331648 \
        + 12544 * 2048
    assert weights == 772039680
    pairs = 2 * 2 * 32 * 64 * (8192 * 8192 / 2) / 8192
    assert g4h.model_flops_per_token(cfg, 8192) == \
        3 * (2 * weights + pairs + 9 * scan) == 4847917056


def test_flash_calls_are_one_kind_at_heads_of_64(cfg, traffic, g4h):
    assert g4h.flash_calls(cfg, traffic) == [
        ((2, 32, 8, 8192, 64), {"window": None}, 1)]


def test_scan_needs_against_a_hand_count(cfg, traffic, g4h):
    needs = g4h.ssd_needs(cfg, traffic)
    assert needs["flops_a_call"] == {"fwd": 16384 * 4259840,
                                     "bwd": 16384 * 9633792}
    # a token: x, y 8,192 B each, B, C 256 B each, two decay vectors 256 B
    # each, the entering state 4 * 128 * 4096 / 256 = 8,192 B
    assert needs["bytes_a_call"] == {
        "fwd": 16384 * (16384 + 512 + 512 + 8192),
        "bwd": 16384 * (24576 + 1024 + 1024 + 8192)}
    peak = counts.peaks("TPU v5 lite")
    # at chunks of 256 the backward's operations bind, the forward's bytes
    assert g4h.ssd_call_min_seconds("fwd", needs, peak) == (
        16384 * 25600 / 819e9, "bytes")
    assert g4h.ssd_call_min_seconds("bwd", needs, peak) == (
        16384 * 9633792 / 197e12, "flops")


def test_the_parent_loads_the_counts_without_jax():
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import arch, run; "
            "c = arch.load(sys.argv[2], 'counts'); "
            "assert callable(c.total_params) and callable(c.flash_calls) "
            "and callable(c.model_flops_per_token) and callable(c.ssd_needs); "
            "bad = [m for m in sys.modules if m == 'jax' or "
            "m.startswith(('jax.', 'tony_tpu'))]; assert not bad, bad")
    done = subprocess.run([sys.executable, "-c", code, HERE, ARCH],
                          capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ARCH, "reference.py"), encoding="utf-8") as f:
        text = f.read()
    assert "tony_tpu" not in text and "pallas" not in text


# ---------------------------------------------------------------------------
# The readers
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def readers():
    return {m.NAME: m for m in harness.load_metrics()}


def test_the_scan_s_roofline_holds_the_calls_to_granite_s_needs(
        cfg, traffic, g4h, readers):
    peak = counts.peaks("TPU v5 lite")
    needs = g4h.ssd_needs(cfg, traffic)
    fwd = g4h.ssd_call_min_seconds("fwd", needs, peak)[0]
    bwd = g4h.ssd_call_min_seconds("bwd", needs, peak)[0]
    ops = {"ssd_fwd.3 tpu_custom_call (bf16[2], f32[2]) operands=6":
           [18.0, 18 * fwd / 0.5],
           "ssd_bwd.1 tpu_custom_call (bf16[2], f32[2]) operands=8":
           [9.0, 9 * bwd / 0.25],
           "fusion.1 fusion f32[8]": [1.0, 0.3]}
    run = {"worker": {"trace": {"ops": ops, "busy_s": 0.9,
                                "window_s": 0.9 / 0.999, "steps": 1},
                      "device": {"kind": "TPU v5 lite", "count": 1}},
           "architecture": ARCH, "config": cfg, "traffic": traffic}
    took = 18 * fwd / 0.5 + 9 * bwd / 0.25
    assert readers["ssd_roofline.g4hm"].read(run) == pytest.approx(
        100 * (18 * fwd + 9 * bwd) / took)
    assert readers["ssd_share_of_busy.g4hm"].read(run) == pytest.approx(
        100 * took / 0.9)


@pytest.mark.parametrize("mine, there", sorted(LENT.items()))
def test_a_lent_reader_is_the_reader_there(mine, there, readers):
    """``<name>.g4hm`` is the reader and the table entry of the metric it
    borrows but for the name and the cell."""
    lent, theirs = readers[mine], readers[there]
    assert lent.read.__code__ == theirs.read.__code__
    assert (lent.UNIT, lent.SOURCE, lent.LAYER, lent.MOVES) == (
        theirs.UNIT, theirs.SOURCE, theirs.LAYER, theirs.MOVES)
    assert hasattr(lent, "note") == hasattr(theirs, "note")
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    a, b = ({k: v for k, v in e.items() if k not in ("name", "workloads")}
            for e in bench["per_layer"] if e["name"] in (mine, there))
    assert a == b


def test_every_new_entry_has_its_reader_and_its_cell():
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [e for e in bench["per_layer"] if e.get("workloads") == [CELL]]
    assert {e["name"] for e in mine} == set(LENT)
    first = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][first:first + len(LENT)] == mine
    cell, config = harness.find_cell(bench, CELL)
    assert cell["chips"] == 1 and config["name"] == CONFIG
    assert cell["traffic"] == "seq8k-2rows"
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert os.path.isfile(os.path.join(HERE, "limits", CELL + ".json"))


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
        chips=cell["chips"], seed=3000000391, seconds=0.3, trace=0,
        out=str(tmp_path), rehearsal=True, control=control)
    result, _ = train.run_cell(opts, break_step=break_step)
    return result


def test_a_sound_run_of_the_tiny_cell_is_correct(tmp_path):
    result = run(tmp_path)
    assert result["correct"], result["checks"]


def test_the_int8_control_is_not_correct(tmp_path):
    result = run(tmp_path, control="int8")
    assert not result["correct"]
    assert "grad_sample_diff" in sparse.failing(result)


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
    """Every chunk of the scan starts from a zero state (off the TPU the
    program's scan is the ``jax.numpy`` chunked path, whose hand-over is
    ``carried_states``)."""
    import jax.numpy as jnp

    from tony_tpu.ops import ssd

    monkeypatch.setattr(ssd, "carried_states",
                        lambda left, kept: jnp.zeros_like(left))
    result = run(tmp_path)
    assert not result["correct"]
