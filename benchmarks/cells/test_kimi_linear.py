"""Tests of the ``kimi_linear`` architecture's files. Not collected by
``pytest tests/``; run

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/cells/test_kimi_linear.py -q

- the configuration's file against the catalog row and its own cut: every
  key as published but those ``reduced`` lists, and the parameter sum that
  ``reduced``'s arithmetic states;
- its ``counts.py`` against hand counts at the published widths (parameters
  by kind of layer, operations a token with the KDA scan's products and MLA's
  attention at q/k 192 and v 128, what a scan call and a flash call need),
  loaded without JAX;
- its readers: each entry there that this cell shares lists it last;
  ``kda_roofline.kimiL``, ``kda_share_of_busy.kimiL``,
  ``flash_roofline.kimiL`` and the per-kernel ``flash_<kind>_roofline.kimiL``
  on a synthetic trace; the scope readers on a
  synthetic map;
- a tiny configuration under a rehearsal table of its own
  (``fixtures/rehearsal_kimi_linear``): a sound run is ``correct``; the int8
  control and the two planted faults of every cell are not (through
  ``train.run_cell``).
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

ARCH = os.path.join(HERE, "architectures", "kimi_linear")
REHEARSAL = os.path.join(HERE, "fixtures", "rehearsal_kimi_linear")
CONFIG = "kimi-linear-48b-a3b"
CELL = "kimiL.seq32k"
# Entries there that read this cell too: the cell is appended to their
# ``workloads`` (the table holds at most 128 per-layer entries, so a reader
# lent under a second name is one entry too many).
SHARED = ("mfu", "step_s_p50", "step_s_p95", "step_hbm_gb_per_chip",
          "device_idle_share", "data_wait_share", "orchestrator_s",
          "user_boot_s", "compile_cache_misses", "boot_pre_import_s",
          "boot_init_state_s", "boot_compile_s", "boot_compile_trace_s",
          "boot_compile_lower_s", "boot_compile_backend_s",
          "moe_gmm_roofline", "moe_gmm_share_of_busy",
          "moe_route_share_of_busy", "moe_dispatch_share_of_busy",
          "moe_combine_share_of_busy", "moe_experts_xla_share_of_busy",
          "backward_share_of_busy", "recompute_share_of_busy",
          "optimizer_share_of_busy", "loss_head_share_of_busy",
          "mlp_share_of_busy", "unscoped_share_of_busy",
          "attn_proj_share_of_busy", "flash_fwd_calls_per_step")
SCOPES = {"kda_proj_share_of_busy.kimiL": ("tony.kda.in_proj",
                                           "tony.kda.out_proj"),
          "kda_conv_share_of_busy.kimiL": ("tony.kda.conv",),
          "kda_out_norm_share_of_busy.kimiL": ("tony.kda.out_norm",),
          "kda_scan_xla_share_of_busy.kimiL": ("tony.kda.scan",)}
FLASH_KINDS = ("fwd", "dq", "dkv")
NEW = {"kda_roofline.kimiL", "kda_share_of_busy.kimiL",
       "flash_roofline.kimiL"} | set(SCOPES) | {
           f"flash_{kind}_roofline.kimiL" for kind in FLASH_KINDS}


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return load(os.path.join(HERE, "configs", CONFIG + ".json"))


@pytest.fixture(scope="module")
def traffic():
    return load(os.path.join(HERE, "traffic", "seq32k.json"))


@pytest.fixture(scope="module")
def kimi():
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
    lin, published = cfg["linear_attn_config"], \
        cfg["published"]["linear_attn_config"]
    assert lin["kda_layers"] == [i for i in published["kda_layers"] if i <= 5]
    assert lin["full_attn_layers"] == [
        i for i in published["full_attn_layers"] if i <= 5]
    assert {k: v for k, v in lin.items() if not k.endswith("_layers")} == {
        k: v for k, v in published.items() if not k.endswith("_layers")}
    assert cfg["share"]["chips_sharing_a_layer"] == 32
    assert cfg["num_experts"] * 32 == cfg["published"]["num_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]


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
                if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    assert row["source_url"] == cfg["source"]
    differ = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"]) - {"arithmetic", "total"}
    assert all(cfg["published"][k] == row["config"][k] for k in differ)


def test_the_parameter_sum_is_the_arithmetic_s(cfg, kimi):
    """``reduced``'s arithmetic, by hand, is ``total_params``."""
    assert "602,433,408" in cfg["reduced"]["total"]
    kda, mla, side, expert = 39514272, 29114880, 589824 + 7077888, 7077888
    for n in ("39,514,272", "29,114,880", "7,077,888", "63,700,992"):
        assert n in cfg["reduced"]["arithmetic"], n
    dense, norms = 63700992, 2 * 2304
    layer_1 = kda + dense + norms
    kda_sparse = kda + side + 8 * expert + norms
    mla_sparse = mla + side + 8 * expert + norms
    assert (layer_1, kda_sparse, mla_sparse) == (103219872, 103809696,
                                                 93410304)
    total = layer_1 + 3 * kda_sparse + mla_sparse + 2 * 20480 * 2304 + 2304
    assert total == kimi.total_params(cfg) == 602433408
    # the cuts the arithmetic rules out: 16 experts held, the whole
    # vocabulary
    assert kimi.total_params(dict(cfg, num_experts=16)) == 828925824
    assert kimi.total_params(dict(cfg, vocab_size=163840)) \
        == 602433408 + 2 * 2304 * (163840 - 20480)


def test_parameters_by_part_against_a_hand_count(cfg, kimi):
    # W_q, W_k, W_v, W_o 4 x 2304 x 4096; f and g pairs 2 x (2304 x 128 +
    # 128 x 4096); W_b 2304 x 32; conv taps 3 x 4 x 4096; dt_bias 4096,
    # A_log 32, the output norm 128
    assert kimi.kda_matmul_params(cfg) == 4 * 2304 * 4096 \
        + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 == 39460864
    assert kimi.kda_params(cfg) == 39460864 + 49152 + 4096 + 32 + 128
    # W_q 2304 x 6144 + W_kv_a 2304 x 576 + W_kv_b 512 x 8192 + W_o
    # 4096 x 2304, and the latent's norm
    assert kimi.mla_matmul_params(cfg) == 14155776 + 1327104 + 4194304 \
        + 9437184
    assert kimi.mla_params(cfg) == 29114880
    assert kimi.kinds(cfg) == ["kda", "kda", "kda", "mla", "kda"]
    assert kimi.experts_a_token_here(cfg) == 8 * 8 / 256


def test_operations_a_token_against_a_hand_count(cfg, kimi):
    c, k = 64, 128
    fwd = 2 * c * k + 6 * k * k + 2 * c * k + c * c / 3
    assert kimi.scan_flops_a_token(cfg) == {
        "fwd": fwd, "bwd": 6 * c * k + 14 * k * k + 5 * c * k + c * c / 3}
    weights = 4 * (39460864 + 49152) + 29114368 + 63700992 \
        + 4 * (589824 + 7077888 + 0.25 * 7077888) + 2304 * 20480
    assert weights == 335790080
    attention = 2 * 32 * (192 + 128) * (32768 * 32768 / 2) / 32768
    assert kimi.model_flops_per_token(cfg, 32768) == pytest.approx(
        3 * (2 * weights + attention + 4 * 32 * fwd))
    assert round(kimi.model_flops_per_token(cfg, 32768)) == 3072229376


def test_the_flash_calls_at_qk_192_and_v_128(cfg, traffic, kimi):
    assert kimi.flash_calls(cfg, traffic) == [
        ((1, 32, 32, 32768, 192), {"window": None}, 1)]
    needs = kimi.mla_flash_needs(cfg, traffic)
    pairs = 32768 * 32768 / 2
    assert needs["flops_a_call"] == {
        "fwd": 2 * 32 * pairs * (192 + 128),
        "dq": 2 * 32 * pairs * (2 * 192 + 128),
        "dkv": 2 * 32 * pairs * (2 * 192 + 2 * 128)}
    q, v, stat = 2 * 32 * 32768 * 192, 2 * 32 * 32768 * 128, 4 * 32 * 32768
    assert needs["bytes_a_call"] == {"fwd": 2 * q + 2 * v + stat,
                                     "dq": 3 * q + 2 * v + 2 * stat,
                                     "dkv": 3 * q + 3 * v + 2 * stat}
    peak = counts.peaks("TPU v5 lite")
    # the harness's one-width count holds v to 192 columns: 1.2x the fwd's
    one_width = counts.flash_call_flops("fwd", needs["shape"][:5])
    assert one_width / needs["flops_a_call"]["fwd"] == pytest.approx(1.2)
    for kind in ("fwd", "dq", "dkv"):
        assert kimi.mla_call_min_seconds(kind, needs, peak) == (
            needs["flops_a_call"][kind] / 197e12, "flops")


def test_scan_needs_against_a_hand_count(cfg, traffic, kimi):
    needs = kimi.kda_needs(cfg, traffic)
    tokens, heads = 32768, 32
    flops = kimi.scan_flops_a_token(cfg)
    assert needs["flops_a_call"] == {
        "fwd": tokens * heads * flops["fwd"],
        "bwd": tokens * heads * flops["bwd"]}
    # a token and head: q, k, v in bf16 768 B, x (float32, K) 512 B, β 4 B,
    # o or dO 256 B, the entering state 4 x 128 x 128 / 64 = 1,024 B; the
    # backward writes dq, dk, dv, dx and dβ besides
    assert needs["bytes_a_call"] == {
        "fwd": tokens * heads * (768 + 512 + 4 + 256 + 1024),
        "bwd": tokens * heads * (1536 + 1024 + 8 + 256 + 1024)}
    peak = counts.peaks("TPU v5 lite")
    # the states' bytes bind both calls
    assert kimi.kda_call_min_seconds("fwd", needs, peak) == (
        tokens * heads * 2564 / 819e9, "bytes")
    assert kimi.kda_call_min_seconds("bwd", needs, peak) == (
        tokens * heads * 3848 / 819e9, "bytes")


def test_the_expert_calls_see_1024_rows_a_layer(cfg, traffic, kimi):
    needs = kimi.moe_gmm_needs(cfg, traffic)
    # 32,768 tokens x 8 choices x 8 / 256 held: 1,024 rows an expert
    assert needs["chunks_a_layer"] * needs["rows_a_call"] \
        / cfg["num_experts"] == 32768 * 8 / 256 == 1024
    assert needs["shape"][1:] == (2304, 1024)


def test_the_parent_loads_the_counts_without_jax():
    code = ("import sys; sys.path.insert(0, sys.argv[1]); import arch, run; "
            "c = arch.load(sys.argv[2], 'counts'); "
            "assert callable(c.total_params) and callable(c.flash_calls) "
            "and callable(c.model_flops_per_token) and callable(c.kda_needs)"
            "; bad = [m for m in sys.modules if m == 'jax' or "
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


def _run(cfg, traffic, ops, busy, scopes=None):
    return {"worker": {"trace": {"ops": ops, "busy_s": busy,
                                 "window_s": busy / 0.999, "steps": 1},
                       "device": {"kind": "TPU v5 lite", "count": 1}},
            "spans": {"step_scopes": {"scopes": scopes or {}}},
            "architecture": ARCH, "config": cfg, "traffic": traffic}


def test_the_scan_s_roofline_holds_the_calls_to_kimi_s_needs(
        cfg, traffic, kimi, readers):
    peak = counts.peaks("TPU v5 lite")
    needs = kimi.kda_needs(cfg, traffic)
    fwd = kimi.kda_call_min_seconds("fwd", needs, peak)[0]
    bwd = kimi.kda_call_min_seconds("bwd", needs, peak)[0]
    ops = {"kda_fwd.3 tpu_custom_call (bf16[2], f32[2]) operands=6":
           [8.0, 8 * fwd / 0.2],
           "kda_bwd.1 tpu_custom_call (bf16[2], f32[2]) operands=8":
           [4.0, 4 * bwd / 0.1],
           "fusion.1 fusion f32[8]": [1.0, 0.3]}
    run = _run(cfg, traffic, ops, 1.5)
    took = 8 * fwd / 0.2 + 4 * bwd / 0.1
    assert readers["kda_roofline.kimiL"].read(run) == pytest.approx(
        100 * (8 * fwd + 4 * bwd) / took)
    assert readers["kda_share_of_busy.kimiL"].read(run) == pytest.approx(
        100 * took / 1.5)
    # a program without the kernels: nothing to read
    bare = _run(cfg, traffic, {"fusion.1 fusion f32[8]": [1.0, 0.3]}, 0.3)
    assert readers["kda_roofline.kimiL"].read(bare) is None
    assert readers["kda_share_of_busy.kimiL"].read(bare) is None


def test_the_flash_roofline_holds_the_calls_to_mla_s_widths(
        cfg, traffic, kimi, readers):
    peak = counts.peaks("TPU v5 lite")
    needs = kimi.mla_flash_needs(cfg, traffic)
    least = {k: kimi.mla_call_min_seconds(k, needs, peak)[0]
             for k in ("fwd", "dq", "dkv")}
    ops = {"flash_fwd.2 tpu_custom_call (bf16[2], f32[2]) operands=3":
           [1.0, least["fwd"] / 0.6],
           "flash_dq.1 tpu_custom_call bf16[2] operands=6":
           [1.0, least["dq"] / 0.5],
           "flash_dkv.4 tpu_custom_call (bf16[2], bf16[2]) operands=6":
           [1.0, least["dkv"] / 0.4]}
    took = sum(v[1] for v in ops.values())
    run = _run(cfg, traffic, ops, 2.0)
    assert readers["flash_roofline.kimiL"].read(run) == pytest.approx(
        100 * sum(least.values()) / took)
    for kind, share in zip(FLASH_KINDS, (60.0, 50.0, 40.0)):
        assert readers[f"flash_{kind}_roofline.kimiL"].read(
            run) == pytest.approx(share)
    # a program without latent attention's counts: nothing to read
    bare = dict(run, architecture=os.path.join(HERE, "architectures",
                                               "mistral"))
    for name in ["flash_roofline.kimiL"] + [
            f"flash_{kind}_roofline.kimiL" for kind in FLASH_KINDS]:
        assert readers[name].read(bare) is None


@pytest.mark.parametrize("name", sorted(SCOPES))
def test_a_scope_reader_reads_its_scopes(name, cfg, traffic, readers):
    scope = SCOPES[name][0]
    ops = {"fusion.1 fusion f32[8]": [2.0, 0.2],
           "kda_fwd.1 tpu_custom_call f32[8]": [1.0, 0.1],
           "fusion.2 fusion f32[8]": [1.0, 0.7]}
    scopes = {f"forward/{scope}": "fusion.1 kda_fwd.1",
              "backward/tony.mlp": "fusion.2"}
    got = readers[name].read(_run(cfg, traffic, ops, 1.0, scopes))
    mosaic = "scan_xla" not in name
    assert got == pytest.approx(30.0 if mosaic else 20.0)


@pytest.mark.parametrize("name", SHARED)
def test_a_shared_entry_lists_the_cell_last(name, readers):
    """The entry there reads this cell too: the cell is the last of its
    ``workloads``, after the cells it had, and its reader is there."""
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entry, = [e for e in bench["per_layer"] if e["name"] == name]
    assert entry["workloads"][-1] == CELL
    assert CELL not in entry["workloads"][:-1] and entry["workloads"][:-1]
    assert callable(readers[name].read)


def test_every_new_entry_has_its_reader_and_its_cell(readers):
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    assert len(bench["per_layer"]) <= 128
    assert not [n for n in readers if n.endswith(".kimiL") and n not in NEW]
    mine = [e for e in bench["per_layer"] if e.get("workloads") == [CELL]]
    assert {e["name"] for e in mine} == NEW
    assert bench["per_layer"][-len(mine):] == mine
    for e in mine:
        module = readers[e["name"]]
        assert (module.UNIT, module.SOURCE, module.LAYER, module.MOVES) == (
            e["unit"], e["source"], e["layer"], e["moves"])
    cell, config = harness.find_cell(bench, CELL)
    assert bench["workloads"][-1] == {k: v for k, v in cell.items()
                                      if k != "base"}
    assert cell["chips"] == 1 and config["name"] == CONFIG
    assert cell["traffic"] == "seq32k"
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


@pytest.mark.parametrize("fault, caught_by", [
    (sparse.unchanged_state, "change_norm_gap"),
    (sparse.half_batch, "grad_norm_gap")],
    ids=["state_unchanged", "half_batch"])
def test_a_planted_fault_is_not_correct(tmp_path, fault, caught_by):
    result = run(tmp_path, break_step=fault)
    assert not result["correct"]
    assert caught_by in sparse.failing(result)
