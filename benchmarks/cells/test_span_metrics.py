"""Tests of the per-layer metrics that read what the program names and
records itself. Not collected by ``pytest tests/``; run

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/cells/test_span_metrics.py -q

- the three per-kernel rooflines against a hand count on a reduced trace
  whose Mosaic calls carry the program's names (``fixtures/named_trace.json``),
  and their combination against ``flash_roofline``'s own sums there;
- nothing to read on a trace without the names (``synthetic_trace.json``, the
  shape of a trace of a program that names no kernel);
- the three boot readers on a recorded ``cold_start_breakdown`` with and
  without ``user_boot``;
- every reader answers to one entry of ``BENCHMARK.json``.
"""

from __future__ import annotations

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE]

import run as harness  # noqa: E402
import trace_reduce  # noqa: E402

KERNELS = ("fwd", "dq", "dkv")


def load(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def readers():
    return {m.NAME: m for m in harness.load_metrics()}


def a_run(trace: dict, spans: dict) -> dict:
    """What ``run.drive`` hands the readers, for ``m7b.seq2k``."""
    return {"worker": {"trace": trace_reduce.reduce_trace(trace),
                       "device": {"kind": "TPU v5 lite"}},
            "spans": spans,
            "architecture": os.path.join(HERE, "architectures", "mistral"),
            "config": load(os.path.join(HERE, "configs",
                                        "mistral-7b-v0.3.json")),
            "traffic": load(os.path.join(HERE, "traffic", "seq2k.json"))}


@pytest.fixture(scope="module")
def named():
    fixture = load(os.path.join(HERE, "fixtures", "named_trace.json"))
    return a_run(fixture["trace"], fixture["spans"])


@pytest.fixture(scope="module")
def unnamed():
    spans = {"total_s": 21.5, "task": "worker:0",
             "phases": {"launch": 0.95, "user_boot": 20.55},
             "span_durations": {}}
    return a_run(load(os.path.join(HERE, "fixtures",
                                   "synthetic_trace.json")), spans)


def test_kernel_rooflines_against_a_hand_count(readers, named):
    # seq2k's call is (8, 32, 8, 2048, 128): the forward needs
    # 2 matmuls * 2 * 8*32*2048*2048*128 / 2 = 274,877,906,944 flops, dq 1.5
    # times and dkv twice that; flops bind, at 197e12 a second.
    fwd_s = 274877906944 / 197e12
    want = {"fwd": 2 * fwd_s / 6.2e-3,       # two calls: 3.0 ms + 3.2 ms
            "dq": 1.5 * fwd_s / 4.0e-3,
            "dkv": 2 * fwd_s / 5.0e-3}
    for kind in KERNELS:
        reader = readers[f"flash_{kind}_roofline"]
        assert reader.read(named) == pytest.approx(100 * want[kind])
        assert 0 < reader.read(named) < 100
        assert "binding bound flops" in reader.note(named)
    assert readers["flash_fwd_roofline"].note(named).startswith("2 calls")
    # The fusion named flash_dq_like.1 is no Mosaic call: not dq's.
    assert readers["flash_dq_roofline"].note(named).startswith("1 calls")


def test_the_three_combine_to_flash_roofline(readers, named):
    # flash_roofline selects the same calls by operands and results; the
    # time-weighted combination of the three shares is its share.
    import kernel_roofline

    least = took = 0.0
    for kind in KERNELS:
        l, t, _, _ = kernel_roofline.sums(named, kind, f"flash_{kind}")
        assert readers[f"flash_{kind}_roofline"].read(named) \
            == pytest.approx(100 * l / t)
        least, took = least + l, took + t
    assert took == pytest.approx(15.2e-3)
    assert readers["flash_roofline"].read(named) == pytest.approx(
        100 * least / took)


def test_nothing_to_read_without_the_names(readers, unnamed):
    # The trace of a program that names no kernel (attn.1): the operand
    # count still finds the forward, the names find nothing, and a metric
    # with nothing to read is left out, never 0.
    assert readers["flash_roofline"].read(unnamed) is not None
    for kind in KERNELS:
        assert readers[f"flash_{kind}_roofline"].read(unnamed) is None
    no_trace = dict(unnamed, worker={"trace": {},
                                     "device": {"kind": "TPU v5 lite"}})
    for kind in KERNELS:
        assert readers[f"flash_{kind}_roofline"].read(no_trace) is None


def test_boot_readers_with_and_without_user_boot(readers, named, unnamed):
    assert readers["boot_pre_import_s"].read(named) == 11.8
    assert readers["boot_init_state_s"].read(named) == 0.45
    assert readers["boot_compile_s"].read(named) == 4.2
    boot = named["spans"]["user_boot"]
    assert sum(boot.values()) == pytest.approx(
        named["spans"]["phases"]["user_boot"])
    for name in ("boot_pre_import_s", "boot_init_state_s", "boot_compile_s"):
        assert readers[name].read(unnamed) is None
    # A job whose script let build_mesh find the devices has the span the
    # cells cannot show, and may lack another: each reader stands alone.
    partial = dict(named, spans=dict(named["spans"], user_boot={
        "user.backend_init": 7.0, "user.compile": 3.0,
        "unattributed": 10.55}))
    assert readers["boot_compile_s"].read(partial) == 3.0
    assert readers["boot_pre_import_s"].read(partial) is None


def test_every_new_reader_answers_to_its_table_entry(readers):
    bench = load(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {e["name"]: e for e in bench["per_layer"]}
    new = [f"flash_{k}_roofline" for k in KERNELS] + [
        "boot_pre_import_s", "boot_init_state_s", "boot_compile_s"]
    for name in new:
        entry, reader = entries[name], readers[name]
        assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
            entry["unit"], entry["source"], entry["layer"], entry["moves"])
        assert entry["workloads"] == ["m7b.seq2k", "m7b.seq32k"]
