"""Tests of ``flash_fwd_calls_per_step``. Not collected by ``pytest tests/``;
run

    JAX_PLATFORMS=cpu python3 -m pytest benchmarks/cells/test_calls_per_step.py -q

- the count on a reduced trace whose Mosaic calls carry the program's names
  (``fixtures/named_trace.json``: two ``flash_fwd`` calls and one fusion that
  only looks like one), over one and over two traced steps;
- nothing to read on a trace without the names, on a run without a trace,
  and on a trace that does not say how many steps it holds;
- the reader answers to its entry of ``BENCHMARK.json``.
"""

from __future__ import annotations

import os
import sys

import pytest

sys.path[:0] = [os.path.dirname(os.path.abspath(__file__))]

import run as harness  # noqa: E402
from test_span_metrics import HERE, ROOT, load  # noqa: E402
from test_span_metrics import a_run as a_traced_run  # noqa: E402

NAME = "flash_fwd_calls_per_step"


@pytest.fixture(scope="module")
def reader():
    return {m.NAME: m for m in harness.load_metrics()}[NAME]


def a_run(trace: dict, steps) -> dict:
    """What ``run.drive`` hands the readers, for ``m7b.seq2k``:
    ``train.trace_steps`` adds the number of whole steps it traced."""
    run = a_traced_run(trace, spans={})
    if steps is not None:
        run["worker"]["trace"]["steps"] = steps
    return run


@pytest.fixture(scope="module")
def named_trace():
    return load(os.path.join(HERE, "fixtures", "named_trace.json"))["trace"]


@pytest.mark.parametrize("steps,want", [(1, 2.0), (2, 1.0), (4, 0.5)])
def test_calls_over_the_steps_traced(reader, named_trace, steps, want):
    run = a_run(named_trace, steps)
    assert reader.read(run) == want
    assert reader.note(run) == f"2 calls in {steps} steps"


def test_only_the_forward_kernel_counts(reader, named_trace):
    # dq, dkv and the fusion named flash_dq_like.1 are not the forward's;
    # a forward renamed away leaves nothing to read.
    events = named_trace["planes"][0]["lines"][0]["events"]
    renamed = [[e[0].replace("flash_fwd.", "attn."), e[1], e[2]]
               for e in events]
    other = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": renamed}]}]}
    assert reader.read(a_run(other, 1)) is None


def test_nothing_to_read(reader, named_trace):
    unnamed = load(os.path.join(HERE, "fixtures", "synthetic_trace.json"))
    assert reader.read(a_run(unnamed, 3)) is None
    assert reader.read(a_run(named_trace, None)) is None
    no_trace = a_run(named_trace, 1)
    no_trace["worker"]["trace"] = {}        # a --trace 0 run
    assert reader.read(no_trace) is None


def test_the_reader_answers_to_its_table_entry(reader):
    entry, = [e for e in load(os.path.join(ROOT, "BENCHMARK.json"))[
        "per_layer"] if e["name"] == NAME]
    assert (reader.UNIT, reader.SOURCE, reader.LAYER, reader.MOVES) == (
        entry["unit"], entry["source"], entry["layer"], entry["moves"])
    assert entry["better"] == "lower"
    assert entry["workloads"] == ["m7b.seq2k", "m7b.seq32k"]
