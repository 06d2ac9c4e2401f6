"""The benchmark's cell runner, walked on the CPU.

``benchmarks/cells/run.py`` is the one yardstick (``BENCHMARK.json``); its
numbers come only from a TPU. What tier-1 can hold is the plumbing a
``tony_tpu/`` change could break before the chip is asked: that each cell of
the rehearsal table, and the tiny cells of the four newest architectures'
own tables, goes through submit → coordinator → executor → the train
script and comes back ``correct`` against the plain reference, without a
metric, and that the runner refuses to give a result where there is no TPU.
The runner is invoked as the driver invokes it: a subprocess, from the root
of the checkout, nothing of ``benchmarks/cells/`` imported here."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RUN = os.path.join("benchmarks", "cells", "run.py")
FIXTURES = os.path.join("benchmarks", "cells", "fixtures")
TABLE = os.path.join(FIXTURES, "rehearsal", "table.json")
# An architecture brings a rehearsal table of its own.
LAGUNA_TABLE = os.path.join(FIXTURES, "rehearsal_laguna", "table.json")
NEMOTRON_TABLE = os.path.join(FIXTURES, "rehearsal_nemotron_h", "table.json")
GRANITE_TABLE = os.path.join(FIXTURES, "rehearsal_granite", "table.json")
KIMI_TABLE = os.path.join(FIXTURES, "rehearsal_kimi_linear", "table.json")
SEED = 3000000391       # no other caller's: the output directory is its own


def _run(workload, *args):
    """The runner's result and the directory it kept its artifacts in."""
    out_dir = os.path.join(REPO, "chiprun_out", "cells",
                           f"{workload}.seed{SEED}.trace0")
    r = subprocess.run(
        [sys.executable, RUN, "--workload", workload, "--seed", str(SEED),
         "--trace", "0", *args],
        cwd=REPO, capture_output=True, text=True, timeout=140)
    return r, out_dir


@pytest.mark.timeout_s(150)
@pytest.mark.parametrize("workload, table", [
    ("tiny.b4", TABLE), ("tiny.b4-4dev", TABLE), ("tiny_tied.b4", TABLE),
    ("tiny_lag.b4", LAGUNA_TABLE), ("tiny_nem.b4", NEMOTRON_TABLE),
    ("tiny_g4h.b4", GRANITE_TABLE), ("tiny_kimi.b4", KIMI_TABLE)])
def test_rehearsal_cell_is_correct_and_reports_no_metric(workload, table):
    r, out_dir = _run(workload, "--seconds", "2", "--rehearsal",
                      "--table", table)
    try:
        assert r.returncode == 0, r.stderr[-3000:]
        line = json.loads(r.stdout.strip().splitlines()[-1])
        assert line["correct"] is True, line["compared"]
        assert line["rehearsal"] is True
        assert line["failed"] == 0 and line["attempted"] > 0
        assert line["metrics"] == {}
        assert line["device"]["platform"] == "cpu"
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


@pytest.mark.timeout_s(150)
def test_default_invocation_fails_without_a_tpu():
    """conftest pins JAX_PLATFORMS=cpu for this process tree; the runner
    hands its job JAX_PLATFORMS=tpu regardless, so the worker dies inside
    jax's TPU initialisation: no result line, no metric under any name."""
    r, out_dir = _run("m7b.seq2k", "--seconds", "40")
    try:
        assert r.returncode != 0
        assert "bench FAILED" in r.stderr
        assert "Unable to initialize backend 'tpu'" in r.stderr
        assert "tokens_per_s_per_chip" not in r.stdout
        assert '"metrics"' not in r.stdout
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
