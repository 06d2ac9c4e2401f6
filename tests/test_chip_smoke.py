"""chip_smoke.py's plumbing, without a chip.

The smoke itself proves something only on a TPU (run it through the chip
tool). What tier-1 can hold is everything around the device: that the
explicit CPU rehearsal walks submit → coordinator → executor → worker →
artifact checks end to end, and that the DEFAULT invocation refuses to
pass when there is no TPU — no quiet CPU run, no result on stdout."""

import json
import os
import re
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args):
    return subprocess.run([sys.executable, SMOKE, *args], cwd=REPO,
                          capture_output=True, text=True, timeout=600)


def test_cpu_rehearsal_walks_the_whole_path():
    r = _run("--cpu-rehearsal")
    assert r.returncode == 0, r.stderr[-3000:]
    detail, verdict = (json.loads(line)
                       for line in r.stdout.strip().splitlines()[-2:])
    out_dir = os.path.join(REPO, detail["artifacts"])
    try:
        # It says what it is — in both lines — and is no chip run.
        assert verdict == {"ok": True, "rehearsal": True,
                           "device": {"platform": "cpu", "kind": "cpu",
                                      "count": 4}}
        assert "REHEARSAL" in detail["what"] and detail["interpret"]
        # The four-device branch: fsdp x tp mesh, the library step, six
        # telemetry-wrapped steps on one batch, loss falling from ln V + ½.
        assert detail["mesh"] == {"fsdp": 2, "tp": 2}
        assert len(detail["losses"]) == 6
        assert detail["losses"][-1] < detail["losses"][0]
        assert 0.25 < detail["loss0_minus_ln_vocab"] < 0.75
        assert set(detail["kernel_check"]["rel_err"]) == {"o", "dq", "dk",
                                                          "dv"}
        # Judged from the job's own artifacts.
        assert detail["user_metrics"]["device_count"] == 4
        assert detail["user_metrics"]["steps_completed"] == 6
        assert detail["submit_to_first_step_s"] > 0
        assert detail["invariants"][0].endswith(": OK")
        kept = os.listdir(out_dir)
        assert any(n.endswith("-SUCCEEDED.jhist.jsonl") for n in kept)
        for name in ("chip_smoke.json", "coordinator.log",
                     "worker.stdout.log", "worker.stderr.log",
                     "trace.spans.jsonl", "user-metrics.json"):
            assert name in kept, kept
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)


def test_default_invocation_fails_without_a_tpu():
    """conftest pins JAX_PLATFORMS=cpu for this process tree; the smoke
    hands its job JAX_PLATFORMS=tpu regardless, so the worker dies inside
    jax's TPU initialisation instead of quietly training on the CPU."""
    r = _run()
    m = re.search(r"artifacts kept in (\S+)", r.stderr)
    try:
        assert r.returncode == 1
        assert r.stdout == ""           # no result, not even a partial one
        assert "chip_smoke FAILED" in r.stderr
        assert "Unable to initialize backend 'tpu'" in r.stderr
    finally:
        if m:
            shutil.rmtree(m.group(1), ignore_errors=True)
