"""Checkpoint/resume contract script: trains with per-step saves and
resumes from ``latest_step()`` after a restart.

Two crash modes (the e2e picks by env):
- default: self-crash (exit 1) after step 2 in retry epoch 0 — the
  deterministic whole-job-retry test;
- ``TONY_TEST_SELF_CRASH=0`` + ``TONY_TEST_STEP_SLEEP``: no self-crash,
  just slow steps — the harness kills the HOST mid-run instead
  (slice-backend preemption e2e).

Writes "start end w1" to TONY_TEST_RESULT so the e2e can assert the
final epoch RESUMED (start > 0) instead of restarting.
"""
import os
import sys
import time

import jax.numpy as jnp

from tony_tpu.checkpoint import CheckpointManager

ckpt_dir = os.environ["TONY_CHECKPOINT_DIR"]
epoch = os.environ.get("SESSION_ID", "0")
total = int(os.environ.get("TONY_TEST_STEPS", "4"))
self_crash = os.environ.get("TONY_TEST_SELF_CRASH", "1") == "1"
step_sleep = float(os.environ.get("TONY_TEST_STEP_SLEEP", "0"))

with CheckpointManager(ckpt_dir, async_save=False) as mgr:
    state = {"step": jnp.zeros((), jnp.int32),
             "w": jnp.arange(4, dtype=jnp.float32)}
    latest = mgr.latest_step()
    if latest is not None:
        state = mgr.restore(latest, state)
    start = int(state["step"])

    for _ in range(start, total):
        state = {"step": state["step"] + 1, "w": state["w"] * 2.0}
        mgr.save(int(state["step"]), state, force=True)
        mgr.wait()
        if self_crash and int(state["step"]) == 2 and epoch == "0":
            print("crashing after step 2 in epoch 0", file=sys.stderr)
            os._exit(1)
        if step_sleep:
            time.sleep(step_sleep)

with open(os.environ["TONY_TEST_RESULT"], "w") as f:
    f.write(f"{start} {int(state['step'])} {float(state['w'][1])}")
