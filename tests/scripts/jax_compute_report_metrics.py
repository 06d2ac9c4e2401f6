"""Imports tony_tpu (auto-starting the telemetry reporter), brings up jax,
runs a computation, and makes sure one stats snapshot is on disk before
exiting — the TASK_FINISHED metrics must then carry user-process device
stats."""
import os

import jax.numpy as jnp

import tony_tpu  # noqa: F401  (starts the reporter: TONY_METRICS_FILE is set)
from tony_tpu import telemetry

x = jnp.ones((64, 64))
# Step-timed compute: the utilization signal (steps/s, duty cycle, model
# FLOP/s) that TASK_FINISHED metrics must carry end-to-end.
for _ in range(3):
    with telemetry.step(flops=2 * 64 ** 3, tokens=64):
        y = (x @ x).sum()
        y.block_until_ready()

# Deterministic final snapshot (the 3 s reporter cadence may not have fired
# for a task this short).
assert telemetry.write_stats_once(os.environ["TONY_METRICS_FILE"])
