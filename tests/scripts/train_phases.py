"""Phase-attribution drill script: a jax training loop fed through the
REAL ``ShardedBatchIterator`` (so ``data_wait`` comes from the
production data.py wiring, not a hand-rolled timer), with
``step_compute`` block_until_ready-anchored. ``TONY_TEST_DATA_STALL_S``
injects a per-step input stall (the INPUT_BOUND acceptance shape);
``TONY_TEST_STEPS`` bounds the run. Single-process jax per task — the
gang rendezvous is the coordinator's, not jax.distributed's."""
import os
import time

import tony_tpu  # noqa: F401  (starts the reporter + arms TONY_FAULTS)
from tony_tpu import telemetry

import numpy as np

from tony_tpu.data import ShardedBatchIterator  # noqa: E402
from tony_tpu.parallel import MeshSpec, build_mesh  # noqa: E402

mesh = build_mesh(MeshSpec())
stall = float(os.environ.get("TONY_TEST_DATA_STALL_S", "0") or 0)


def load_local(step, rows):
    if stall:
        time.sleep(stall)
    return {"x": np.full((rows.stop - rows.start, 4), float(step),
                         np.float32)}


# prefetch=0: the synchronous assemble (including the injected stall) is
# the consumer-side data_wait — deterministic attribution for the drill.
it = ShardedBatchIterator(mesh=mesh, global_batch=8,
                          load_local=load_local, prefetch=0)

steps = int(os.environ.get("TONY_TEST_STEPS", "200"))
for _ in range(steps):
    batch = next(it)
    with telemetry.step():
        with telemetry.phase("step_compute") as p:
            y = (batch["x"] * 2.0).sum()
            p.block_until_ready(y)
it.close()
# One final synchronous telemetry write so the last phase totals (and a
# just-finished capture result) reach the beacon even on a fast exit.
telemetry.write_stats_once(os.environ.get("TONY_METRICS_FILE", ""))
