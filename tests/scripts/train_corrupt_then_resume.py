"""Fault-matrix script: preemption mid-epoch WITH a torn newest checkpoint.

Epoch 0 (SESSION_ID=0): saves steps 0..2, then corrupts step 2 on disk
(truncates every manifest-listed file — the torn-write shape a dying host
leaves behind) and exits 143, the preemption exit (128+SIGTERM — what a
save-on-notice handler exits with).

Epoch 1+: restores; the integrity layer must REJECT the corrupt step 2
and fall back to verified step 1. Writes "<restored_step> <end_step>" to
TONY_TEST_RESULT, finishes the remaining steps, exits 0.
"""
import json
import os
import sys

import jax.numpy as jnp

from tony_tpu.checkpoint import CheckpointManager

ckpt_dir = os.environ["TONY_CHECKPOINT_DIR"]
epoch = int(os.environ.get("SESSION_ID", "0"))
result = os.environ["TONY_TEST_RESULT"]
TOTAL = 4

mgr = CheckpointManager(ckpt_dir, async_save=False, max_to_keep=10)
like = {"s": jnp.zeros((), jnp.int32)}

if epoch == 0:
    for step in range(3):                    # steps 0, 1, 2
        mgr.save(step, {"s": jnp.int32(step)}, force=True)
    mgr.wait()                               # manifests durable
    # Tear the newest step: truncate every file its manifest lists.
    with open(mgr.manifest_path(2), encoding="utf-8") as f:
        manifest = json.load(f)
    root = os.path.join(ckpt_dir, "2")
    for rel in manifest["files"]:
        p = os.path.join(root, rel.replace("/", os.sep))
        with open(p, "r+b") as f:
            f.truncate(os.path.getsize(p) // 2)
    sys.exit(143)                            # preempted mid-epoch

restored = mgr.restore(None, like)           # must skip torn step 2
start = int(restored["s"])
for step in range(start + 1, TOTAL + 1):
    mgr.save(step, {"s": jnp.int32(step)}, force=True)
mgr.wait()
mgr.close()
with open(result, "w", encoding="utf-8") as f:
    f.write(f"{start} {TOTAL}")
sys.exit(0)
