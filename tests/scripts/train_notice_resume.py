"""Preemption-notice resume contract script: NO periodic saves; the only
checkpoint source is the save-on-SIGTERM handler fired by the executor's
metadata-notice watcher. Epoch 0 trains slowly until the notice kills it;
epoch 1 restores at the handler's step and finishes."""
import os
import time

import jax

import jax.numpy as jnp

from tony_tpu.checkpoint import CheckpointManager

TOTAL = 6
mgr = CheckpointManager(os.environ["TONY_CHECKPOINT_DIR"], async_save=False)
state = {"step": jnp.zeros((), jnp.int32)}
latest = mgr.latest_step()
if latest is not None:
    state = mgr.restore(latest, state)
start = int(state["step"])

mgr.install_preemption_handler(lambda: (int(state["step"]), state))

ready = os.environ.get("TONY_TEST_READY_FILE", "")
for _ in range(start, TOTAL):
    state = {"step": state["step"] + 1}
    jax.block_until_ready(state["step"])
    if ready and int(state["step"]) == 3 and start == 0:
        with open(ready, "w") as f:
            f.write("3")          # signal the test: flip the notice now
    # Epoch 0 idles between steps so the notice lands mid-training;
    # epoch 1 (resumed) runs fast to finish.
    if start == 0:
        time.sleep(0.3)

with open(os.environ["TONY_TEST_RESULT"], "w") as f:
    f.write(f"{start} {int(state['step'])}")
