"""Save-on-preemption contract script: NO periodic saves — the ONLY way a
checkpoint can exist is the SIGTERM handler firing inside the teardown
grace window (CheckpointManager.install_preemption_handler riding the
kill chain's TERM→grace→KILL contract). The e2e force-kills this job
mid-training and asserts a handler-written checkpoint survived."""
import os
import time

import jax

import jax.numpy as jnp

from tony_tpu.checkpoint import CheckpointManager

mgr = CheckpointManager(os.environ["TONY_CHECKPOINT_DIR"], async_save=False)
state = {"step": jnp.zeros((), jnp.int32),
         "w": jnp.arange(4, dtype=jnp.float32)}

mgr.install_preemption_handler(lambda: (int(state["step"]), state))

ready = os.environ.get("TONY_TEST_READY_FILE", "")
for _ in range(10_000):               # run "forever" — the kill ends us
    state = {"step": state["step"] + 1, "w": state["w"] * 2.0}
    jax.block_until_ready(state["w"])
    if ready and int(state["step"]) == 3:
        with open(ready, "w") as f:   # signal: mid-training, state exists
            f.write("3")
    time.sleep(0.1)
