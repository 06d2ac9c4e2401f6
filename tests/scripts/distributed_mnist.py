"""The minimum end-to-end training slice (SURVEY.md §7.5): every worker
joins the JAX coordination service bootstrapped by the tony-tpu rendezvous,
forms a global mesh over all processes' devices, and runs pjit data-parallel
training steps on a synthetic MNIST-shaped problem.

This is the TPU-native analogue of the reference's
``mnist-tensorflow/mnist_distributed.py`` (TF PS/worker) — one uniform
`jax.distributed` bootstrap instead of four env dialects."""
import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")
import jax

jax.distributed.initialize(
    coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
    num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
    process_id=int(os.environ["JAX_PROCESS_ID"]),
    # Generous heartbeat budget: on a loaded 1-core CI box the peer
    # process can be starved for tens of seconds; the default 100 s
    # budget SIGABRTed the faster process once under a full serial
    # suite run (exit 134).
    heartbeat_timeout_seconds=300,
)

import jax.numpy as jnp
import optax

from tony_tpu.models import MnistMLP
from tony_tpu.models.mlp import classification_loss
from tony_tpu.parallel import (MeshSpec, build_mesh, init_sharded_state,
                               jit_train_step)

rank = jax.process_index()
n_dev = len(jax.devices())
print(f"process {rank}: {jax.process_count()} processes, {n_dev} global "
      f"devices")

mesh = build_mesh(MeshSpec(dp=n_dev))
model = MnistMLP(hidden=32)
x = jax.random.normal(jax.random.key(0), (16, 28, 28, 1))
labels = jax.random.randint(jax.random.key(1), (16,), 0, 10)
batch = {"x": x, "y": labels}


def loss_fn(params, b, rng):
    logits = model.apply({"params": params}, b["x"])
    return classification_loss(logits, b["y"]), {}


state, state_sh = init_sharded_state(model, x, optax.adam(1e-2), mesh)
step = jit_train_step(loss_fn, mesh, state_sh, batch)
losses = []
for i in range(5):
    state, m = step(state, batch, jax.random.key(i))
    losses.append(float(m["loss"]))
print(f"process {rank} losses: {losses}")
assert losses[-1] < losses[0], f"loss did not decrease: {losses}"
assert all(jnp.isfinite(jnp.asarray(losses))), losses
jax.distributed.shutdown()
sys.exit(0)
