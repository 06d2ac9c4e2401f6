"""Data-parallel MNIST: the canonical first job (the analogue of the
reference's ``tony-examples/mnist-tensorflow`` / ``mnist-pytorch``, but one
uniform JAX bootstrap instead of per-framework env dialects).

Synthetic data (no dataset download — swap in real MNIST loading where you
have network/disk). Multi-process: the tony-tpu JAXRuntime provides
JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID; single
process runs standalone on whatever chips are visible.
"""
import os
import sys

import jax

if int(os.environ.get("JAX_NUM_PROCESSES", "1")) > 1:
    jax.distributed.initialize(
        coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
        num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
        process_id=int(os.environ["JAX_PROCESS_ID"]))

import jax.numpy as jnp
import optax

from tony_tpu.models import MnistMLP
from tony_tpu.models.mlp import classification_loss
from tony_tpu.parallel import (MeshSpec, build_mesh, init_sharded_state,
                               jit_train_step)

STEPS = int(os.environ.get("MNIST_STEPS", "20"))

mesh = build_mesh(MeshSpec(dp=-1))          # pure data parallelism
model = MnistMLP(hidden=128)
x = jax.random.normal(jax.random.key(0), (64, 28, 28, 1))
y = jax.random.randint(jax.random.key(1), (64,), 0, 10)
batch = {"x": x, "y": y}


def loss_fn(params, b, rng):
    return classification_loss(model.apply({"params": params}, b["x"]),
                               b["y"]), {}


state, state_sh = init_sharded_state(model, x, optax.adam(1e-2), mesh)
step = jit_train_step(loss_fn, mesh, state_sh, batch)
first = last = None
for i in range(STEPS):
    state, m = step(state, batch, jax.random.key(i))
    last = float(m["loss"])
    first = first if first is not None else last
print(f"process {jax.process_index()}: loss {first:.4f} -> {last:.4f}")
assert last < first, "loss did not decrease"
if jax.process_count() > 1:
    jax.distributed.shutdown()
sys.exit(0)
