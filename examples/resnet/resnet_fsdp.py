"""ResNet image classification with dp x fsdp sharding (reference
analogue: the examples tree's vision workload; here the model weights are
fully sharded over the fsdp axis, gradients reduced over dp)."""
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

from tony_tpu.models.mlp import classification_loss
from tony_tpu.models.resnet import ResNet, ResNetConfig
from tony_tpu.parallel import (MeshSpec, build_mesh, init_sharded_state,
                               jit_train_step)

STEPS = int(os.environ.get("RESNET_STEPS", "10"))
FSDP = int(os.environ.get("RESNET_FSDP", "2"))

mesh = build_mesh(MeshSpec(dp=-1, fsdp=FSDP))
cfg = ResNetConfig.tiny() if os.environ.get("RESNET_TINY", "1") == "1" \
    else ResNetConfig.resnet50()
model = ResNet(cfg)
x = jax.random.normal(jax.random.key(0), (16, 32, 32, 3))
y = jax.random.randint(jax.random.key(1), (16,), 0, cfg.num_classes)
batch = {"x": x, "y": y}


def loss_fn(params, b, rng):
    return classification_loss(model.apply({"params": params}, b["x"]),
                               b["y"]), {}


state, state_sh = init_sharded_state(model, x, optax.adam(1e-3), mesh)
step = jit_train_step(loss_fn, mesh, state_sh, batch)
# telemetry.step feeds utilization (steps/s, duty cycle) into the job's
# TASK_FINISHED metrics and the portal /metrics view when run under
# tony-tpu; standalone it is a no-op beyond a timestamp.
from tony_tpu import telemetry

first = last = None
for i in range(STEPS):
    with telemetry.step():
        state, m = step(state, batch, jax.random.key(i))
        last = float(m["loss"])
    first = first if first is not None else last
print(f"process {jax.process_index()}: loss {first:.4f} -> {last:.4f}")
assert last < first, "loss did not decrease"
if jax.process_count() > 1:
    jax.distributed.shutdown()
sys.exit(0)
