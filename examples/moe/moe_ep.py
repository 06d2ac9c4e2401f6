"""Mixture-of-experts training with expert parallelism: experts live on
the `ep` mesh axis, tokens reach them via all_to_all dispatch
(tony_tpu/models/moe.py). New capability relative to the reference, which
never sharded a model across tasks (SURVEY.md section 2.3)."""
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

from tony_tpu.models.moe import MoEConfig, MoETransformer, moe_lm_loss
from tony_tpu.parallel import MeshSpec, build_mesh, init_sharded_state
from tony_tpu.parallel.sharding import DEFAULT_RULES

import flax.linen as nn
import functools

STEPS = int(os.environ.get("MOE_STEPS", "5"))
EP = int(os.environ.get("MOE_EP", "2"))

mesh = build_mesh(MeshSpec(dp=-1, ep=EP))
cfg = MoEConfig.tiny_moe()
model = MoETransformer(cfg)
tokens = jax.random.randint(jax.random.key(0), (8, 32), 0, cfg.vocab_size)

state, state_sh = init_sharded_state(model, tokens, optax.adam(1e-3), mesh)


def loss(params):
    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        out = model.apply({"params": params}, tokens)
        return moe_lm_loss(out, tokens, aux_weight=cfg.aux_loss_weight)


@jax.jit
def step(state):
    l, grads = jax.value_and_grad(loss)(state.params)
    return state.apply_gradients(grads), l


# telemetry.step feeds utilization into TASK_FINISHED metrics / the
# portal /metrics view when run under tony-tpu.
from tony_tpu import telemetry

first = last = None
with jax.set_mesh(mesh):
    for i in range(STEPS):
        with telemetry.step():
            state, l = step(state)
            last = float(l)
        first = first if first is not None else last
print(f"process {jax.process_index()}: loss {first:.4f} -> {last:.4f}")
assert last < first, "loss did not decrease"
if jax.process_count() > 1:
    jax.distributed.shutdown()
sys.exit(0)
