"""Mixture-of-experts training with expert parallelism: experts live on
the `ep` mesh axis, every shard routes its group's rows over all experts,
computes its own experts' share without a capacity or a dropped token, and
the shares are summed over `ep` (tony_tpu/models/moe.py). New capability
relative to the reference, which never sharded a model across tasks
(SURVEY.md section 2.3)."""
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

from tony_tpu.models import Transformer, causal_lm_loss
from tony_tpu.models.moe import MoEConfig, moe_counters
from tony_tpu.parallel import MeshSpec, build_mesh, init_sharded_state
from tony_tpu.parallel.sharding import DEFAULT_RULES

import flax.linen as nn
import functools

STEPS = int(os.environ.get("MOE_STEPS", "5"))
EP = int(os.environ.get("MOE_EP", "2"))

mesh = build_mesh(MeshSpec(dp=-1, ep=EP))
cfg = MoEConfig.tiny_moe()
model = Transformer(cfg)
tokens = jax.random.randint(jax.random.key(0), (8, 32), 0, cfg.vocab_size)

state, state_sh = init_sharded_state(model, tokens, optax.adam(1e-3), mesh)


def loss(params):
    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        logits, sown = model.apply({"params": params}, tokens,
                                   mutable=["intermediates"])
        return causal_lm_loss(logits, tokens), moe_counters(
            sown["intermediates"])


@jax.jit
def step(state):
    (l, counters), grads = jax.value_and_grad(loss, has_aux=True)(
        state.params)
    return state.apply_gradients(grads), l, counters


# telemetry.step feeds utilization into TASK_FINISHED metrics / the
# portal /metrics view when run under tony-tpu.
from tony_tpu import telemetry

first = last = None
with jax.set_mesh(mesh):
    for i in range(STEPS):
        with telemetry.step():
            state, l, counters = step(state)
            last = float(l)
        first = first if first is not None else last
print(f"process {jax.process_index()}: loss {first:.4f} -> {last:.4f}; "
      + ", ".join(f"{k} {float(v):.3g}" for k, v in counters.items()))
assert last < first, "loss did not decrease"
if jax.process_count() > 1:
    jax.distributed.shutdown()
sys.exit(0)
