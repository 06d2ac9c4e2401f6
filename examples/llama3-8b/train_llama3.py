"""Llama-3-8B pretraining step: fsdp x tp sharding, flash attention,
bf16 activations, f32 params, checkpoint/resume via the job checkpoint
dir. Geometry from the public Llama-3-8B config (32L / 4096d / 32h /
8kv / 14336 mlp / 128k vocab)."""
import os
import sys

import jax

if int(os.environ.get("JAX_NUM_PROCESSES", "1")) > 1:
    jax.distributed.initialize(
        coordinator_address=os.environ["JAX_COORDINATOR_ADDRESS"],
        num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
        process_id=int(os.environ["JAX_PROCESS_ID"]))

import flax.linen as nn
import jax.numpy as jnp
import optax

from tony_tpu.checkpoint import CheckpointManager
from tony_tpu.models import Transformer, TransformerConfig
from tony_tpu.models.transformer import (causal_lm_loss,
                                         chunked_causal_lm_loss)
from tony_tpu.parallel import (MeshSpec, build_mesh, init_sharded_state,
                               jit_train_step)
from tony_tpu.parallel.sharding import DEFAULT_RULES

BATCH = int(os.environ.get("LLAMA_BATCH", "8"))
SEQ = int(os.environ.get("LLAMA_SEQ", "8192"))
STEPS = int(os.environ.get("LLAMA_STEPS", "100"))
TP = int(os.environ.get("LLAMA_TP", "4"))
# The tony.train.* hot-loop knobs, env-shaped for this script:
# accumulation + bucketed DCN grad sync (parallel/grad_sync.py) and the
# quantized projection path (ops/quant.py). Defaults = monolithic step,
# bf16 — the pre-grad-sync behaviour, bitwise.
ACCUM = int(os.environ.get("LLAMA_ACCUM_STEPS", "1"))
BUCKET_MB = int(os.environ.get("LLAMA_BUCKET_MB", "32"))
MATMUL_DTYPE = os.environ.get("LLAMA_MATMUL_DTYPE", "")

if os.environ.get("LLAMA_TINY"):
    # CI shape: same code path (mesh, remat policy, checkpointing), toy
    # geometry — lets the flagship script run on the virtual CPU mesh.
    cfg = TransformerConfig.tiny(
        n_layers=2, remat=True,
        remat_policy="dots_with_no_batch_dims_saveable",
        matmul_dtype=MATMUL_DTYPE or None)
else:
    cfg = TransformerConfig.llama3_8b(
        remat=True, remat_policy="dots_with_no_batch_dims_saveable",
        # RoPE guard bound: follow the requested context (llama3's native
        # window is 8192; longer runs are context extension on synthetic
        # data here).
        max_seq_len=max(SEQ, 8192),
        matmul_dtype=MATMUL_DTYPE or None)
mesh = build_mesh(MeshSpec(dp=1, fsdp=-1, tp=TP))
model = Transformer(cfg)
tokens = jax.random.randint(jax.random.key(0), (BATCH, SEQ), 0,
                            cfg.vocab_size)  # synthetic; wire your loader

state, state_sh = init_sharded_state(
    model, tokens, optax.adamw(3e-4, weight_decay=0.1), mesh)


# Past ~8k context the [B, S, 128k-vocab] logits (not attention) are the
# memory wall: the chunked loss never materializes them. Short sequences
# keep the one-matmul full path. LLAMA_CHUNKED_LOSS=1 forces the chunked
# branch (CI exercises it at toy geometry).
LOSS_CHUNK = int(os.environ.get("LLAMA_LOSS_CHUNK", "2048"))
CHUNKED = SEQ >= 8192 or os.environ.get("LLAMA_CHUNKED_LOSS") == "1"


def _loss_on(params, toks):
    with nn.logical_axis_rules(list(DEFAULT_RULES)):
        if CHUNKED:
            h = model.apply({"params": params}, toks, return_hidden=True)
            return chunked_causal_lm_loss(
                h, params["lm_head"]["kernel"], toks,
                chunk_size=LOSS_CHUNK, head_dtype=cfg.lm_head_dtype)
        return causal_lm_loss(model.apply({"params": params}, toks), toks)


def _loss_fn(params, b, rng):
    return _loss_on(params, b["tokens"]), {}


batch = {"tokens": tokens}
if ACCUM > 1:
    # Grad-sync path: ACCUM microbatches per optimizer step, bucketed
    # cross-slice all-reduce as its own telemetry-phased dispatch — the
    # step `top`/perf.json can attribute a comms fraction to.
    from tony_tpu.parallel import jit_train_step_accum

    _step = jit_train_step_accum(
        _loss_fn, mesh, state_sh, batch,
        accum_steps=ACCUM, bucket_mb=BUCKET_MB, donate=False)
else:
    # The library step: explicit in/out shardings, and the mesh bound
    # around every call. The flash kernels need that bound mesh to run
    # per shard — a step jitted by hand outside ``jax.set_mesh`` hands the
    # TPU compiler a Mosaic kernel to partition, which it refuses.
    _step = jit_train_step(_loss_fn, mesh, state_sh, batch)


def step(state):
    state, metrics = _step(state, batch, jax.random.key(0))
    return state, metrics["loss"]


ckpt_dir = os.environ.get("TONY_CHECKPOINT_DIR", "")
mgr = CheckpointManager(ckpt_dir, save_interval_steps=50) if ckpt_dir \
    else None
start = 0


def _ckpt_tree(s):
    # FULL state: params alone would resume with re-warming Adam moments
    # and a reset step counter — a loss spike after every restart.
    return {"step": s.step, "params": s.params, "opt_state": s.opt_state}


if mgr is not None and mgr.latest_step() is not None:
    try:
        state = state.replace(**mgr.restore(mgr.latest_step(),
                                            _ckpt_tree(state)))
    except Exception:  # noqa: BLE001 — pre-full-state checkpoint layout
        print("warning: checkpoint has no opt_state (older layout); "
              "resuming with params only — optimizer moments re-warm",
              file=sys.stderr)
        partial = {"step": state.step, "params": state.params}
        state = state.replace(**mgr.restore(mgr.latest_step(), partial))
    # Checkpoint i is saved AFTER loop iteration i (post-step state), so
    # the next iteration to run is i+1 — resuming at i would duplicate
    # one optimizer update per restart.
    start = int(mgr.latest_step()) + 1

# Per-step utilization (steps/s, duty cycle, MFU) flows to TASK_FINISHED
# metrics and the portal's /metrics view via the telemetry reporter — the
# TPU analogue of per-container GPU util (TaskMonitor.java:116-170).
from tony_tpu import telemetry

n_params = sum(x.size for x in jax.tree.leaves(state.params))
flops_per_step = 6 * n_params * BATCH * SEQ
for i in range(start, STEPS):
    with telemetry.step(flops=flops_per_step, tokens=BATCH * SEQ):
        state, l = step(state)
        jax.block_until_ready(l)
    if mgr is not None:
        mgr.save(i, _ckpt_tree(state))
if mgr is not None:
    mgr.wait()
print(f"process {jax.process_index()}: final loss {float(l):.4f}")
if jax.process_count() > 1:
    jax.distributed.shutdown()
sys.exit(0)
