"""Remat sweep: 8×8192-with-remat, the 0.95B single-chip model, and 32k
flash blocks (measure_point discipline: one scan program per K steps,
best-of-N reps, fresh tokens per step).

Run on the chip, one command per call:
    chiprun -- python benchmarks/remat_sweep.py [8k|big|32k|all]

It compares full remat, the layer-granular knob
(TransformerConfig.remat_skip_every: every Nth block un-remat'd) and the
``jax.checkpoint_policies`` dot-saving policies. Not measured on the
current installation (jax 0.9.0 / libtpu 0.0.34); ahead-of-time, the
dot-saving policies compile for v5e at Llama-3-8B widths (CHANGES.md,
PR 21).
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def _flagship_8k(**kw):
    from tony_tpu.models import TransformerConfig
    base = dict(vocab_size=32000, dim=1024, n_layers=16, n_heads=8,
                n_kv_heads=4, mlp_dim=4096, max_seq_len=8192, remat=True,
                attn_block_q=1024, attn_block_k=1024)
    base.update(kw)
    return TransformerConfig(**base)


def _big(**kw):
    from tony_tpu.models import TransformerConfig
    base = dict(vocab_size=32000, dim=1536, n_layers=24, n_heads=12,
                n_kv_heads=6, mlp_dim=6144, max_seq_len=2048, remat=True,
                attn_block_q=1024, attn_block_k=1024)
    base.update(kw)
    return TransformerConfig(**base)


def _try(label, fn):
    try:
        r = fn()
    except Exception as e:  # noqa: BLE001
        r = {"error": str(e)[:200]}
    print(label, r, flush=True)
    return r


def sweep_8k():
    """Flagship at 8×8192 chunked-CE (b8 only fits WITH remat)."""
    out = {}
    for skip in (0, 2, 3, 4):
        out[f"skip{skip}"] = _try(
            f"8k skip{skip}",
            lambda s=skip: bench.measure_point(
                _flagship_8k(remat_skip_every=s), batch=8, seq=8192,
                steps=8, chunked=True, loss_chunk=2048, reps=2))
    # One checkpoint-policy probe, kept to document the rig limitation.
    out["policy_dots_no_batch"] = _try(
        "8k policy", lambda: bench.measure_point(
            _flagship_8k(remat_policy="dots_with_no_batch_dims_saveable"),
            batch=8, seq=8192, steps=8, chunked=True, loss_chunk=2048,
            reps=1))
    return out


def sweep_big():
    """0.95B at 4×2048, bf16 mu."""
    import jax.numpy as jnp

    out = {}
    for skip in (0, 2, 3):
        out[f"skip{skip}"] = _try(
            f"big skip{skip}",
            lambda s=skip: bench.measure_point(
                _big(remat_skip_every=s), batch=4, seq=2048, steps=12,
                chunked=True, loss_chunk=1024, reps=2,
                mu_dtype=jnp.bfloat16))
    return out


def sweep_32k():
    """32k context, remat off (fits via chunked CE): flash block shapes.
    Blocks > 1024 fail VMEM at d=128 — expected errors, kept to pin the
    tiling ceiling."""
    out = {}
    for bq, bk in ((1024, 1024), (2048, 1024), (1024, 2048)):
        os.environ["TONY_BENCH_BLOCK_Q"] = str(bq)
        os.environ["TONY_BENCH_BLOCK_K"] = str(bk)
        out[f"bq{bq}_bk{bk}"] = _try(
            f"32k bq{bq} bk{bk}",
            lambda: bench.measure_point(
                bench.build_flagship_config(32768), batch=1, seq=32768,
                steps=5, chunked=True, loss_chunk=8192, reps=2))
    os.environ.pop("TONY_BENCH_BLOCK_Q", None)
    os.environ.pop("TONY_BENCH_BLOCK_K", None)
    return out


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    results = {}
    if which in ("8k", "all"):
        results["8k"] = sweep_8k()
    if which in ("big", "all"):
        results["big"] = sweep_big()
    if which in ("32k", "all"):
        results["32k"] = sweep_32k()
    print(json.dumps(results))
