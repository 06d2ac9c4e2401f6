"""Low-precision (int8 / fp8-e4m3) matmul paths for the training hot loop.

The Gemma-on-TPU comparison (PAPERS.md) attributes most of its TPU win to
low-precision matmuls: v5e's MXU runs int8 at 2x the bf16 rate (394 vs
197 TOPS), and the flagship's attention/MLP projections are plain
``x @ W`` contractions that tolerate symmetric per-channel quantization.
This module is that lever, opt-in via ``tony.train.matmul-dtype``
(`TransformerConfig.matmul_dtype` threads it into every ``_dense``
projection in models/transformer.py):

- **Symmetric, per-channel, round-to-nearest.** Activations get one scale
  per row (amax over the contraction dim), weights one per output
  channel; no zero points, no stochastic rounding — dequantization is two
  rank-1 scale multiplies on the f32/int32 accumulator.
- **Forward-only.** The quantized dot runs under a ``jax.custom_vjp``
  whose backward is the exact full-precision matmul gradient
  (straight-through estimator): training dynamics stay within the
  loss-parity tolerance of the bf16 golden (test-gated over the bench
  window), and disabling the knob restores the *bitwise* bf16 path
  (``QDense`` with the knob unset replicates ``nn.Dense`` exactly).
- **Degrade, never die.** ``resolve_mode`` probes the backend once per
  (mode, backend) with a tiny eager dot; an unsupported backend (or the
  ``quant.probe`` fault site) downgrades the path to bf16 with a
  ONE-TIME warning that also rides the telemetry metrics beacon
  (``quant_fallback``) — a refused quantized path must cost throughput,
  not the job.

When quantization is unsafe (loss-scale-sensitive runs, custom loss
scaling, <1e-2 gradient magnitudes): see docs/operations.md "Spending
the verdict".
"""

from __future__ import annotations

import functools
import logging
import threading
from typing import Any, Dict, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

log = logging.getLogger(__name__)

INT8 = "int8"
FP8_E4M3 = "fp8_e4m3"
#: the modes resolve_mode accepts (anything else raises).
MODES = (INT8, FP8_E4M3)
#: spellings that mean "quantization off".
_OFF = (None, "", "bf16", "none", "off")

_INT8_MAX = 127.0
_FP8_E4M3_MAX = 448.0       # largest finite float8_e4m3fn
_EPS = 1e-12

_fallback_lock = threading.Lock()
_fallbacks: Dict[str, str] = {}


def fallback_events() -> Dict[str, str]:
    """{mode: reason} for every quantized path that degraded to bf16 in
    this process — shipped on the telemetry metrics beacon so the
    one-time event is visible in `top`/metrics, not just a log line."""
    with _fallback_lock:
        return dict(_fallbacks)


def _record_fallback(mode: str, reason: str) -> None:
    with _fallback_lock:
        if mode in _fallbacks:
            return
        _fallbacks[mode] = reason
    log.warning(
        "quantized matmul path %r unavailable on this backend (%s); "
        "DEGRADING to the bf16 path — throughput loses the low-precision "
        "win, the job keeps training (one-time warning)", mode, reason)


@functools.lru_cache(maxsize=None)
def _probe(mode: str, backend: str) -> str:
    """Empty string when the backend runs the quantized dot; else the
    refusal reason. Cached per (mode, backend) — the probe is a tiny
    eager computation, run once."""
    from tony_tpu import faults

    try:
        faults.check("quant.probe")
        if mode == INT8:
            a = jnp.ones((8, 8), jnp.int8)
            out = lax.dot_general(a, a, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        else:
            f8 = jnp.ones((8, 8), jnp.float8_e4m3fn)
            out = lax.dot_general(f8, f8, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        jax.block_until_ready(out)
    except Exception as e:  # noqa: BLE001 — any refusal shape degrades
        return f"{type(e).__name__}: {e}"[:200]
    return ""


def resolve_mode(mode: Optional[str]) -> Optional[str]:
    """Effective quantization mode: None when off or degraded (use the
    bf16 path), else the validated mode. Unknown names raise — a typo'd
    knob must fail loudly at trace time, not silently train in bf16."""
    if mode in _OFF:
        return None
    if mode not in MODES:
        raise ValueError(
            f"unknown tony.train.matmul-dtype {mode!r} (choose from "
            f"{list(MODES)}, or empty for bf16)")
    reason = _probe(mode, jax.default_backend())
    if reason:
        _record_fallback(mode, reason)
        return None
    return mode


def _reset_fallback_state() -> None:
    """Tests: forget recorded fallbacks and probe results."""
    with _fallback_lock:
        _fallbacks.clear()
    _probe.cache_clear()


def quantize_symmetric(x: jax.Array, mode: str, axis: int):
    """Per-channel symmetric quantization along ``axis`` (the contraction
    dim): returns ``(q, scale)`` with ``q * scale ~= x`` and ``scale``
    keeping dims (f32). int8 rounds to nearest; fp8 relies on the cast's
    rounding. Scales come from the f32 amax so bf16 inputs don't lose
    their own range computation."""
    qmax = _INT8_MAX if mode == INT8 else _FP8_E4M3_MAX
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis,
                   keepdims=True)
    scale = jnp.maximum(amax, _EPS) / qmax
    y = x.astype(jnp.float32) / scale
    if mode == INT8:
        q = jnp.clip(jnp.round(y), -_INT8_MAX, _INT8_MAX).astype(jnp.int8)
    else:
        q = jnp.clip(y, -_FP8_E4M3_MAX, _FP8_E4M3_MAX).astype(
            jnp.float8_e4m3fn)
    return q, scale


def _qmm_forward(x: jax.Array, w: jax.Array, mode: str) -> jax.Array:
    """The quantized contraction: x [..., K] @ w [K, N] with per-row /
    per-output-channel scales; accumulate int32 (int8) or f32 (fp8)."""
    qx, sx = quantize_symmetric(x, mode, axis=-1)       # sx [..., 1]
    qw, sw = quantize_symmetric(w, mode, axis=0)        # sw [1, N]
    dims = (((x.ndim - 1,), (0,)), ((), ()))
    if mode == INT8:
        acc = lax.dot_general(qx, qw, dims,
                              preferred_element_type=jnp.int32)
        acc = acc.astype(jnp.float32)
    else:
        acc = lax.dot_general(qx, qw, dims,
                              preferred_element_type=jnp.float32)
    out = acc * sx * sw
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def quantized_matmul(x: jax.Array, w: jax.Array, mode: str) -> jax.Array:
    """``x @ w`` through the quantized path; gradients are the exact
    full-precision matmul gradients (straight-through) so backward
    numerics are untouched by quantization noise."""
    return _qmm_forward(x, w, mode)


def _qmm_fwd(x, w, mode):
    return _qmm_forward(x, w, mode), (x, w)


def _qmm_bwd(mode, res, g):
    x, w = res
    g = g.astype(x.dtype)
    dims_dx = (((g.ndim - 1,), (1,)), ((), ()))
    dx = lax.dot_general(g, w, dims_dx)                 # g @ w.T
    x2 = x.reshape(-1, x.shape[-1])
    g2 = g.reshape(-1, g.shape[-1])
    dw = lax.dot_general(x2, g2, (((0,), (0,)), ((), ())))  # x.T @ g
    return dx.astype(x.dtype), dw.astype(w.dtype)


quantized_matmul.defvjp(_qmm_fwd, _qmm_bwd)


class QDense(nn.Module):
    """``nn.Dense(use_bias=False)`` with an opt-in quantized forward.

    With ``matmul_dtype`` unset (or resolved to a fallback) this module
    replicates ``nn.Dense``'s exact math — same param name/init/path,
    same ``promote_dtype``, same ``lax.dot_general`` call — so switching
    the knob off restores bitwise-identical behaviour, and an
    unsupported backend degrades to numbers indistinguishable from the
    unquantized model."""

    features: int
    dtype: Any = None
    param_dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.lecun_normal()
    matmul_dtype: str = ""

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", self.kernel_init,
                            (jnp.shape(x)[-1], self.features),
                            self.param_dtype)
        x, kernel = nn.dtypes.promote_dtype(x, kernel, dtype=self.dtype)
        mode = resolve_mode(self.matmul_dtype)
        if mode is None:
            # The nn.Dense path, verbatim (use_bias=False, precision
            # default) — the bitwise-identity contract.
            return lax.dot_general(
                x, kernel, (((x.ndim - 1,), (0,)), ((), ())),
                precision=None)
        return quantized_matmul(x, kernel, mode)


def dense(features: int, axes, name: str, *, dtype, param_dtype,
          matmul_dtype: Optional[str]) -> nn.Module:
    """A bias-free projection whose ``kernel`` carries the logical ``axes``:
    ``nn.Dense``, or ``QDense`` where ``matmul_dtype`` names a quantized
    forward. Same param name, path and init either way, so the knob flips
    freely across checkpoints of the same model."""
    init = nn.with_logical_partitioning(nn.initializers.lecun_normal(), axes)
    if matmul_dtype:
        return QDense(features=features, dtype=dtype,
                      param_dtype=param_dtype, name=name, kernel_init=init,
                      matmul_dtype=matmul_dtype)
    return nn.Dense(features, use_bias=False, dtype=dtype,
                    param_dtype=param_dtype, name=name, kernel_init=init)
