"""The Mamba-2 mixer: a state-space layer's one part.

For a layer's normed input ``n [B, S, D]``::

    z, u, dt = n·W_z, n·W_xbc, n·W_dt          u = [x | B | C]
    u  ← silu(b_c + Σ_k w_k ⊙ u_{t−K+1+k})     depthwise, causal, zeros before
                                                the row's start
    Δ  = softplus(dt + dt_bias)                 a head and token, float32
    y  = ssd(x, Δ, −exp(A_log), B, C, D)        ``ops/ssd.py``
    y  ← W_norm ⊙ grouprms(y ⊙ silu(z))         RMS over each group's columns,
                                                the gate before the norm
    out = y·W_o

``H`` heads of width ``P`` (``H·P`` need not be a multiple of ``D``), ``G``
groups of ``B`` and ``C`` with state ``N``. The input projection is three
leaves, so that the ``H`` columns that make Δ have a scale of their own;
``matmul_dtype`` covers all of it and the output projection (``wz``,
``wxbc``, ``wdt``, ``wo``). Activations and matmuls run in ``dtype``, the
``H`` dt columns' product accumulated and kept in float32; Δ, the decays,
the conv's sum and the norm's statistics in float32. No bias on the
projections.

The scan hands its state from chunk to chunk along the whole row, so a layer
cannot be split over the sequence: the sequence-parallel attention modes
refuse a stack that has one (``models/transformer.py``).

Spans (``jax.named_scope``): ``tony.ssm.in_proj``, ``tony.ssm.conv``,
``tony.ssm.scan``, ``tony.ssm.gate_norm``, ``tony.ssm.out_proj``. Counters,
sown into ``intermediates`` and reduced by ``transformer.layer_counters``:
``ssm_dt_mean`` (mean Δ), ``ssm_decay_mean`` (mean ``a_t = exp(Δ·A)``: 0 is
a state that forgets at once, 1 one that never does) and
``ssm_head_rms_max_over_median`` (each head's RMS of ``y`` over its tokens
and columns, before the gate; the largest over the median: far above 1, one
head sets the norm's statistics of its group).
"""

from __future__ import annotations

import dataclasses
import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from tony_tpu.ops.quant import dense, quantized_matmul, resolve_mode
from tony_tpu.ops.ssd import ssd


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    """A state-space mixer's sizes: ``n_heads`` heads of ``head_dim``,
    ``n_groups`` groups of B and C with ``state`` columns each, a causal
    depthwise conv of ``conv`` taps, the scan's ``chunk``."""
    n_heads: int
    head_dim: int
    n_groups: int
    state: int
    conv: int = 4
    chunk: int = 128

    def __post_init__(self):
        if self.n_heads % self.n_groups:
            raise ValueError(f"{self.n_heads} heads over {self.n_groups} "
                             f"groups")


# The mixer's published draw: A uniform in A_RANGE; a head's step log-uniform
# in DT_RANGE and at least DT_FLOOR, kept as softplus's inverse of it.
A_RANGE = (1.0, 16.0)
DT_RANGE = (1e-3, 0.1)
DT_FLOOR = 1e-4


def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, dtype, *A_RANGE))


def _dt_bias_init(key, shape, dtype):
    lo, hi = (math.log(t) for t in DT_RANGE)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape, dtype, lo, hi)),
                     DT_FLOOR)
    return dt + jnp.log(-jnp.expm1(-dt))


class SSMixer(nn.Module):
    spec: SSMSpec
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    matmul_dtype: str = ""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, n: jax.Array) -> jax.Array:
        spec = self.spec
        b, s, d = n.shape
        h, p, g, state = (spec.n_heads, spec.head_dim, spec.n_groups,
                          spec.state)
        inner, conv_dim = h * p, h * p + 2 * g * state
        proj = functools.partial(dense, dtype=self.dtype,
                                 param_dtype=self.param_dtype,
                                 matmul_dtype=self.matmul_dtype or None)

        def leaf(name, init, shape, axes):
            return self.param(name, nn.with_logical_partitioning(init, axes),
                              shape, self.param_dtype)

        with jax.named_scope("tony.ssm.in_proj"):
            z = proj(inner, ("embed", "ssm_inner"), "wz")(n)
            u = proj(conv_dim, ("embed", "ssm_inner"), "wxbc")(n)
            w_dt = leaf("wdt", nn.initializers.lecun_normal(), (d, h),
                        ("embed", "ssm_heads")).astype(self.dtype)
            mode = resolve_mode(self.matmul_dtype)
            dt = jnp.dot(n, w_dt, preferred_element_type=jnp.float32) \
                if mode is None else quantized_matmul(
                    n, w_dt, mode).astype(jnp.float32)
        with jax.named_scope("tony.ssm.conv"):
            taps = leaf("conv_kernel", nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                (spec.conv, conv_dim), ("conv", "ssm_inner"))
            acc = leaf("conv_bias", nn.initializers.zeros, (conv_dim,),
                       ("ssm_inner",)).astype(jnp.float32)
            padded = jnp.pad(u, ((0, 0), (spec.conv - 1, 0), (0, 0)))
            for k in range(spec.conv):
                acc = acc + padded[:, k:k + s].astype(jnp.float32) * taps[k]
            u = nn.silu(acc).astype(self.dtype)
        with jax.named_scope("tony.ssm.scan"):
            x, bm, cm = jnp.split(u, (inner, inner + g * state), axis=-1)
            dt = jax.nn.softplus(dt + leaf(
                "dt_bias", _dt_bias_init, (h,), ("ssm_heads",)))
            a = -jnp.exp(leaf("A_log", _a_log_init, (h,),
                              ("ssm_heads",)).astype(jnp.float32))
            self.sow("intermediates", "ssm_dt_mean", jnp.mean(dt))
            self.sow("intermediates", "ssm_decay_mean",
                     jnp.mean(jnp.exp(dt * a)))
            y = ssd(x.reshape(b, s, h, p), dt, a, bm.reshape(b, s, g, state),
                    cm.reshape(b, s, g, state),
                    leaf("D", nn.initializers.ones, (h,), ("ssm_heads",)),
                    chunk=spec.chunk)
        with jax.named_scope("tony.ssm.gate_norm"):
            rms = jnp.sqrt(jnp.mean(jnp.square(y.astype(jnp.float32)),
                                    axis=(0, 1, 3)))
            self.sow("intermediates", "ssm_head_rms_max_over_median",
                     jnp.max(rms) / jnp.median(rms))
            gated = (y.reshape(b, s, inner).astype(jnp.float32)
                     * nn.silu(z.astype(jnp.float32))).reshape(b, s, g, -1)
            var = jnp.mean(jnp.square(gated), axis=-1, keepdims=True)
            y = (gated * jax.lax.rsqrt(var + self.eps)).reshape(b, s, inner) \
                * leaf("norm", nn.initializers.ones, (inner,), ("ssm_inner",))
            y = nn.with_logical_constraint(y.astype(self.dtype),
                                           ("batch", "seq", "ssm_inner"))
        with jax.named_scope("tony.ssm.out_proj"):
            return proj(d, ("ssm_inner", "embed"), "wo")(y)
