"""Kimi Delta Attention (KDA): a linear-attention layer's one part.

For a layer's normed input ``n [B, S, D]``, ``H`` heads of width ``K``::

    q, k, v = silu(conv(n W_q)), silu(conv(n W_k)), silu(conv(n W_v))
                                    depthwise, causal, no bias, zeros before
                                    the row's start
    q, k ← q / ‖q‖ · K^−½, k / ‖k‖  a head at a time (L2 over its columns)
    g    = −exp(A_log_h) · softplus(n W_fa W_fb + dt_bias)
                                    [S, H, K] float32: a decay per CHANNEL
    β    = sigmoid(n W_b)           [S, H]
    o    = kda(q, k, v, g, β)       ``ops/kda.py``: the gated delta rule
    o    ← W_on ⊙ rms(o) ⊙ sigmoid(n W_ga W_gb)
                                    RMS a head over its K columns
    out  = o W_o

``W_q``, ``W_k``, ``W_v`` and ``W_o`` take ``matmul_dtype``; the low-rank
products of the decay and of the output gate (``f_a``, ``f_b``, ``g_a``,
``g_b``) and β's ``b`` stay in ``dtype``. The decay's and β's products are
accumulated and kept in float32, as are the conv's sum, the L2 and RMS
norms' statistics and the gate's sigmoid; the conv's output, the gate's
product and what the scan takes and gives are in ``dtype``. The L2 norms and
the decay gate are taken inside the scan's kernels (``ops/kda.py``), which
are handed the decay's pre-activation ``x = n W_fa W_fb + dt_bias``. No bias
anywhere.

The scan hands its state from chunk to chunk along the whole row, so a layer
cannot be split over the sequence: the sequence-parallel attention modes
refuse a stack that has one (``models/transformer.py``).

Spans (``jax.named_scope``): ``tony.kda.in_proj`` (q, k, v and the
low-rank f, g and β projections), ``tony.kda.conv``, ``tony.kda.scan`` (the
L2 norms, the decay gate, the kernel call and its layouts),
``tony.kda.out_norm`` and ``tony.kda.out_proj``. Counters, sown into
``intermediates`` and reduced by ``transformer.layer_counters``:
``kda_decay_mean`` (mean ``α = e^g`` over channels and tokens),
``kda_log_decay_min`` (the most negative ``g`` a channel took: how near the
chunked form's numerical edge a step ran, ``ops/kda.py``) and
``kda_beta_mean``.
"""

from __future__ import annotations

import dataclasses
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp

from tony_tpu.ops.kda import kda, log_decays
from tony_tpu.ops.quant import dense


@dataclasses.dataclass(frozen=True)
class KDASpec:
    """A KDA mixer's sizes: ``n_heads`` heads of ``head_dim`` (keys and
    values alike), a causal depthwise conv of ``conv`` taps, the scan's
    ``chunk``."""
    n_heads: int
    head_dim: int
    conv: int = 4
    chunk: int = 64


@jax.checkpoint
def _gated_norm(o, gate, scale, eps):
    """``scale ⊙ rms(o) ⊙ sigmoid(gate)`` a head, float32 inside and ``o``'s
    dtype out. Recomputed from its bf16 inputs in the backward pass, so no
    float32 copy of a ``[S, H, K]`` tensor outlives the forward."""
    o32 = o.astype(jnp.float32)
    var = jnp.mean(jnp.square(o32), axis=-1, keepdims=True)
    return (o32 * jax.lax.rsqrt(var + eps) * scale
            * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)


class KDAMixer(nn.Module):
    spec: KDASpec
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    matmul_dtype: str = ""
    eps: float = 1e-5

    @nn.compact
    def __call__(self, n: jax.Array) -> jax.Array:
        spec = self.spec
        b, s, d = n.shape
        h, kd = spec.n_heads, spec.head_dim
        inner = h * kd
        proj = functools.partial(dense, dtype=self.dtype,
                                 param_dtype=self.param_dtype,
                                 matmul_dtype=self.matmul_dtype or None)

        def leaf(name, init, shape, axes):
            return self.param(name, nn.with_logical_partitioning(init, axes),
                              shape, self.param_dtype)

        def low_rank(x, name, shape, axes):
            """x times a kernel that stays in ``dtype``, accumulated and
            kept in float32."""
            w = leaf(name, nn.initializers.lecun_normal(), shape, axes)
            return jnp.dot(x, w.astype(self.dtype),
                           preferred_element_type=jnp.float32)

        with jax.named_scope("tony.kda.in_proj"):
            qkv = [proj(inner, ("embed", "kda_inner"), name)(n)
                   for name in ("wq", "wk", "wv")]
            f = low_rank(low_rank(n, "f_a", (d, kd), ("embed", "rank"))
                         .astype(self.dtype), "f_b", (kd, inner),
                         ("rank", "kda_inner"))
            gate = low_rank(low_rank(n, "g_a", (d, kd), ("embed", "rank"))
                            .astype(self.dtype), "g_b", (kd, inner),
                            ("rank", "kda_inner")).astype(self.dtype)
            beta = jax.nn.sigmoid(low_rank(n, "b", (d, h),
                                           ("embed", "kda_heads")))
        with jax.named_scope("tony.kda.conv"):
            for i, name in enumerate(("q_conv", "k_conv", "v_conv")):
                taps = leaf(name, nn.initializers.variance_scaling(
                    1.0, "fan_in", "normal", in_axis=0, out_axis=1),
                    (spec.conv, inner), ("conv", "kda_inner"))
                padded = jnp.pad(qkv[i], ((0, 0), (spec.conv - 1, 0), (0, 0)))
                acc = jnp.zeros((b, s, inner), jnp.float32)
                for t in range(spec.conv):
                    acc = acc + padded[:, t:t + s].astype(jnp.float32) \
                        * taps[t]
                qkv[i] = nn.silu(acc).astype(self.dtype).reshape(b, s, h, kd)
        with jax.named_scope("tony.kda.scan"):
            a = -jnp.exp(leaf("A_log", nn.initializers.zeros, (h,),
                              ("kda_heads",)).astype(jnp.float32))
            x = (f + leaf("dt_bias", nn.initializers.zeros, (inner,),
                          ("kda_inner",))).reshape(b, s, h, kd)
            g = log_decays(x, a)
            self.sow("intermediates", "kda_decay_mean", jnp.mean(jnp.exp(g)))
            self.sow("intermediates", "kda_log_decay_min", jnp.min(g))
            self.sow("intermediates", "kda_beta_mean", jnp.mean(beta))
            o = kda(*qkv, x, a, beta, chunk=spec.chunk)
        with jax.named_scope("tony.kda.out_norm"):
            o = _gated_norm(o, gate.reshape(b, s, h, kd), leaf(
                "o_norm", nn.initializers.ones, (kd,), ("norm",)), self.eps)
            o = nn.with_logical_constraint(o.reshape(b, s, inner),
                                           ("batch", "seq", "kda_inner"))
        with jax.named_scope("tony.kda.out_proj"):
            return proj(d, ("kda_inner", "embed"), "wo")(o)
