"""HBM-aware fused GroupNorm→ReLU for the resnet conv trunk.

ResNet's conv→norm→relu chains are HBM-bound, not MXU-bound: the lever is
*fewer HBM passes per chain*, not faster matmuls. ``nn.GroupNorm`` + a
separate ``nn.relu`` walks the [B, H, W, C] activation several times
(stats, normalize, affine, relu) and saves the normalized tensor for
backward. This module collapses the chain:

- **One-pass stats.** mean and E[x²] per (batch, group) come from a
  single fused reduction sweep (XLA fuses the two reductions over the
  same operand into one pass).
- **Folded affine.** scale/rsqrt/mean/bias collapse into per-(B, C)
  ``a``/``b`` vectors, so normalize+affine+relu is ONE fused
  multiply-add-max over the activation — a Pallas kernel on TPU (one
  HBM read + one write, ``pallas_guide.md``), a single fused ``lax``
  expression everywhere else (the portable path tier-1 CPU runs).
- **Remat'd epilogue.** The fused apply sits under ``jax.checkpoint``
  (on by default): backward recomputes the cheap normalize instead of
  keeping the [B, H, W, C] normalized tensor resident — HBM footprint
  and write traffic both drop.

On TPU the Pallas apply is called plainly: a shape the compiler refuses
is an error at trace time, where it can be read — never a silent switch
to the lax path. models/resnet.py threads this through every bottleneck
via ``ResNetConfig.fused`` (on by default; the unfused GroupNorm path
stays as the parity twin). Whether the kernel beats the lax composition
on the chip is not measured (ROADMAP S5).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from tony_tpu.compat import per_shard
from tony_tpu.ops.attention import _interpret
from tony_tpu.parallel.mesh import BATCH_AXES

#: row-block for the Pallas apply kernel ([rows, C] tiles of the
#: flattened [B, H·W, C] view).
APPLY_BLOCK_ROWS = 256


def group_stats(x: jax.Array, groups: int):
    """(mean, var) per (batch, group) over spatial dims and the group's
    channels, f32, one fused sweep (E[x²] − E[x]² with a non-negative
    clamp)."""
    b, c = x.shape[0], x.shape[-1]
    xg = x.reshape(b, -1, groups, c // groups).astype(jnp.float32)
    mean = jnp.mean(xg, axis=(1, 3))
    ex2 = jnp.mean(jnp.square(xg), axis=(1, 3))
    var = jnp.maximum(ex2 - jnp.square(mean), 0.0)
    return mean, var


def folded_affine(mean: jax.Array, var: jax.Array, scale: jax.Array,
                  bias: jax.Array, channels: int, eps: float):
    """Fold (mean, var, scale, bias) into per-(B, C) ``a``/``b`` so the
    whole normalize+affine is ``x * a + b`` — one fused elementwise pass
    instead of GroupNorm's subtract/rsqrt/mul/mul/add chain."""
    groups = mean.shape[-1]
    inv = lax.rsqrt(var + eps)                          # [B, G]
    cg = channels // groups
    inv_c = jnp.repeat(inv, cg, axis=1)                 # [B, C]
    mean_c = jnp.repeat(mean, cg, axis=1)
    a = inv_c * scale.astype(jnp.float32)[None, :]
    b = bias.astype(jnp.float32)[None, :] - mean_c * a
    return a, b


def _apply_lax(x: jax.Array, a: jax.Array, b: jax.Array,
               relu: bool) -> jax.Array:
    """Portable fused apply: one multiply-add(-max) expression XLA fuses
    into a single pass (and into the neighbouring conv where it can)."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1],)
    y = x.astype(jnp.float32) * a.reshape(shape) + b.reshape(shape)
    if relu:
        y = jnp.maximum(y, 0.0)
    return y.astype(x.dtype)


def _apply_kernel(x_ref, a_ref, b_ref, o_ref, *, relu):
    y = x_ref[0].astype(jnp.float32) * a_ref[0] + b_ref[0]
    if relu:
        y = jnp.maximum(y, 0.0)
    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _apply_pallas(x: jax.Array, a: jax.Array, b: jax.Array,
                  relu: bool) -> jax.Array:
    """One-HBM-pass apply: grid over (batch, row blocks) of the
    flattened [B, H·W, C] view. a/b ride along as [B, 1, C] arrays in
    (1, 1, C) blocks — the TPU lowering wants a block's last two dims
    tile-aligned or equal to the array's, which a (1, C) block of a
    [B, C] array is not for any B > 1. Under a bound mesh each device
    applies its own batch shard (compat.per_shard)."""
    batch, c = x.shape[0], x.shape[-1]
    x2 = x.reshape(batch, -1, c)

    def call(x2, a3, b3):
        rows = x2.shape[1]
        block = min(APPLY_BLOCK_ROWS, rows)
        row_spec = pl.BlockSpec((1, block, c), lambda i, j: (i, j, 0))
        vec_spec = pl.BlockSpec((1, 1, c), lambda i, j: (i, 0, 0))
        return pl.pallas_call(
            functools.partial(_apply_kernel, relu=relu),
            grid=(x2.shape[0], pl.cdiv(rows, block)),
            in_specs=[row_spec, vec_spec, vec_spec],
            out_specs=row_spec,
            out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
            interpret=_interpret(),
            name="groupnorm_relu",
        )(x2, a3, b3)

    out = per_shard(call, (BATCH_AXES,))(x2, a[:, None, :], b[:, None, :])
    return out.reshape(x.shape)


def _apply_pallas_fwd(x, a, b, relu):
    return _apply_pallas(x, a, b, relu), (x, a, b)


def _apply_pallas_bwd(relu, res, dy):
    # pallas_call has no reverse-mode rule. The backward is the lax
    # composition's own VJP from the kernel's INPUTS — one XLA fusion, and
    # the normalized output is never a residual.
    _, vjp = jax.vjp(functools.partial(_apply_lax, relu=relu), *res)
    return vjp(dy)


_apply_pallas.defvjp(_apply_pallas_fwd, _apply_pallas_bwd)


def fused_groupnorm_relu(x: jax.Array, scale: jax.Array, bias: jax.Array,
                         *, groups: int, eps: float = 1e-6,
                         relu: bool = True,
                         use_pallas: Optional[bool] = None,
                         remat: bool = True) -> jax.Array:
    """GroupNorm (+ optional ReLU) in two HBM passes: one fused stats
    sweep, one fused folded-affine apply. Numerically matches
    ``nn.relu(nn.GroupNorm(num_groups=groups)(x))`` to f32 tolerance.

    ``use_pallas=None`` selects from the devices in use: the Pallas
    kernel on TPU, the lax composition elsewhere. ``True`` forces the
    kernel (interpret mode off-TPU — the unit tests), ``False`` the lax
    path. ``remat=True`` wraps the apply in ``jax.checkpoint`` so
    backward recomputes it instead of keeping the normalized activation
    resident."""
    c = x.shape[-1]
    if c % groups:
        raise ValueError(f"channels {c} not divisible by groups {groups}")
    mean, var = group_stats(x, groups)
    a, b = folded_affine(mean, var, scale, bias, c, eps)

    if use_pallas is None:
        use_pallas = not _interpret()
    fn = _apply_pallas if use_pallas else _apply_lax

    def apply(x, a, b):
        return fn(x, a, b, relu)

    if remat:
        apply = jax.checkpoint(apply)
    return apply(x, a, b)
