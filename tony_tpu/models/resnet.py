"""ResNet (v1.5 bottleneck) — the allreduce-DP parity workload.

The data-parallel image workload (the reference's Horovod ResNet-50 job,
with the gradient allreduce over ICI). TPU-first choices: NHWC layout
(XLA's native conv layout on TPU), bf16 compute, GroupNorm instead of
BatchNorm — no cross-replica batch-stat sync, so pure-DP scaling needs only
the gradient psum and the step stays a single fused XLA program (BatchNorm
would add mutable state + a cross-device mean/var exchange every layer).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp

from tony_tpu.ops.convfuse import fused_groupnorm_relu


@dataclasses.dataclass(frozen=True)
class ResNetConfig:
    stage_sizes: Sequence[int] = (3, 4, 6, 3)   # ResNet-50
    width: int = 64
    num_classes: int = 1000
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    norm_groups: int = 32
    # HBM-aware conv trunk (the conv fusions are HBM-bound, not
    # MXU-bound): each conv→norm→relu chain runs the fused two-pass
    # GroupNorm epilogue (ops/convfuse.py — folded affine, Pallas apply
    # on TPU, remat'd backward) instead of nn.GroupNorm + separate relu.
    # False keeps the original module chain (the parity twin the fused
    # path is tested against).
    fused: bool = True

    @classmethod
    def resnet50(cls, **kw) -> "ResNetConfig":
        return cls(stage_sizes=(3, 4, 6, 3), **kw)

    @classmethod
    def tiny(cls, **kw) -> "ResNetConfig":
        defaults = dict(stage_sizes=(1, 1), width=8, num_classes=10,
                        dtype=jnp.float32, norm_groups=4)
        defaults.update(kw)
        return cls(**defaults)


class _Conv(nn.Module):
    features: int
    kernel: Tuple[int, int]
    strides: Tuple[int, int]
    cfg: ResNetConfig

    @nn.compact
    def __call__(self, x):
        return nn.Conv(
            self.features, self.kernel, self.strides, padding="SAME",
            use_bias=False, dtype=self.cfg.dtype,
            param_dtype=self.cfg.param_dtype,
            # In-channel dim stays unsharded: the stem conv has only 3 input
            # channels, which no mesh axis divides.
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.he_normal(), (None, None, None, "mlp")))(x)


class _Norm(nn.Module):
    cfg: ResNetConfig

    @nn.compact
    def __call__(self, x):
        groups = min(self.cfg.norm_groups, x.shape[-1])
        return nn.GroupNorm(num_groups=groups, dtype=self.cfg.dtype,
                            param_dtype=self.cfg.param_dtype)(x)


class _NormAct(nn.Module):
    """Fused GroupNorm(+ReLU): same params (scale/bias, same shapes and
    leaf order as the _Norm twin) applied through the two-HBM-pass
    fused epilogue. ``relu=False`` for the pre-residual norms."""
    cfg: ResNetConfig
    relu: bool = True

    @nn.compact
    def __call__(self, x):
        groups = min(self.cfg.norm_groups, x.shape[-1])
        scale = self.param("scale", nn.initializers.ones,
                           (x.shape[-1],), self.cfg.param_dtype)
        bias = self.param("bias", nn.initializers.zeros,
                          (x.shape[-1],), self.cfg.param_dtype)
        return fused_groupnorm_relu(x, scale, bias, groups=groups,
                                    relu=self.relu)


class _Bottleneck(nn.Module):
    features: int
    strides: Tuple[int, int]
    cfg: ResNetConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        residual = x
        if cfg.fused:
            y = _Conv(self.features, (1, 1), (1, 1), cfg)(x)
            y = _NormAct(cfg)(y)
            y = _Conv(self.features, (3, 3), self.strides, cfg)(y)
            y = _NormAct(cfg)(y)
            y = _Conv(self.features * 4, (1, 1), (1, 1), cfg)(y)
            y = _NormAct(cfg, relu=False)(y)
            if residual.shape != y.shape:
                residual = _Conv(self.features * 4, (1, 1), self.strides,
                                 cfg)(x)
                residual = _NormAct(cfg, relu=False)(residual)
            return nn.relu(y + residual)
        y = _Conv(self.features, (1, 1), (1, 1), cfg)(x)
        y = nn.relu(_Norm(cfg)(y))
        y = _Conv(self.features, (3, 3), self.strides, cfg)(y)
        y = nn.relu(_Norm(cfg)(y))
        y = _Conv(self.features * 4, (1, 1), (1, 1), cfg)(y)
        y = _Norm(cfg)(y)
        if residual.shape != y.shape:
            residual = _Conv(self.features * 4, (1, 1), self.strides,
                             cfg)(x)
            residual = _Norm(cfg)(residual)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    """Images [B, H, W, 3] → logits [B, num_classes]."""
    cfg: ResNetConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        x = x.astype(cfg.dtype)
        x = _Conv(cfg.width, (7, 7), (2, 2), cfg)(x)
        if cfg.fused:
            x = _NormAct(cfg)(x)
        else:
            x = nn.relu(_Norm(cfg)(x))
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for stage, n_blocks in enumerate(cfg.stage_sizes):
            for block in range(n_blocks):
                strides = (2, 2) if stage > 0 and block == 0 else (1, 1)
                x = _Bottleneck(cfg.width * 2 ** stage, strides, cfg)(x)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(
            cfg.num_classes, dtype=jnp.float32,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.with_logical_partitioning(
                nn.initializers.lecun_normal(), ("embed", "vocab")))(
                    x.astype(jnp.float32))
