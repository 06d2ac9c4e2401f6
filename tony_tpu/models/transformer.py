"""Decoder-only transformer (llama-family architecture), TPU-first.

The model every cell of the benchmark and the Llama-3-8B example build.
Design choices map straight onto TPU hardware:

- every weight carries logical axes (``embed``/``mlp``/``heads``/``vocab``)
  so `tony_tpu.parallel` can lay it out on any dp/fsdp/tp/sp mesh;
- bf16 activations (MXU-native), f32 params and softmax statistics;
- attention is pluggable: Pallas flash kernel (default), ring attention for
  sequence-parallel long context, Ulysses, or the XLA reference;
- static shapes and `remat`-friendly block structure (scan over layers is
  deliberately NOT used so pipeline stages can slice layers later);
- one loop over layers that may differ: ``TransformerConfig.layers`` says
  of each its mixer (attention, latent attention without RoPE (``MLA``), a
  state-space mixer, ``models/ssm.py``, a linear-attention mixer,
  ``models/kda.py``, or none) and its feed-forward (the dense ``MLP``, sparse experts,
  ``models/moe.py``, or none); of an attention mixer its mask (full causal
  or a window), its head counts, its RoPE (none, or a ``RopeSpec``: θ, the
  share of a head's columns rotated, a YaRN table) and whether its output
  is gated per head. Every part that is there has one norm before it and
  one residual around it, so a layer may be one part alone. Without
  ``layers`` every layer is the default: attention, then the dense MLP;
- four multipliers (Granite 4.0's): on the embedding's rows, on every part's
  output before its residual add, the softmax's scale in place of
  ``head_dim^-½``, and a divisor of the logits. Each is the identity when
  unset, and then adds no operation to the step.

Spans (``jax.named_scope``, one where each layer's work happens, so that a
device trace reads by layer: ``profiling/scopes.py``): ``tony.embed``,
``tony.norm``, ``tony.attn.proj`` (q, k, v and the output projection;
latent attention's ``W_q``, ``W_kv_a``, the latent's norm, ``W_kv_b``, the
key's layout and ``W_o``), ``tony.attn.rope``, ``tony.attn.core`` (the kernel call and the layouts
around it), ``tony.attn.gate``, ``tony.mlp``, ``tony.loss_head`` (both losses,
and the head of the full-logits path); the state-space mixer's are in
``models/ssm.py``, the KDA mixer's in ``models/kda.py``, the experts' in
``models/moe.py``. A multiplier lives
in the scope of what it scales: the embedding's under ``tony.embed``, a
residual branch's under its part's (``tony.attn.proj``, ``tony.ssm.out_proj``,
``tony.mlp``, ``tony.moe.combine``), the logits' under ``tony.loss_head``,
the softmax's scale inside the kernel under ``tony.attn.core``. Counters,
sown into ``intermediates`` and reduced by ``layer_counters``:
``attn_gate_mean``, ``ssm_dt_mean``, ``ssm_decay_mean``,
``ssm_head_rms_max_over_median``, ``kda_decay_mean``, ``kda_log_decay_min``,
``kda_beta_mean``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tony_tpu.models.kda import KDAMixer, KDASpec
from tony_tpu.models.moe import ExpertLayer, ExpertSpec, moe_counters
from tony_tpu.models.ssm import SSMixer, SSMSpec
from tony_tpu.ops import quant
from tony_tpu.ops.attention import (FLASH_RESIDUAL_NAMES, flash_attention,
                                    reference_attention)
from tony_tpu.ops.ring import ring_attention
from tony_tpu.ops.ulysses import ulysses_attention


@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN's blend of interpolated and original RoPE frequencies, as
    Hugging Face's ``_compute_yarn_parameters`` has it: positions stretched
    by ``factor`` from ``original_max_position``, the frequencies that turn
    more than ``beta_fast`` times in the original context kept, those that
    turn fewer than ``beta_slow`` times divided by ``factor``, a linear ramp
    between; cos and sin are multiplied by ``attention_factor``."""
    factor: float
    original_max_position: int
    attention_factor: float
    beta_fast: float = 32.0
    beta_slow: float = 1.0


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    """A layer's rotary embedding: base ``theta``, the leading share
    ``rotated`` of a head's columns it turns (the rest pass through), and an
    optional YaRN table."""
    theta: float
    rotated: float = 1.0
    yarn: Optional[Yarn] = None

    def table(self, head_dim: int):
        """(inverse frequencies of the rotated columns' pairs, the factor on
        cos and sin). Plain RoPE's frequencies are the expression they have
        always been; YaRN's table is worked out once, in numpy, at trace
        time."""
        rot = int(head_dim * self.rotated)
        if self.yarn is None:
            return self.theta ** (
                -jnp.arange(0, rot, 2, dtype=jnp.float32) / rot), 1.0
        y = self.yarn

        def correction_dim(turns):
            return rot * math.log(y.original_max_position
                                  / (turns * 2 * math.pi)) \
                / (2 * math.log(self.theta))

        low = max(math.floor(correction_dim(y.beta_fast)), 0)
        high = min(math.ceil(correction_dim(y.beta_slow)), rot - 1)
        if low == high:
            high += 0.001
        extrapolated = 1.0 / self.theta ** (
            np.arange(0, rot, 2, dtype=np.float64) / rot)
        ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0, 1)
        inv_freq = extrapolated / y.factor * ramp + extrapolated * (1 - ramp)
        return jnp.asarray(inv_freq, jnp.float32), float(y.attention_factor)


@dataclasses.dataclass(frozen=True)
class MLASpec:
    """Latent attention without RoPE (``mla_use_nope``): ``n_heads`` heads
    whose q and k are ``qk_nope + qk_rope`` wide and whose v is ``v_dim``;
    keys and values come from one latent of ``kv_rank`` columns a token
    (RMS-normed), the last ``qk_rope`` columns of each key shared by every
    head and turned by no rotary embedding."""
    n_heads: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    kv_rank: int


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer of the stack: a mixer, a feed-forward, or both, each
    ``x + part(norm(x))``. ``mixer``: ``"attention"``, an ``SSMSpec`` (the
    state-space mixer of those sizes) or None. Of an attention mixer,
    ``window=w``: query i attends keys i − w < j ≤ i (None: the full causal
    triangle). ``rope``: True is RoPE at ``cfg.rope_theta`` over the whole
    head, False no position embedding on this layer's q and k, a
    ``RopeSpec`` the layer's own. ``n_heads``: the layer's q heads over
    ``cfg.n_kv_heads`` (None: the config's). ``gate``: attention's output is
    scaled, a head and token, by the sigmoid of a projection ``wg`` of the
    layer's normed input. ``mixer`` may also be a ``KDASpec`` (the
    linear-attention mixer of ``models/kda.py``) or an ``MLASpec`` (latent
    attention, ``MLA``: full causal, its own head count and widths, no
    position embedding; ``window``, ``rope``, ``n_heads`` and ``gate`` are
    the plain attention's). ``feed_forward``: False leaves the layer without
    one; otherwise ``experts`` is the sparse feed-forward in place of the
    dense ``MLP`` (None: dense, at ``cfg.mlp_dim``)."""
    window: Optional[int] = None
    rope: Union[bool, RopeSpec] = True
    experts: Optional[ExpertSpec] = None
    n_heads: Optional[int] = None
    gate: bool = False
    mixer: Union[str, SSMSpec, KDASpec, MLASpec, None] = "attention"
    feed_forward: bool = True

    def __post_init__(self):
        if not (self.mixer in (None, "attention")
                or isinstance(self.mixer, (SSMSpec, KDASpec, MLASpec))):
            raise ValueError(f"mixer {self.mixer!r} is neither 'attention', "
                             f"an SSMSpec, a KDASpec, an MLASpec nor None")
        if self.mixer is None and not self.feed_forward:
            raise ValueError("a layer without a mixer and without a "
                             "feed-forward has no part")
        if self.experts is not None and not self.feed_forward:
            raise ValueError("experts are a feed-forward: feed_forward=False "
                             "names none")


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    mlp_dim: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16          # activations
    param_dtype: jnp.dtype = jnp.float32
    attn_impl: str = "flash"                 # flash | ring | ulysses | xla
    remat: bool = True
    # Name of a jax.checkpoint_policies policy for remat, honoured to the
    # letter, e.g. "dots_with_no_batch_dims_saveable" (save matmul outputs,
    # recompute only cheap elementwise/norm ops — ~the full-remat memory
    # win at a fraction of the recompute FLOPs). None → each block is
    # recomputed except the flash forward kernel: its outputs o (activation
    # dtype, [B, S, n_heads·head_dim]: one more hidden state) and lse (f32
    # [B, n_heads, 8, S]: 32·n_heads bytes a token, 1/8 of a bf16 o at
    # head_dim 128) stay live per layer beside the block's input, so the
    # backward does not run flash_fwd a second time (attn_impl flash and
    # ulysses; ring and xla have nothing tagged and keep nothing).
    # "nothing_saveable" keeps nothing: every block recomputed whole.
    remat_policy: Optional[str] = None
    # Flash kernel tile sizes (see ops/attention.py block sweep notes).
    attn_block_q: int = 1024
    attn_block_k: int = 1024
    tie_embeddings: bool = False
    # LM-head matmul dtype; None → activation dtype (bf16 on TPU: the
    # [dim, vocab] projection is ~20% of model FLOPs and f32 runs at half
    # the MXU rate — loss softmax stays f32 downstream either way).
    lm_head_dtype: Optional[jnp.dtype] = None
    # Opt-in quantized matmul path for the attention/MLP projections
    # (tony.train.matmul-dtype): "int8" | "fp8_e4m3" | None. Forward-only
    # symmetric per-channel quantization (ops/quant.py) on wq/wk/wv/wo and
    # gate/up/down; the embedding and LM head stay in bf16/f32 (they set
    # the loss scale). None keeps the exact nn.Dense path — bitwise
    # identical to the pre-quantization model. An unsupported backend
    # degrades to bf16 with a one-time beacon warning.
    matmul_dtype: Optional[str] = None
    # A head's width where the model states one (q width n_heads·head_dim
    # need not equal dim); None derives dim // n_heads.
    head_dim: Optional[int] = None
    # One LayerSpec a layer, read by Transformer's one loop; None makes
    # every layer the default (full causal, RoPE, dense MLP).
    layers: Optional[Tuple[LayerSpec, ...]] = None
    # Multipliers, each the identity when unset (no operation is added): the
    # embedding's rows times ``embedding_multiplier`` (under tony.embed);
    # every part's output times ``residual_multiplier`` before its residual
    # add (in float32, under the part's own scope); ``attention_multiplier``
    # the softmax's scale (None: head_dim^-½); the logits divided by
    # ``logits_scaling`` (under tony.loss_head; ``chunked_causal_lm_loss``
    # takes it as an argument).
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0

    def __post_init__(self):
        if self.layers is not None and len(self.layers) != self.n_layers:
            raise ValueError(f"{len(self.layers)} layer specs for "
                             f"n_layers={self.n_layers}")

    @property
    def head_size(self) -> int:
        return self.head_dim or self.dim // self.n_heads

    def layer(self, i: int) -> LayerSpec:
        return self.layers[i] if self.layers is not None else LayerSpec()

    @classmethod
    def llama3_8b(cls, **kw) -> "TransformerConfig":
        """Llama-3-8B geometry (public: 32L, 4096d, 32h/8kv, 14336 mlp,
        128k vocab). Overrides win, so a depth or vocabulary cut is one
        call: ``llama3_8b(n_layers=2)``."""
        defaults = dict(vocab_size=128256, dim=4096, n_layers=32,
                        n_heads=32, n_kv_heads=8, mlp_dim=14336,
                        rope_theta=500000.0)
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def tiny(cls, **kw) -> "TransformerConfig":
        """CI-sized config for the fake mesh (SURVEY.md §4 test strategy)."""
        defaults = dict(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, mlp_dim=128, max_seq_len=128,
                        dtype=jnp.float32, remat=False)
        defaults.update(kw)
        return cls(**defaults)


def remat_policy_of(cfg: TransformerConfig):
    """The ``jax.checkpoint`` policy of a block's remat (``cfg.remat`` set):
    the ``jax.checkpoint_policies`` member ``cfg.remat_policy`` names, or,
    with none named, the one that keeps the flash forward's tagged o and lse
    and recomputes everything else."""
    if cfg.remat_policy:
        return getattr(jax.checkpoint_policies, cfg.remat_policy)
    return jax.checkpoint_policies.save_only_these_names(
        *FLASH_RESIDUAL_NAMES)


def _dense(cfg: TransformerConfig, feats: int, axes, name: str) -> nn.Module:
    return quant.dense(feats, axes, name, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype,
                       matmul_dtype=cfg.matmul_dtype)


def _sp_offset() -> jax.Array:
    """Shard index on the sp axis, or 0 when not under shard_map (init /
    single-shard apply trace the model outside any mesh axis context). A
    shard_map with a differently-named sequence axis raises instead of
    silently restarting positions at 0 (see ops.ring.bound_axis_size)."""
    from tony_tpu.ops.ring import bound_axis_size

    if bound_axis_size("sp") is None:
        return jnp.zeros((), jnp.int32)
    return jax.lax.axis_index("sp")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _turn(x: jax.Array, cos: jax.Array, sin: jax.Array,
          half: int) -> jax.Array:
    """``x·cos + (x R)·sin`` in f32, rounded once to x's dtype: ``R`` the
    constant 0/1 matrix that swaps the two halves of x's leading ``2·half``
    columns and zeroes the others, so ``x R`` is exact (each output is one
    input times 1)."""
    d = x.shape[-1]
    swap = np.zeros((d, d), np.float32)
    pairs = np.arange(half)
    swap[pairs + half, pairs] = swap[pairs, pairs + half] = 1.0
    swapped = jnp.einsum("...d,de->...e", x, jnp.asarray(swap, x.dtype),
                         preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST)
    return (x.astype(jnp.float32) * cos + swapped * sin).astype(x.dtype)


def _turn_fwd(x, cos, sin, half):
    return _turn(x, cos, sin, half), (cos, sin)


def _turn_bwd(half, tables, dy):
    # The transpose turns back by the same angles: (dy·sin) Rᵀ = (dy R)·(−sin)
    # for R = Rᵀ and sin's halves of opposite signs. So the backward is the
    # forward's one pass, rounded once, where autodiff would round dy·cos
    # and (dy·sin) Rᵀ to x's dtype apart and then add them.
    cos, sin = tables
    return _turn(dy, cos, -sin, half), None, None


_turn.defvjp(_turn_fwd, _turn_bwd)


def _rope(x: jax.Array, positions: jax.Array, rope: RopeSpec) -> jax.Array:
    """Rotary position embedding on [B, S, H, D]; f32 trig and arithmetic,
    cast back. Each head turns as one row of D columns, never as two
    halves: a tensor half a head wide fills half a lane tile, and the
    compiler then lays q and k out sequence-minor, with f32 relayout copies
    on both sides of the rotation. So ``out = x·cos' + (x R)·sin'``
    (``_turn``), with full-width tables over the columns, ``cos' = [cos,
    cos, 1 …]`` and ``sin' = [−sin, sin, 0 …]`` (YaRN's factor on the
    rotated columns alone; [B, S, 1, D], broadcast over heads): the
    columns past the rotated ones pass through."""
    d = x.shape[-1]
    freqs, factor = rope.table(d)
    half = freqs.shape[0]
    kept = d - 2 * half
    freqs = jnp.concatenate([freqs, freqs, jnp.zeros(kept, jnp.float32)])
    on_cos = np.array([factor] * 2 * half + [1.0] * kept, np.float32)
    on_sin = np.array([-factor] * half + [factor] * half + [0.0] * kept,
                      np.float32)
    angles = positions[:, :, None, None].astype(jnp.float32) * freqs
    return _turn(x, jnp.cos(angles) * on_cos, jnp.sin(angles) * on_sin, half)


class RMSNorm(nn.Module):
    eps: float
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        scale = self.param(
            "scale", nn.with_logical_partitioning(nn.initializers.ones,
                                                  ("norm",)),
            (x.shape[-1],), self.param_dtype)
        with jax.named_scope("tony.norm"):
            var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                           keepdims=True)
            y = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
            return (y * scale).astype(x.dtype)


class Attention(nn.Module):
    cfg: TransformerConfig
    spec: LayerSpec = LayerSpec()

    @nn.compact
    def __call__(self, x, positions):
        cfg, spec = self.cfg, self.spec
        head_dim = cfg.head_size
        n_heads = spec.n_heads or cfg.n_heads
        n_kv_heads = cfg.n_kv_heads
        b, s, _ = x.shape
        # Plain Dense with a fused (heads·head_dim) output: the fused dim is
        # heads-major, so sharding it over tp == sharding heads over tp.
        # (DenseGeneral flattens multi-dim kernels before calling
        # kernel_init, which breaks 3-axis logical metadata.)
        with jax.named_scope("tony.attn.proj"):
            q = _dense(cfg, n_heads * head_dim, ("embed", "heads"), "wq")(
                x).reshape(b, s, n_heads, head_dim)
            k = _dense(cfg, n_kv_heads * head_dim, ("embed", "kv_heads"),
                       "wk")(x).reshape(b, s, n_kv_heads, head_dim)
            v = _dense(cfg, n_kv_heads * head_dim, ("embed", "kv_heads"),
                       "wv")(x).reshape(b, s, n_kv_heads, head_dim)
        if spec.rope:
            rope = RopeSpec(cfg.rope_theta) if spec.rope is True \
                else spec.rope
            with jax.named_scope("tony.attn.rope"):
                q = _rope(q, positions, rope)
                k = _rope(k, positions, rope)
        # What is left around the kernel: the head layouts, the
        # transposes in and out, and the kernel call itself.
        with jax.named_scope("tony.attn.core"):
            q = nn.with_logical_constraint(q, ("batch", "seq", "heads", "kv"))
            k, v = (nn.with_logical_constraint(
                t, ("batch", "seq", "kv_heads", "kv")) for t in (k, v))

            if cfg.attn_impl == "flash":
                o = flash_attention(q, k, v, causal=True,
                                    scale=cfg.attention_multiplier,
                                    block_q=cfg.attn_block_q,
                                    block_k=cfg.attn_block_k,
                                    window=spec.window)
            elif cfg.attn_impl == "xla":
                g = n_heads // n_kv_heads
                o = reference_attention(q, jnp.repeat(k, g, axis=2),
                                        jnp.repeat(v, g, axis=2),
                                        causal=True,
                                        scale=cfg.attention_multiplier,
                                        window=spec.window)
            elif cfg.attn_impl == "ring":
                # GQA-native: K/V ride the ring at kv-head width (no repeat).
                # Ring and Ulysses refuse a window.
                o = ring_attention(q, k, v, axis_name="sp", causal=True,
                                   scale=cfg.attention_multiplier,
                                   block_q=cfg.attn_block_q,
                                   block_k=cfg.attn_block_k,
                                   window=spec.window)
            elif cfg.attn_impl == "ulysses":
                o = ulysses_attention(q, k, v, axis_name="sp", causal=True,
                                      scale=cfg.attention_multiplier,
                                      block_q=cfg.attn_block_q,
                                      block_k=cfg.attn_block_k,
                                      window=spec.window)
            else:
                raise ValueError(f"unknown attn_impl {cfg.attn_impl!r}")
            o = nn.with_logical_constraint(o, ("batch", "seq", "heads", "kv"))
        if spec.gate:
            # One scalar a head and token, from what q, k and v are read
            # from. The projection is H columns wide: it stays outside the
            # quantized path, in the activation dtype.
            with jax.named_scope("tony.attn.gate"):
                gate = jax.nn.sigmoid(quant.dense(
                    n_heads, ("embed", "heads"), "wg", dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, matmul_dtype=None)(
                        x).astype(jnp.float32))
                self.sow("intermediates", "attn_gate_mean", jnp.mean(gate))
                o = (o.astype(jnp.float32) * gate[..., None]).astype(o.dtype)
        with jax.named_scope("tony.attn.proj"):
            o = o.reshape(b, s, n_heads * head_dim)
            return _dense(cfg, cfg.dim, ("heads", "embed"), "wo")(o)


class MLA(nn.Module):
    """Latent attention without RoPE, expanded: ``q = n W_q`` ``[S, H,
    qk_nope + qk_rope]``; ``[c | k_r] = n W_kv_a``, ``c`` RMS-normed
    (``kv_norm``); ``[k_n | v] = c W_kv_b`` a head; ``k = [k_n | k_r]``, k_r
    the same for every head; causal softmax at ``(qk_nope + qk_rope)^−½``
    (or ``cfg.attention_multiplier``) over values ``v_dim`` wide; ``out =
    concat_h(a_h) W_o``. The projections, the latent's norm and the layouts
    of k are under ``tony.attn.proj``, the kernel call under
    ``tony.attn.core``."""
    cfg: TransformerConfig
    spec: MLASpec

    @nn.compact
    def __call__(self, x):
        cfg, spec = self.cfg, self.spec
        b, s, _ = x.shape
        h, qk = spec.n_heads, spec.qk_nope + spec.qk_rope
        with jax.named_scope("tony.attn.proj"):
            q = _dense(cfg, h * qk, ("embed", "heads"), "wq")(x).reshape(
                b, s, h, qk)
            latent = _dense(cfg, spec.kv_rank + spec.qk_rope,
                            ("embed", "rank"), "wkv_a")(x)
            c, k_r = jnp.split(latent, (spec.kv_rank,), axis=-1)
            c32 = c.astype(jnp.float32)
            c = (c32 * jax.lax.rsqrt(jnp.mean(jnp.square(c32), axis=-1,
                                              keepdims=True) + cfg.norm_eps)
                 * self.param("kv_norm", nn.with_logical_partitioning(
                     nn.initializers.ones, ("norm",)), (spec.kv_rank,),
                     cfg.param_dtype)).astype(cfg.dtype)
            kv = _dense(cfg, h * (spec.qk_nope + spec.v_dim),
                        ("rank", "heads"), "wkv_b")(c).reshape(
                            b, s, h, spec.qk_nope + spec.v_dim)
            k_n, v = jnp.split(kv, (spec.qk_nope,), axis=-1)
            k = jnp.concatenate([k_n, jnp.broadcast_to(
                k_r[:, :, None, :], (b, s, h, spec.qk_rope))], axis=-1)
        with jax.named_scope("tony.attn.core"):
            q, k, v = (nn.with_logical_constraint(
                t, ("batch", "seq", "heads", "kv")) for t in (q, k, v))
            if cfg.attn_impl == "flash":
                o = flash_attention(q, k, v, causal=True,
                                    scale=cfg.attention_multiplier,
                                    block_q=cfg.attn_block_q,
                                    block_k=cfg.attn_block_k)
            elif cfg.attn_impl == "xla":
                o = reference_attention(q, k, v, causal=True,
                                        scale=cfg.attention_multiplier)
            else:
                raise ValueError(f"attn_impl {cfg.attn_impl!r}: latent "
                                 f"attention runs as flash or xla")
            o = nn.with_logical_constraint(o, ("batch", "seq", "heads", "kv"))
        with jax.named_scope("tony.attn.proj"):
            return _dense(cfg, cfg.dim, ("heads", "embed"), "wo")(
                o.reshape(b, s, h * spec.v_dim))


class MLP(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        with jax.named_scope("tony.mlp"):
            gate = _dense(cfg, cfg.mlp_dim, ("embed", "mlp"), "gate")(x)
            up = _dense(cfg, cfg.mlp_dim, ("embed", "mlp"), "up")(x)
            h = nn.silu(gate) * up
            h = nn.with_logical_constraint(h, ("batch", "seq", "mlp"))
            return _dense(cfg, cfg.dim, ("mlp", "embed"), "down")(h)


class Block(nn.Module):
    cfg: TransformerConfig
    spec: LayerSpec = LayerSpec()

    @nn.compact
    def __call__(self, x, positions):
        cfg, spec = self.cfg, self.spec

        def norm(name):
            return RMSNorm(cfg.norm_eps, cfg.param_dtype, name=name)

        def branch(scope, y):
            """A part's output as its residual add takes it."""
            if cfg.residual_multiplier == 1.0:
                return y
            with jax.named_scope(scope):
                return (y.astype(jnp.float32)
                        * cfg.residual_multiplier).astype(y.dtype)

        n, h = None, x
        if spec.mixer == "attention":
            n = norm("attn_norm")(x)
            h = x + branch("tony.attn.proj",
                           Attention(cfg, spec, name="attn")(n, positions))
        elif isinstance(spec.mixer, MLASpec):
            n = norm("attn_norm")(x)
            h = x + branch("tony.attn.proj",
                           MLA(cfg, spec.mixer, name="mla")(n))
        elif spec.mixer is not None:
            if cfg.attn_impl in ("ring", "ulysses"):
                kind = "state-space" if isinstance(spec.mixer, SSMSpec) \
                    else "KDA"
                raise ValueError(
                    f"attn_impl {cfg.attn_impl!r} splits the sequence over "
                    f"the sp axis, and {self.name}'s {kind} mixer hands its "
                    f"state along the whole row (a state-space or a KDA "
                    f"mixer: neither is split over the sequence)")
            if isinstance(spec.mixer, SSMSpec):
                h = x + branch("tony.ssm.out_proj", SSMixer(
                    spec.mixer, cfg.dtype, cfg.param_dtype,
                    cfg.matmul_dtype or "", cfg.norm_eps,
                    name="ssm")(norm("ssm_norm")(x)))
            else:
                h = x + branch("tony.kda.out_proj", KDAMixer(
                    spec.mixer, cfg.dtype, cfg.param_dtype,
                    cfg.matmul_dtype or "", cfg.norm_eps,
                    name="kda")(norm("kda_norm")(x)))
        out = h
        if spec.feed_forward:
            m = norm("mlp_norm")(h)
            if spec.experts is None:
                out = h + branch("tony.mlp", MLP(cfg, name="mlp")(m))
            else:
                # The router may read what attention reads (the normed
                # block input); the experts read the normed state after the
                # mixer.
                before = spec.experts.route_before_attention and n is not None
                out = h + branch("tony.moe.combine", ExpertLayer(
                    spec.experts, cfg.dtype, cfg.param_dtype,
                    cfg.matmul_dtype or "", name="moe")(n if before else m,
                                                        m))
        return nn.with_logical_constraint(out, ("batch", "seq", "embed"))


class Transformer(nn.Module):
    """Causal LM: tokens [B, S] int32 → logits [B, S, vocab]."""
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, positions=None, return_hidden=False):
        """``return_hidden=True`` skips the LM head and returns the
        final-norm hidden states [B, S, D] — pair with
        ``chunked_causal_lm_loss`` for long context, where the full
        [B, S, vocab] logits tensor (4 GB f32 at 32k×32000) is the
        memory wall, not the attention."""
        cfg = self.cfg
        global_seq = tokens.shape[1]
        if cfg.attn_impl in ("ring", "ulysses"):
            # Under sequence-parallel shard_map this trace sees only the
            # local chunk; the RoPE-extrapolation guard must apply to the
            # GLOBAL sequence = local · sp-shards.
            from tony_tpu.ops.ring import bound_axis_size

            n_sp = bound_axis_size("sp")
            if n_sp is not None:
                global_seq = global_seq * n_sp
        if global_seq > cfg.max_seq_len:
            raise ValueError(
                f"global sequence length {global_seq} exceeds max_seq_len "
                f"{cfg.max_seq_len} (RoPE would extrapolate)")
        if positions is None:
            pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)
            if cfg.attn_impl in ("ring", "ulysses"):
                # Sequence-parallel: the model runs inside shard_map over
                # "sp" and sees only its local chunk — RoPE needs global
                # positions, offset by the shard index (0 under init or a
                # single-shard apply, where no sp axis is bound).
                pos = pos + _sp_offset() * tokens.shape[1]
            positions = jnp.broadcast_to(pos[None, :], tokens.shape)
        # The table gets its own logical names: sharding its vocab dim over
        # BOTH model axes (and leaving the embed dim whole) lets SPMD
        # partition the lookup as masked-gather + all-reduce; an
        # embed-sharded table instead makes the gather output embed-sharded
        # and the reshard to batch-sharded activations is an "involuntary
        # full rematerialization" in the partitioner (XLA b/433785288).
        emb = self.param(
            "embedding", nn.with_logical_partitioning(
                nn.initializers.normal(0.02), ("vocab_table", "embed_table")),
            (cfg.vocab_size, cfg.dim), cfg.param_dtype)
        with jax.named_scope("tony.embed"):
            x = emb[tokens]
            if cfg.embedding_multiplier != 1.0:
                x = x * cfg.embedding_multiplier
            x = x.astype(cfg.dtype)
            x = nn.with_logical_constraint(x, ("batch", "seq", "embed"))
        block = Block
        if cfg.remat:
            # prevent_cse MUST stay True here: layers are a Python loop
            # (deliberately — see module docstring), not a lax.scan, and
            # prevent_cse=False is only sound inside scan/while bodies
            # where XLA cannot CSE across the loop boundary. With False,
            # XLA merged each block's recomputation with its forward and
            # silently un-remat'ed the model — measured on v5e: the 317M
            # flagship at batch 8 / seq 8192 compiled to an identical
            # 21.33 GB HBM footprint with remat on and off; with True the
            # same config fits in 9.8 GB.
            block = nn.remat(Block, prevent_cse=True,
                             policy=remat_policy_of(cfg))
        for i in range(cfg.n_layers):
            x = block(cfg, cfg.layer(i), name=f"layer_{i}")(x, positions)
        x = RMSNorm(cfg.norm_eps, cfg.param_dtype, name="final_norm")(x)
        if return_hidden:
            return x
        head_dtype = cfg.lm_head_dtype or cfg.dtype
        with jax.named_scope("tony.loss_head"):
            if cfg.tie_embeddings:
                logits = jnp.einsum("bsd,vd->bsv", x.astype(head_dtype),
                                    emb.astype(head_dtype),
                                    preferred_element_type=jnp.float32)
            else:
                logits = nn.Dense(
                    cfg.vocab_size, use_bias=False, dtype=head_dtype,
                    param_dtype=cfg.param_dtype, name="lm_head",
                    kernel_init=nn.with_logical_partitioning(
                        nn.initializers.lecun_normal(), ("embed", "vocab")))(
                            x.astype(head_dtype))
            logits = logits.astype(jnp.float32)
            if cfg.logits_scaling != 1.0:
                logits = logits / cfg.logits_scaling
            return logits


def layer_counters(intermediates) -> dict:
    """What the layers sowed, as one dict of scalars for a step's aux
    metrics: the expert layers' counters (``moe_counters``) and the means
    over the layers that sowed them of ``attn_gate_mean`` (the per-head
    output gates, over heads and tokens), ``ssm_dt_mean`` and
    ``ssm_decay_mean`` (a state-space mixer's steps Δ and decays
    ``exp(Δ·A)``, over heads and tokens),
    ``ssm_head_rms_max_over_median`` (of a mixer's heads' scan outputs, the
    largest RMS over the median), ``kda_decay_mean`` and ``kda_beta_mean``
    (a KDA mixer's α over channels and tokens, its β); and the least over
    the layers of ``kda_log_decay_min``. {} where nothing was sown."""
    out = moe_counters(intermediates)
    for name in ("attn_gate_mean", "ssm_dt_mean", "ssm_decay_mean",
                 "ssm_head_rms_max_over_median", "kda_decay_mean",
                 "kda_log_decay_min", "kda_beta_mean"):
        sown = [value for path, value in
                jax.tree_util.tree_leaves_with_path(intermediates)
                if any(getattr(k, "key", None) == name for k in path)]
        if sown:
            reduce = jnp.min if name == "kda_log_decay_min" else jnp.mean
            out[name] = reduce(jnp.stack(sown))
    return out


@jax.named_scope("tony.loss_head")
def causal_lm_loss(logits: jax.Array, tokens: jax.Array,
                   mask: Optional[jax.Array] = None) -> jax.Array:
    """Next-token cross entropy; logits [B,S,V] predict tokens shifted.

    Computed as logsumexp − picked-logit rather than via log_softmax: the
    reductions fuse into passes over the logits, where log_softmax would
    materialize a second [B,S,V] f32 tensor (1 GB at the bench shape) just
    to gather one column from it."""
    targets = tokens[:, 1:]
    logits = logits[:, :-1].astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - picked
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)
    return jnp.mean(nll)


@jax.named_scope("tony.loss_head")
def chunked_causal_lm_loss(hidden: jax.Array, head_kernel: jax.Array,
                           tokens: jax.Array, chunk_size: int = 4096,
                           mask: Optional[jax.Array] = None,
                           head_dtype: Optional[jnp.dtype] = None,
                           seq_axis_name: str = "sp",
                           logits_scaling: float = 1.0) -> jax.Array:
    """Next-token cross entropy without ever materializing [B, S, vocab].

    The long-context memory wall is not attention (flash streams it) but
    the logits: at 32k×32000 vocab the f32 logits plus their cotangent are
    ~8 GB — more than the whole remat'd model. This computes the loss a
    sequence chunk at a time: ``hidden`` [B, S, D] (from
    ``Transformer(..., return_hidden=True)``) is scanned in [B, C, D]
    chunks, each projected through ``head_kernel`` [D, V], reduced to
    (Σnll, count), and rematerialized in backward (``jax.checkpoint``), so
    peak residency is O(B·C·V) — chunk_size trades HBM for recompute.

    Exactly equals ``causal_lm_loss(model(tokens), tokens)`` for the
    untied head (same logsumexp−picked formulation; the matmul runs in
    ``head_dtype`` — pass ``cfg.lm_head_dtype`` if you set it; default =
    the activation dtype, matching ``nn.Dense(dtype=...)``). For
    ``tie_embeddings=True`` pass ``emb.T`` as the kernel; note the tied
    full path additionally accumulates in f32
    (``preferred_element_type``), so equality there is to bf16-matmul
    tolerance, not bitwise. ``logits_scaling`` divides each chunk's float32
    logits, as ``TransformerConfig.logits_scaling`` does on the full path.

    Not sequence-parallel: under a sequence shard_map the per-shard
    sequence shift would misalign targets at shard boundaries, so this
    raises — compute hidden states inside the shard_map, gather, and take
    the loss outside (or keep the loss on the full-logits path). The guard
    probes ``seq_axis_name`` (default ``"sp"``) — meshes with a custom
    sequence axis name must pass it through, or the probe (which also
    checks the other standard mesh axes — ``bound_axis_size`` raises on a
    misnamed axis) cannot see the sharding.
    """
    from tony_tpu.ops.ring import bound_axis_size

    if bound_axis_size(seq_axis_name) is not None:
        raise ValueError(
            f"chunked_causal_lm_loss inside a {seq_axis_name!r} shard_map "
            "would shift targets per-shard (wrong at every shard boundary) "
            "and skip the cross-shard mean; compute it outside the "
            "shard_map")
    if hidden.shape[1] != tokens.shape[1]:
        # A sequence mismatch is the signature of per-shard hidden states
        # meeting full tokens (or vice versa) — the exact wrong-loss bug
        # the shard_map guard exists to stop, caught even when the axis
        # name didn't match the probe.
        raise ValueError(
            f"hidden seq {hidden.shape[1]} != tokens seq {tokens.shape[1]} "
            "— per-shard hidden states with full-sequence tokens? Gather "
            "hidden states before the loss (or pass seq_axis_name)")
    x = hidden[:, :-1]
    t = tokens[:, 1:]
    b, s, d = x.shape
    if s == 0:
        return jnp.float32(0.0)     # degenerate S=1: no next-token pairs
    valid = jnp.ones((b, s), jnp.float32) if mask is None \
        else mask[:, 1:].astype(jnp.float32)
    chunk_size = min(chunk_size, s)
    pad = (-s) % chunk_size
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        t = jnp.pad(t, ((0, 0), (0, pad)))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    nc = x.shape[1] // chunk_size
    xs = x.reshape(b, nc, chunk_size, d).transpose(1, 0, 2, 3)
    ts = t.reshape(b, nc, chunk_size).transpose(1, 0, 2)
    ms = valid.reshape(b, nc, chunk_size).transpose(1, 0, 2)

    hd = head_dtype or hidden.dtype

    @jax.checkpoint
    def chunk_stats(xc, tc, mc):
        logits = (xc.astype(hd)
                  @ head_kernel.astype(hd)).astype(jnp.float32)
        if logits_scaling != 1.0:
            logits = logits / logits_scaling
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, tc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - picked) * mc), jnp.sum(mc)

    def body(carry, args):
        tot, cnt = carry
        dn, dc = chunk_stats(*args)
        return (tot + dn, cnt + dc), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (xs, ts, ms))
    return tot / jnp.maximum(cnt, 1.0)
