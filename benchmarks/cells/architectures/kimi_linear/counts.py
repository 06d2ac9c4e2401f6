"""Kimi Linear's parameters, operations, attention calls, scan calls and
grouped matmuls (KDA layers and latent-attention layers by
``linear_attn_config``; a leading dense layer and sparse layers after it,
each with a shared expert beside its routed ones; an untied head), for ONE
CHIP'S SHARE of a deployment: the experts and vocabulary rows the
configuration file says are held here.

Functions of the configuration file and the traffic file alone, each with its
derivation on one line. No JAX: the run's parent loads this file.
"""

from __future__ import annotations

import counts


def kinds(cfg: dict) -> list:
    """Per layer: ``"kda"`` or ``"mla"`` (``linear_attn_config`` counts from
    1)."""
    lin = cfg["linear_attn_config"]
    return ["kda" if i in lin["kda_layers"] else "mla"
            for i in range(1, cfg["num_hidden_layers"] + 1)]


def dense_layers(cfg: dict) -> int:
    return cfg["first_k_dense_replace"]


def kda_sizes(cfg: dict) -> tuple:
    """(heads, head width, conv taps, chunk)."""
    lin = cfg["linear_attn_config"]
    return (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
            cfg["train"]["kda_chunk"])


def kda_matmul_params(cfg: dict) -> int:
    """The weights that multiply a token in a KDA mixer: W_q, W_k, W_v [d,
    H·K], W_o [H·K, d], the decay's and the gate's low-rank pairs [d, K] and
    [K, H·K], β's W_b [d, H]."""
    d = cfg["hidden_size"]
    h, kd, _, _ = kda_sizes(cfg)
    return 4 * d * h * kd + 2 * (d * kd + kd * h * kd) + d * h


def kda_params(cfg: dict) -> int:
    """The matmuls' weights + three convs' taps [4, H·K] + dt_bias [H·K] +
    A_log [H] + the output norm's scale [K]."""
    h, kd, taps, _ = kda_sizes(cfg)
    return kda_matmul_params(cfg) + 3 * taps * h * kd + h * kd + h + kd


def mla_widths(cfg: dict) -> tuple:
    """(heads, q/k width, v width, latent width, shared key columns)."""
    return (cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"])


def mla_matmul_params(cfg: dict) -> int:
    """W_q [d, H·192] + W_kv_a [d, 512 + 64] + W_kv_b [512, H·(128 + 128)]
    + W_o [H·128, d]."""
    d = cfg["hidden_size"]
    h, qk, dv, rank, rope = mla_widths(cfg)
    return d * h * qk + d * (rank + rope) \
        + rank * h * (cfg["qk_nope_head_dim"] + dv) + h * dv * d


def mla_params(cfg: dict) -> int:
    """The matmuls' weights + the latent's norm scale."""
    return mla_matmul_params(cfg) + cfg["kv_lora_rank"]


def gated_mlp_params(cfg: dict, width: int) -> int:
    """gate, up [d,f] + down [f,d]: the dense MLP, the shared expert, one
    routed expert."""
    return 3 * cfg["hidden_size"] * width


def router_outputs(cfg: dict) -> int:
    """The router keeps the model's published width; the configuration's own
    count is of the experts held here."""
    return cfg["published"]["num_experts"]


def sparse_side_params(cfg: dict) -> int:
    """Beside a sparse layer's routed experts: router [d, E] + the shared
    expert."""
    return cfg["hidden_size"] * router_outputs(cfg) + gated_mlp_params(
        cfg, cfg["num_shared_experts"] * cfg["moe_intermediate_size"])


def total_params(cfg: dict) -> int:
    """Every layer's mixer by its kind + two norm scales; the dense layers'
    MLP; a sparse layer's router, shared expert and the routed experts held;
    embedding [V,d], head [d,V] and the final norm, V the rows held."""
    d = cfg["hidden_size"]
    mixers = sum(kda_params(cfg) if k == "kda" else mla_params(cfg)
                 for k in kinds(cfg))
    sparse = cfg["num_hidden_layers"] - dense_layers(cfg)
    experts = cfg["num_experts"] * gated_mlp_params(
        cfg, cfg["moe_intermediate_size"])
    return (mixers + 2 * d * cfg["num_hidden_layers"]
            + dense_layers(cfg) * gated_mlp_params(cfg,
                                                   cfg["intermediate_size"])
            + sparse * (sparse_side_params(cfg) + experts)
            + 2 * cfg["vocab_size"] * d + d)


def experts_a_token_here(cfg: dict) -> float:
    """Of a token's k choices over E experts the share that meets one of the
    H held here, in expectation under even routing: k * H / E."""
    return cfg["num_experts_per_token"] * cfg["num_experts"] \
        / router_outputs(cfg)


def scan_flops_a_token(cfg: dict) -> dict:
    """Operations a token and head of the chunked gated delta rule, chunk C,
    K = V = head width, the triangles counted at their half: forward, the
    pairs M_qk and M_kk (2 C K), W = V − K_p S_0, O's part from S_0 and the
    state the chunk leaves (3 × 2 K V), U = T Y and M_qk U (2 C V), the
    triangular inverse (C² / 3); backward, the forward's pairs, W, U and
    inverse again (2 C K + 2 K V + C V + C² / 3) and the gradients: dU, dQ_p,
    dK_n, dS_0 twice, dK_p (6 × 2 K V), dM_qk, R = Tᵀ dU, dA, Mᵀ dO (4 C V),
    and the pairs' four (4 C K)."""
    _, kd, _, c = kda_sizes(cfg)
    vd = kd
    fwd = 2 * c * kd + 6 * kd * vd + 2 * c * vd + c * c / 3
    bwd = 6 * c * kd + 14 * kd * vd + 5 * c * vd + c * c / 3
    return {"fwd": fwd, "bwd": bwd}


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward = 2 per weight that multiplies the token (a KDA layer's
    projections and conv taps, the MLA layer's projections, the dense MLP,
    a sparse layer's router and shared expert and k*H/E routed experts in
    expectation, the head's rows held: the work this chip's share needs, not
    the model's) + the MLA layers' QK^T at q/k width and PV at v width over
    the causal triangle (2 flops * H * (192 + 128) * pairs / seq a token) +
    the KDA scan's forward a head. Backward is twice the forward. No
    recomputation, no embedding lookup."""
    d = cfg["hidden_size"]
    h, kd, taps, _ = kda_sizes(cfg)
    heads, qk, dv, _, _ = mla_widths(cfg)
    layer_kinds = kinds(cfg)
    n_kda, n_mla = layer_kinds.count("kda"), layer_kinds.count("mla")
    sparse = cfg["num_hidden_layers"] - dense_layers(cfg)
    weights = n_kda * (kda_matmul_params(cfg) + 3 * taps * h * kd) \
        + n_mla * mla_matmul_params(cfg) \
        + dense_layers(cfg) * gated_mlp_params(cfg, cfg["intermediate_size"]) \
        + sparse * (sparse_side_params(cfg) + experts_a_token_here(cfg)
                    * gated_mlp_params(cfg, cfg["moe_intermediate_size"])) \
        + d * cfg["vocab_size"]
    attention = n_mla * 2 * heads * (qk + dv) * counts.causal_pairs(seq) / seq
    scan = n_kda * h * scan_flops_a_token(cfg)["fwd"]
    return 3.0 * (2 * weights + attention + scan)


def device_tokens(traffic: dict) -> int:
    axes = counts.mesh_axes(traffic)
    return traffic["global_batch"] * traffic["seq"] // (
        max(1, axes.get("dp", 1)) * axes.get("fsdp", 1))


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(the larger of operations over the peak and bytes over the bandwidth,
    which of the two it is)."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), "flops" if t_flops >= t_bytes else "bytes"


def flash_calls(cfg: dict, traffic: dict) -> list:
    """The harness's one-width form: full causal, 32 q heads over 32 kv
    heads at q/k's 192 columns, the MLA layers. v is 128 wide, which that
    form cannot say: the cell's flash reader holds the calls to
    ``mla_flash_needs`` instead."""
    heads, qk, _, _, _ = mla_widths(cfg)
    return [(counts.flash_shard_shape(traffic, heads, heads, qk),
             {"window": None}, kinds(cfg).count("mla"))]


# Matmuls of one flash call a head, by the width they run at (q/k's d or
# v's dv): forward QK^T (d) and PV (dv); dq recomputes QK^T (d), takes dO V^T
# (dv) and dS K (d); dkv recomputes QK^T (d), takes dO V^T (dv), P^T dO (dv)
# and dS^T Q (d).
MLA_MATMULS = {"fwd": (1, 1), "dq": (2, 1), "dkv": (2, 2)}


def mla_flash_needs(cfg: dict, traffic: dict) -> dict:
    """What one flash call of an MLA layer needs: 2 flops * b * h * the
    causal triangle's pairs * (matmuls at d * d + matmuls at dv * dv); bytes,
    bf16 operands read once and results written once: forward reads q, k
    (d) and v (dv) and writes o (dv) and the f32 row statistic; dq reads q,
    k, v, dO and two statistics and writes dq; dkv reads the same and
    writes dk and dv."""
    b, h, hk, s, d = flash_calls(cfg, traffic)[0][0]
    dv = cfg["v_head_dim"]
    pairs = counts.causal_pairs(s)
    q, k, v, o = 2 * b * h * s * d, 2 * b * hk * s * d, \
        2 * b * hk * s * dv, 2 * b * h * s * dv
    stat = 4 * b * h * s
    return {
        "shape": (b, h, hk, s, d, dv),
        "layers": kinds(cfg).count("mla"),
        "flops_a_call": {kind: 2.0 * b * h * pairs * (nd * d + nv * dv)
                         for kind, (nd, nv) in MLA_MATMULS.items()},
        "bytes_a_call": {"fwd": q + k + v + o + stat,
                         "dq": q + k + v + o + 2 * stat + q,
                         "dkv": q + k + v + o + 2 * stat + k + v},
    }


def mla_call_min_seconds(kind: str, needs: dict, peak: dict) -> tuple:
    """(least seconds of one ``fwd``, ``dq`` or ``dkv`` call, which bound
    binds)."""
    return least_seconds(needs["flops_a_call"][kind],
                         needs["bytes_a_call"][kind], peak)


def kda_needs(cfg: dict, traffic: dict) -> dict:
    """What one scan call needs: a call is one KDA layer's scan over all of
    a device's tokens, ``fwd`` (the Mosaic call ``kda_fwd``) or ``bwd``
    (``kda_bwd``). Operations: ``scan_flops_a_token`` times the tokens and
    heads. Bytes a token and head, bf16 q, k, v and float32 decays: forward
    reads q, k [K] and v [V], Γ [K] and β, and writes o [V] and, float32 [K,
    V] a chunk of C tokens, the state the chunk was handed; backward reads
    q, k, v, Γ, β, the states and dO and writes dq, dk, dv, dΓ and dβ."""
    h, kd, _, c = kda_sizes(cfg)
    vd = kd
    tokens = device_tokens(traffic)
    flops = scan_flops_a_token(cfg)
    qkv, gam, beta, state = 2 * (2 * kd + vd), 4 * kd, 4, 4 * kd * vd / c
    return {
        "tokens_a_call": tokens,
        "flops_a_call": {"fwd": tokens * h * flops["fwd"],
                         "bwd": tokens * h * flops["bwd"]},
        "bytes_a_call": {
            "fwd": tokens * h * (qkv + gam + beta + 2 * vd + state),
            "bwd": tokens * h * (2 * qkv + 2 * gam + 2 * beta + 2 * vd
                                 + state)},
        # a step's calls a KDA layer: the forward, the layer's recompute in
        # the backward pass, the backward
        "calls_a_layer": {"fwd": 2, "bwd": 1},
    }


def kda_call_min_seconds(kind: str, needs: dict, peak: dict) -> tuple:
    """(least seconds of one scan call of ``kind``, which bound binds)."""
    return least_seconds(needs["flops_a_call"][kind],
                         needs["bytes_a_call"][kind], peak)


def moe_gmm_needs(cfg: dict, traffic: dict) -> dict:
    """What one grouped matmul call needs, from the rows expected under even
    routing. The program routes ``moe_chunk_tokens`` tokens at a time (all of
    a device's where they are fewer or do not divide); of a chunk's tokens *
    k pairs the share H/E meets an expert here, and every call, whichever of
    gate, up, down, their input gradients (``moe_gmm``) or weight gradients
    (``moe_tgmm``), multiplies those rows through [d,f] or [f,d] of their own
    expert: 2 * rows * d * f operations. Bytes: the rows read in and written
    out in bf16, and every held expert's matrix: read once in bf16 by
    ``gmm``; by ``tgmm`` the float32 running sum over chunks read and the
    float32 result written (the sum is taken inside the kernel)."""
    tokens = device_tokens(traffic)
    chunk = cfg["train"]["moe_chunk_tokens"]
    if tokens % chunk:
        chunk = tokens
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    held = cfg["num_experts"]
    rows = chunk * experts_a_token_here(cfg)
    return {
        "chunks_a_layer": tokens // chunk,
        "rows_a_call": rows,
        "shape": (rows, d, f),
        "flops_a_call": 2.0 * rows * d * f,
        "bytes_a_call": {"gmm": 2 * rows * (d + f) + 2 * held * d * f,
                         "tgmm": 2 * rows * (d + f) + 2 * 4 * held * d * f},
        # a step's calls a chunk and sparse layer: gate, up, down forward;
        # the same again in the chunk's own recompute; three input
        # gradients; three weight gradients
        "calls_a_chunk_and_layer": {"gmm": 9, "tgmm": 3},
    }


def moe_call_min_seconds(kind: str, needs: dict, peak: dict) -> tuple:
    """(least seconds of one ``gmm`` or ``tgmm`` call, which bound binds)."""
    return least_seconds(needs["flops_a_call"], needs["bytes_a_call"][kind],
                         peak)
