"""Nemotron-H's parameters, operations, attention calls, grouped matmuls and
state-space scans (layers of ONE part each: a Mamba-2 mixer, attention
without position embedding, or sparse squared-ReLU experts of two matrices
beside a shared one; an untied head), for ONE CHIP'S SHARE of a deployment:
the experts and vocabulary rows the configuration file says are held here.

Functions of the configuration file and the traffic file alone, each with its
derivation on one line. No JAX: the run's parent loads this file.
"""

from __future__ import annotations

import counts


def letters(cfg: dict, letter: str) -> int:
    """Layers of one kind: ``M`` Mamba-2, ``*`` attention, ``E`` sparse."""
    return cfg["hybrid_override_pattern"].count(letter)


def router_outputs(cfg: dict) -> int:
    """The router keeps the model's published width; the configuration's own
    count is of the experts held here."""
    return cfg["published"]["n_routed_experts"]


def mamba_sizes(cfg: dict) -> tuple:
    """(heads H, head width P, groups G, state N, scan chunk Q)."""
    return (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
            cfg["ssm_state_size"], cfg["chunk_size"])


def mamba_matmul_params(cfg: dict) -> int:
    """W_in [d, H*P (z) + H*P + 2*G*N (x, B, C) + H (dt)] + W_out [H*P, d]."""
    h, p, g, n, _ = mamba_sizes(cfg)
    return cfg["hidden_size"] * (2 * h * p + 2 * g * n + h) \
        + h * p * cfg["hidden_size"]


def mamba_params(cfg: dict) -> int:
    """The matmuls + conv taps and bias over H*P + 2*G*N columns + A_log, D,
    dt_bias [H] + the gated norm's scale [H*P] + the layer's norm [d]."""
    h, p, g, n, _ = mamba_sizes(cfg)
    conv_dim = h * p + 2 * g * n
    return mamba_matmul_params(cfg) + (cfg["conv_kernel"] + 1) * conv_dim \
        + 3 * h + h * p + cfg["hidden_size"]


def attention_matmul_params(cfg: dict) -> int:
    """wq [d, q] + wo [q, d] with q = heads * head_dim, wk, wv [d, kv]."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * cfg["num_attention_heads"] * hd \
        + 2 * d * cfg["num_key_value_heads"] * hd


def relu2_mlp_params(cfg: dict, width: int) -> int:
    """up [d, f] + down [f, d]: the shared expert, one routed expert."""
    return 2 * cfg["hidden_size"] * width


def sparse_side_params(cfg: dict) -> int:
    """Beside a sparse layer's routed experts: router [d, E] + the shared
    expert."""
    return cfg["hidden_size"] * router_outputs(cfg) + relu2_mlp_params(
        cfg, cfg["moe_shared_expert_intermediate_size"])


def total_params(cfg: dict) -> int:
    """A Mamba layer; an attention layer and its norm; a sparse layer's
    router, shared expert, norm and the routed experts held; embedding
    [V, d], head [d, V] and the final norm, V the rows held."""
    d = cfg["hidden_size"]
    experts = cfg["n_routed_experts"] * relu2_mlp_params(
        cfg, cfg["moe_intermediate_size"])
    return (letters(cfg, "M") * mamba_params(cfg)
            + letters(cfg, "*") * (attention_matmul_params(cfg) + d)
            + letters(cfg, "E") * (sparse_side_params(cfg) + d + experts)
            + 2 * cfg["vocab_size"] * d + d)


def experts_a_token_here(cfg: dict) -> float:
    """Of a token's k choices over E experts the share that meets one of the
    H held here, in expectation under even routing: k * H / E."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / router_outputs(cfg)


def scan_flops_a_token(cfg: dict) -> dict:
    """The chunked scan's products a token and Mamba layer, 2 flops a
    multiply-add. Forward: C B^T a group (2*Q*N*G), the chunk's own pairs
    M X a head (2*Q*P*H), the state a chunk leaves and the state it was
    handed read out (2*N*P*H each). Backward as the kernel's algebra has it:
    C B^T again and its two transposes a group (3 * 2*Q*N*G); a head's dM
    and M^T dY (2 * 2*Q*P*H) and five products through the state (C S_in
    again, its two transposes, and the two of the state the chunk leaves:
    5 * 2*N*P*H)."""
    h, p, g, n, q = mamba_sizes(cfg)
    return {"fwd": 2 * q * n * g + 2 * q * p * h + 4 * n * p * h,
            "bwd": 6 * q * n * g + 4 * q * p * h + 10 * n * p * h}


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward = 2 per weight that multiplies the token (a Mamba layer's
    W_in, W_out and conv taps, attention's projections, a sparse layer's
    router and shared expert and k*H/E routed experts in expectation, the
    head's rows held: the work this chip's share needs, not the model's) +
    attention's QK^T and PV over the causal triangle (2 matmuls * 2 flops *
    q width * pairs / seq a token) + the scan's forward products. Backward
    is twice the forward. No recomputation, no embedding lookup."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, p, g, n, _ = mamba_sizes(cfg)
    weights = letters(cfg, "M") * (
        mamba_matmul_params(cfg) + cfg["conv_kernel"] * (h * p + 2 * g * n)) \
        + letters(cfg, "*") * attention_matmul_params(cfg) \
        + letters(cfg, "E") * (
            sparse_side_params(cfg) + experts_a_token_here(cfg)
            * relu2_mlp_params(cfg, cfg["moe_intermediate_size"])) \
        + d * cfg["vocab_size"]
    attention = letters(cfg, "*") * 2 * 2 * cfg["num_attention_heads"] * hd \
        * counts.causal_pairs(seq) / seq
    scan = letters(cfg, "M") * scan_flops_a_token(cfg)["fwd"]
    return 3.0 * (2 * weights + attention + scan)


def flash_calls(cfg: dict, traffic: dict) -> list:
    """One kind of call: full causal, 32 q heads over 2 kv heads."""
    return [(counts.flash_shard_shape(
                traffic, cfg["num_attention_heads"],
                cfg["num_key_value_heads"], cfg["head_dim"]),
             {"window": None}, letters(cfg, "*"))]


def device_tokens(traffic: dict) -> int:
    axes = counts.mesh_axes(traffic)
    return traffic["global_batch"] * traffic["seq"] // (
        max(1, axes.get("dp", 1)) * axes.get("fsdp", 1))


def moe_gmm_needs(cfg: dict, traffic: dict) -> dict:
    """What one grouped matmul call needs, from the rows expected under even
    routing. The program routes ``moe_chunk_tokens`` tokens at a time (all of
    a device's where they are fewer or do not divide); of a chunk's tokens *
    k pairs the share H/E meets an expert here, and every call, whichever of
    up, down, their input gradients (``moe_gmm``) or weight gradients
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
    held = cfg["n_routed_experts"]
    rows = chunk * experts_a_token_here(cfg)
    return {
        "chunks_a_layer": tokens // chunk,
        "rows_a_call": rows,
        "shape": (rows, d, f),
        "flops_a_call": 2.0 * rows * d * f,
        "bytes_a_call": {"gmm": 2 * rows * (d + f) + 2 * held * d * f,
                         "tgmm": 2 * rows * (d + f) + 2 * 4 * held * d * f},
        # a step's calls a chunk and sparse layer, experts of two matrices:
        # up, down forward; the same again in the chunk's own recompute;
        # two input gradients; two weight gradients
        "calls_a_chunk_and_layer": {"gmm": 6, "tgmm": 2},
    }


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple:
    """(the larger of operations over the peak and bytes over the bandwidth,
    which of the two it is)."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), "flops" if t_flops >= t_bytes else "bytes"


def moe_call_min_seconds(kind: str, needs: dict, peak: dict) -> tuple:
    """(least seconds of one ``gmm`` or ``tgmm`` call, which bound binds)."""
    return least_seconds(needs["flops_a_call"], needs["bytes_a_call"][kind],
                         peak)


def ssd_needs(cfg: dict, traffic: dict) -> dict:
    """What one scan call needs: a call is one Mamba layer's scan over all of
    a device's tokens, ``fwd`` (the Mosaic call ``ssd_fwd``) or ``bwd``
    (``ssd_bwd``). Operations: ``scan_flops_a_token`` times the tokens. Bytes
    a token, bf16 activations and float32 decays: forward reads x [H*P], B
    and C [G*N] each, the cumulative log-decays and Δ [H] each, and writes y
    and, float32 [G, N, H/G*P] a chunk of Q tokens, the state the chunk was
    handed; backward reads x, dy, B, C, both decay vectors and the states
    and writes dx, dB, dC and both decay vectors' gradients."""
    h, p, g, n, q = mamba_sizes(cfg)
    tokens = device_tokens(traffic)
    acts, decays = 2 * h * p, 4 * h        # bytes a token
    bc, states = 2 * g * n, 4 * n * h * p // q
    flops = scan_flops_a_token(cfg)
    return {
        "tokens_a_call": tokens,
        "flops_a_call": {"fwd": tokens * flops["fwd"],
                         "bwd": tokens * flops["bwd"]},
        "bytes_a_call": {
            "fwd": tokens * (2 * acts + 2 * bc + 2 * decays + states),
            "bwd": tokens * (3 * acts + 4 * bc + 4 * decays + states)},
        # a step's calls a Mamba layer: the forward, the layer's recompute
        # in the backward pass, the backward
        "calls_a_layer": {"fwd": 2, "bwd": 1},
    }


def ssd_call_min_seconds(kind: str, needs: dict, peak: dict) -> tuple:
    """(least seconds of one scan call of ``kind``, which bound binds)."""
    return least_seconds(needs["flops_a_call"][kind],
                         needs["bytes_a_call"][kind], peak)
