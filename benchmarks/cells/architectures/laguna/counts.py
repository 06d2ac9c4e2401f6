"""Laguna's parameters, operations, attention calls and grouped matmuls (a
leading dense layer and sparse layers after it, each with a shared expert
beside its routed ones; head counts that differ by layer; a per-head output
gate; window and full attention mixed by layer; an untied head), for ONE
CHIP'S SHARE of a deployment: the heads, experts and vocabulary rows the
configuration file says are held here.

Functions of the configuration file and the traffic file alone, each with its
derivation on one line. No JAX: the run's parent loads this file.
"""

from __future__ import annotations

import counts


def windows(cfg: dict) -> list:
    """Per layer: the window's width, or None for the full causal triangle."""
    return [cfg["sliding_window"] if kind == "sliding_attention" else None
            for kind in cfg["layer_types"]]


def sparse_layers(cfg: dict) -> int:
    return sum(kind == "sparse" for kind in cfg["mlp_layer_types"])


def router_outputs(cfg: dict) -> int:
    """The router keeps the model's published width; the configuration's own
    count is of the experts held here."""
    return cfg["published"]["num_experts"]


def attention_params(cfg: dict, heads: int) -> int:
    """A layer of ``heads`` q heads: wq [d,q] + wo [q,d] with q = heads *
    head_dim, wk, wv [d,kv], and the gate's wg [d,heads]."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * heads * hd + 2 * d * cfg["num_key_value_heads"] * hd \
        + d * heads


def gated_mlp_params(cfg: dict, width: int) -> int:
    """gate, up [d,f] + down [f,d]: the dense MLP, the shared expert, one
    routed expert."""
    return 3 * cfg["hidden_size"] * width


def sparse_side_params(cfg: dict) -> int:
    """Beside a sparse layer's routed experts: router [d,E] + the shared
    expert."""
    return cfg["hidden_size"] * router_outputs(cfg) \
        + gated_mlp_params(cfg, cfg["shared_expert_intermediate_size"])


def total_params(cfg: dict) -> int:
    """Every layer's attention at its own head count + two norm scales; the
    dense layers' MLP; a sparse layer's router, shared expert and the routed
    experts held; embedding [V,d], head [d,V] and the final norm, V the rows
    held."""
    d = cfg["hidden_size"]
    attention = sum(attention_params(cfg, h) + 2 * d
                    for h in cfg["num_attention_heads_per_layer"])
    sparse = sparse_layers(cfg)
    dense = cfg["num_hidden_layers"] - sparse
    experts = cfg["num_experts"] * gated_mlp_params(
        cfg, cfg["moe_intermediate_size"])
    return (attention + dense * gated_mlp_params(cfg, cfg["intermediate_size"])
            + sparse * (sparse_side_params(cfg) + experts)
            + 2 * cfg["vocab_size"] * d + d)


def experts_a_token_here(cfg: dict) -> float:
    """Of a token's k choices over E experts the share that meets one of the
    H held here, in expectation under even routing: k * H / E."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / router_outputs(cfg)


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward = 2 per weight that multiplies the token (each layer's
    attention at the heads held, the dense MLP, a sparse layer's router and
    shared expert and k*H/E routed experts in expectation, the head's rows
    held: the work this chip's share needs, not the model's) + attention's
    QK^T and PV over the pairs each layer's mask keeps at that layer's q
    width (2 matmuls * 2 flops * q width * pairs / seq a token). Backward is
    twice the forward. No recomputation, no embedding lookup."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads = cfg["num_attention_heads_per_layer"]
    sparse = sparse_layers(cfg)
    weights = sum(attention_params(cfg, h) for h in heads) \
        + (cfg["num_hidden_layers"] - sparse) * gated_mlp_params(
            cfg, cfg["intermediate_size"]) \
        + sparse * (sparse_side_params(cfg) + experts_a_token_here(cfg)
                    * gated_mlp_params(cfg, cfg["moe_intermediate_size"])) \
        + d * cfg["vocab_size"]
    attention = sum(2 * 2 * h * hd * counts.causal_pairs(seq, w) / seq
                    for h, w in zip(heads, windows(cfg), strict=True))
    return 3.0 * (2 * weights + attention)


def flash_calls(cfg: dict, traffic: dict) -> list:
    """One entry a kind of call, each at its own layers' head count: the
    full causal layers, then the windowed ones (a window that covers the
    sequence is the full call)."""
    seq = traffic["seq"]
    layers: dict = {}
    for h, w in zip(cfg["num_attention_heads_per_layer"], windows(cfg),
                    strict=True):
        kind = (w is not None and w < seq, h)       # (windowed, q heads)
        layers[kind] = layers.get(kind, 0) + 1
    return [(counts.flash_shard_shape(
                traffic, h, cfg["num_key_value_heads"], cfg["head_dim"]),
             {"window": cfg["sliding_window"] if windowed else None}, n)
            for (windowed, h), n in sorted(layers.items())]


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
    axes = counts.mesh_axes(traffic)
    tokens = traffic["global_batch"] * traffic["seq"] // (
        max(1, axes.get("dp", 1)) * axes.get("fsdp", 1))
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
        # the same again in the chunk's own recompute (the block's recompute
        # needs none: a chunk keeps its inputs alone); three input
        # gradients; three weight gradients
        "calls_a_chunk_and_layer": {"gmm": 9, "tgmm": 3},
    }


def moe_call_min_seconds(kind: str, needs: dict, peak: dict) -> tuple:
    """(least seconds of one ``gmm`` or ``tgmm`` call, which bound binds)."""
    t_flops = needs["flops_a_call"] / peak["bf16_flops_per_s"]
    t_bytes = needs["bytes_a_call"][kind] / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), "flops" if t_flops >= t_bytes else "bytes"
