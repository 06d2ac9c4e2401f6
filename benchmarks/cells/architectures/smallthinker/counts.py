"""SmallThinker's parameters, operations, attention calls and grouped matmuls
(every layer sparse experts with no shared expert, a router of the model's
full width read before attention, window and position-free full attention
mixed by layer, a stated head width, an untied head), for ONE CHIP'S SHARE of
a deployment: the experts and vocabulary rows the configuration file says are
held here.

Functions of the configuration file and the traffic file alone, each with its
derivation on one line. No JAX: the run's parent loads this file.
"""

from __future__ import annotations

import counts


def windows(cfg: dict) -> list:
    """Per layer: the window's width, or None for the full causal triangle."""
    return [cfg["sliding_window_size"] if flag else None
            for flag in cfg["sliding_window_layout"]]


def router_outputs(cfg: dict) -> int:
    """The router keeps the model's published width; the configuration's own
    count is of the experts held here."""
    return cfg["published"]["moe_num_primary_experts"]


def attention_params(cfg: dict) -> int:
    """wq [d,q] + wk, wv [d,kv] + wo [q,d], with q = heads * head_dim (which
    need not be d)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return 2 * d * q + 2 * d * kv


def expert_params(cfg: dict) -> int:
    """One expert: gate, up [d,f] + down [f,d]."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def total_params(cfg: dict) -> int:
    """Per layer attention + router [d,E] + two norm scales + the experts
    held; plus embedding [V,d], head [d,V] and the final norm, V the rows
    held."""
    d = cfg["hidden_size"]
    layer = (attention_params(cfg) + d * router_outputs(cfg) + 2 * d
             + cfg["moe_num_primary_experts"] * expert_params(cfg))
    return (cfg["num_hidden_layers"] * layer + 2 * cfg["vocab_size"] * d + d)


def experts_a_token_here(cfg: dict) -> float:
    """Of a token's k choices over E experts the share that meets one of the
    H held here, in expectation under even routing: k * H / E."""
    return (cfg["moe_num_active_primary_experts"]
            * cfg["moe_num_primary_experts"] / router_outputs(cfg))


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward = 2 per weight that multiplies the token (attention, router,
    the head's rows held, and k*H/E experts a layer in expectation: the work
    this chip's share needs, not the model's) + attention's QK^T and PV over
    the pairs each layer's mask keeps (2 matmuls * 2 flops * q width * pairs
    / seq a token). Backward is twice the forward. No recomputation, no
    embedding lookup."""
    d = cfg["hidden_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    weights = cfg["num_hidden_layers"] * (
        attention_params(cfg) + d * router_outputs(cfg)
        + experts_a_token_here(cfg) * expert_params(cfg)) \
        + d * cfg["vocab_size"]
    pairs = sum(counts.causal_pairs(seq, w) for w in windows(cfg))
    return 3.0 * (2 * weights + 2 * 2 * q * pairs / seq)


def flash_shard_shape(cfg: dict, traffic: dict) -> tuple:
    return counts.flash_shard_shape(
        traffic, cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["head_dim"])


def flash_calls(cfg: dict, traffic: dict) -> list:
    """One entry a kind of layer: the full causal layers, then the windowed
    ones (a window that covers the sequence is the full call)."""
    shape, seq = flash_shard_shape(cfg, traffic), traffic["seq"]
    full = sum(1 for w in windows(cfg) if w is None or w >= seq)
    out = [(shape, {"window": None}, full)] if full else []
    banded = len(windows(cfg)) - full
    if banded:
        out.append((shape, {"window": cfg["sliding_window_size"]}, banded))
    return out


def moe_gmm_needs(cfg: dict, traffic: dict) -> dict:
    """What one grouped matmul call needs, from the rows expected under even
    routing. The program routes ``moe_chunk_tokens`` tokens at a time (all of
    a device's where they are fewer or do not divide); of a chunk's tokens *
    k pairs the share H/E meets an expert here, and every call, whichever of
    gate, up, down, their input gradients (``moe_gmm``) or weight gradients
    (``moe_tgmm``), multiplies those rows through [d,f] or [f,d] of their own
    expert: 2 * rows * d * f operations. Bytes: the rows read in and written
    out in bf16, and every held expert's matrix once (bf16 read by ``gmm``,
    float32 written by ``tgmm``)."""
    axes = counts.mesh_axes(traffic)
    tokens = traffic["global_batch"] * traffic["seq"] // (
        max(1, axes.get("dp", 1)) * axes.get("fsdp", 1))
    chunk = cfg["train"]["moe_chunk_tokens"]
    if tokens % chunk:
        chunk = tokens
    d, f = cfg["hidden_size"], cfg["moe_ffn_hidden_size"]
    held = cfg["moe_num_primary_experts"]
    rows = chunk * experts_a_token_here(cfg)
    return {
        "chunks_a_layer": tokens // chunk,
        "rows_a_call": rows,
        "shape": (rows, d, f),
        "flops_a_call": 2.0 * rows * d * f,
        "bytes_a_call": {"gmm": 2 * rows * (d + f) + 2 * held * d * f,
                         "tgmm": 2 * rows * (d + f) + 4 * held * d * f},
        # a step's calls a chunk and layer: gate, up, down forward; the same
        # again in the chunk's own recompute (the block's recompute needs
        # none: a chunk keeps its inputs alone); three input gradients;
        # three weight gradients
        "calls_a_chunk_and_layer": {"gmm": 9, "tgmm": 3},
    }


def moe_call_min_seconds(kind: str, needs: dict, peak: dict) -> tuple:
    """(least seconds of one ``gmm`` or ``tgmm`` call, which bound binds)."""
    t_flops = needs["flops_a_call"] / peak["bf16_flops_per_s"]
    t_bytes = needs["bytes_a_call"][kind] / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), "flops" if t_flops >= t_bytes else "bytes"
