"""Peaks, and the operations and bytes a cell's shapes need.

Functions of the configuration file and the traffic file alone. Each has its
derivation on one line. Nothing here reads the program.
"""

from __future__ import annotations

# Published peaks of one chip, keyed by the exact ``device_kind`` JAX
# reports. Source: Google Cloud documentation, "TPU v5e" system architecture
# (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip). A kind that is not a
# key is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peak on record for device_kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg[
        "num_attention_heads"]


def matmul_params(cfg: dict) -> int:
    """Weights that multiply every token: per layer wq [d,q] + wk, wv [d,kv]
    + wo [q,d] + gate, up, down [d,f]; plus the head [d,V]. The embedding
    table is a lookup and is left out."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * head_dim(cfg)
    kv = cfg["num_key_value_heads"] * head_dim(cfg)
    layer = 2 * d * q + 2 * d * kv + 3 * d * f
    return cfg["num_hidden_layers"] * layer + d * cfg["vocab_size"]


def total_params(cfg: dict) -> int:
    """matmul weights + embedding [V,d] + two norm scales a layer + final."""
    d = cfg["hidden_size"]
    return (matmul_params(cfg) + cfg["vocab_size"] * d
            + (2 * cfg["num_hidden_layers"] + 1) * d)


def model_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward = 2 per weight per token + causal attention (QK^T and PV, 2
    flops a multiply-add, half the square): 2*seq*q per layer. Backward is
    twice the forward. No recomputation, no embedding lookup."""
    q = cfg["num_attention_heads"] * head_dim(cfg)
    fwd = 2 * matmul_params(cfg) + cfg["num_hidden_layers"] * 2 * seq * q
    return 3.0 * fwd


def mesh_axes(traffic: dict) -> dict:
    """``"fsdp=2,tp=2"`` -> {"fsdp": 2, "tp": 2}."""
    out = {}
    for part in filter(None, traffic.get("mesh", "").split(",")):
        k, _, v = part.partition("=")
        out[k.strip()] = int(v)
    return out


def flash_shard_shape(cfg: dict, traffic: dict) -> tuple:
    """(batch, q heads, kv heads, seq, head_dim) of one device's kernel call:
    batch over dp*fsdp, heads over tp."""
    axes = mesh_axes(traffic)
    rows = max(1, axes.get("dp", 1)) * axes.get("fsdp", 1)
    tp = axes.get("tp", 1)
    return (traffic["global_batch"] // rows,
            cfg["num_attention_heads"] // tp,
            cfg["num_key_value_heads"] // tp, traffic["seq"], head_dim(cfg))


# Matmuls of [s,d]x[d,s] shape each kernel's algorithm needs per head:
# forward QK^T and PV; dq recomputes QK^T, takes dO V^T and dS K; dkv
# recomputes QK^T, takes dO V^T, P^T dO and dS^T Q.
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_call_flops(kind: str, shape: tuple) -> float:
    """matmuls * 2 flops * b*h*s*s*d, halved for the causal triangle."""
    b, h, _, s, d = shape
    return FLASH_MATMULS[kind] * 2.0 * b * h * s * s * d / 2


def flash_call_bytes(kind: str, shape: tuple) -> float:
    """bf16 operands read once and results written once: forward reads q, k,
    v and writes o (+ f32 row statistics); dq reads q, k, v, dO (+ two f32
    row statistics) and writes dq; dkv reads the same and writes dk, dv."""
    b, h, hk, s, d = shape
    q_like, kv_like, stat = 2 * b * h * s * d, 2 * b * hk * s * d, 4 * b * h * s
    if kind == "fwd":
        return 2 * q_like + 2 * kv_like + stat
    if kind == "dq":
        return 3 * q_like + 2 * kv_like + 2 * stat
    return 2 * q_like + 4 * kv_like + 2 * stat


def flash_call_min_seconds(kind: str, shape: tuple, peak: dict) -> tuple:
    """(least seconds, which bound binds)."""
    t_flops = flash_call_flops(kind, shape) / peak["bf16_flops_per_s"]
    t_bytes = flash_call_bytes(kind, shape) / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), "flops" if t_flops >= t_bytes else "bytes"
