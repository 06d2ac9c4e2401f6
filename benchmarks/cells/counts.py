"""Peaks, and the operations and bytes an attention call's shape needs.

What is the same for every architecture: the table of peaks, the mesh a
traffic file names, and a causal attention kernel's operations and bytes from
its per-device shape and its mask. What a model's shapes are, how many
parameters it has and how many operations a token costs is the
architecture's to say, in ``architectures/<model_type>/counts.py``
(``arch.py``). No architecture is named in this code. Each function has its
derivation on one line. Nothing here reads the program or imports JAX.
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


def mesh_axes(traffic: dict) -> dict:
    """``"fsdp=2,tp=2"`` -> {"fsdp": 2, "tp": 2}."""
    out = {}
    for part in filter(None, traffic.get("mesh", "").split(",")):
        k, _, v = part.partition("=")
        out[k.strip()] = int(v)
    return out


def flash_shard_shape(traffic: dict, heads: int, kv_heads: int,
                      head_dim: int) -> tuple:
    """(batch, q heads, kv heads, seq, head_dim) of one device's kernel call:
    batch over dp*fsdp, heads over tp."""
    axes = mesh_axes(traffic)
    rows = max(1, axes.get("dp", 1)) * axes.get("fsdp", 1)
    tp = axes.get("tp", 1)
    return (traffic["global_batch"] // rows, heads // tp, kv_heads // tp,
            traffic["seq"], head_dim)


# Matmuls of [s,d]x[d,s] shape each kernel's algorithm needs per head:
# forward QK^T and PV; dq recomputes QK^T, takes dO V^T and dS K; dkv
# recomputes QK^T, takes dO V^T, P^T dO and dS^T Q.
FLASH_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def causal_pairs(s: int, window=None) -> float:
    """Query-key pairs one head's causal mask keeps, as an area: the triangle
    s*s/2; where a query sees only keys i-w < j <= i and w < s, the triangle
    less the one of side s-w that the window cuts off, s*w - w*w/2."""
    if window is None or window >= s:
        return s * s / 2
    return s * window - window * window / 2


def flash_call_flops(kind: str, shape: tuple, window=None) -> float:
    """matmuls * 2 flops * b*h*d * the pairs the mask keeps."""
    b, h, _, s, d = shape
    return FLASH_MATMULS[kind] * 2.0 * b * h * causal_pairs(s, window) * d


def flash_call_bytes(kind: str, shape: tuple) -> float:
    """bf16 operands read once and results written once: forward reads q, k,
    v and writes o (+ f32 row statistics); dq reads q, k, v, dO (+ two f32
    row statistics) and writes dq; dkv reads the same and writes dk, dv. A
    window leaves them as they are: every row is still read once."""
    b, h, hk, s, d = shape
    q_like, kv_like, stat = 2 * b * h * s * d, 2 * b * hk * s * d, 4 * b * h * s
    if kind == "fwd":
        return 2 * q_like + 2 * kv_like + stat
    if kind == "dq":
        return 3 * q_like + 2 * kv_like + 2 * stat
    return 2 * q_like + 4 * kv_like + 2 * stat


def flash_call_min_seconds(kind: str, shape: tuple, peak: dict,
                           window=None) -> tuple:
    """(least seconds, which bound binds)."""
    t_flops = flash_call_flops(kind, shape, window) / peak["bf16_flops_per_s"]
    t_bytes = flash_call_bytes(kind, shape) / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), "flops" if t_flops >= t_bytes else "bytes"


def least_seconds(kind: str, calls: float, needs: list, peak: dict) -> tuple:
    """(least seconds, which bound binds) of ``calls`` calls of one kernel
    seen in a trace. ``needs`` is what the architecture's ``flash_calls``
    says a step's attention is: ``[(per-device shape, mask, layers)]``, a
    mask being ``{"window": w}`` with ``None`` for the full causal triangle.
    A trace does not say which layer a call served, so where the list has
    more than one entry the calls split in its ratio of layers. That is the
    work the mathematics needs, whatever kernel did it."""
    layers = sum(n for _, _, n in needs)
    least, binds = 0.0, []
    for shape, mask, n in needs:
        seconds, bound = flash_call_min_seconds(kind, shape, peak,
                                                mask["window"])
        least += calls * n / layers * seconds
        binds.append(bound)
    return least, "/".join(dict.fromkeys(binds))
