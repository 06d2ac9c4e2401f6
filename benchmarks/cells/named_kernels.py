"""Calls and device seconds of the Mosaic kernels a trace shows under a
name's prefix, and the share of a roofline or of the device's busy time they
come to. The program names its kernels (``pallas_call(name=)``); the compiler
numbers them (``moe_gmm.12``). Where the trace names no such operation, as
with a program that lacks the kernel, there is nothing to read."""

from __future__ import annotations

import arch
import counts


def taken(run: dict, prefix: str) -> tuple:
    """(calls, device seconds) of the Mosaic calls whose instruction name
    begins ``prefix``."""
    ops = run["worker"].get("trace", {}).get("ops", {})
    mine = [v for name, v in ops.items()
            if name.startswith(prefix) and " tpu_custom_call " in name]
    return sum(v[0] for v in mine), sum(v[1] for v in mine)


def peak(run: dict) -> dict:
    return counts.peaks(run["worker"]["device"]["kind"])


def architecture_counts(run: dict):
    return arch.load(run["architecture"], "counts")


def flash_sums(run: dict, prefix: str, needs: list) -> tuple:
    """(least seconds, seconds taken, calls) of the flash calls named
    ``<prefix>fwd*``, ``<prefix>dq*`` and ``<prefix>dkv*``, every call held to
    the attention ``needs`` ([(shape, mask, layers)]) of its own kind of
    layer. Nothing where there are no such needs."""
    least = took = calls = 0.0
    for kind in ("fwd", "dq", "dkv") if needs else ():
        n, seconds = taken(run, prefix + kind)
        if n:
            least += counts.least_seconds(kind, n, needs, peak(run))[0]
        took += seconds
        calls += n
    return least, took, calls


def flash_needs(run: dict, windowed: bool) -> list:
    """The architecture's ``flash_calls`` of one kind: windowed or full."""
    return [n for n in architecture_counts(run).flash_calls(
        run["config"], run["traffic"])
        if (n[1]["window"] is not None) == windowed]


def share(least: float, took: float):
    return 100.0 * least / took if took > 0 else None
