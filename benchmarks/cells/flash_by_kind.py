"""One flash kernel (forward, dq or dkv) in a cell whose layers differ: the
calls the device trace names ``flash_<kind>*`` held to the full entries of
the architecture's ``flash_calls``, those it names ``flash_win_<kind>*`` to
the windowed ones. ``kernel_roofline`` selects the full name alone and splits
its calls over every entry, which is right only where all layers are of one
kind. Where the trace names no such call there is nothing to read."""

from __future__ import annotations

import counts
import named_kernels


def sums(run: dict, kind: str) -> tuple:
    """(least seconds, seconds taken, calls) of one kernel's calls, full and
    windowed, each held to the attention of its own kind of layer."""
    least = took = calls = 0.0
    for prefix, windowed in (("flash_", False), ("flash_win_", True)):
        needs = named_kernels.flash_needs(run, windowed)
        n, seconds = named_kernels.taken(run, prefix + kind)
        if n and needs:
            least += counts.least_seconds(kind, n, needs,
                                          named_kernels.peak(run))[0]
            took += seconds
            calls += n
    return least, took, calls


def read(run: dict, kind: str):
    least, took, _ = sums(run, kind)
    return named_kernels.share(least, took)


def note(run: dict, kind: str) -> str:
    least, took, calls = sums(run, kind)
    return f"{calls:g} calls, least {least:.6f} s of {took:.6f} s"
