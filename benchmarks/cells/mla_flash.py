"""The flash calls of a latent-attention layer held to its own widths: q and
k ``d`` wide, v ``dv`` (the architecture's ``mla_flash_needs`` and
``mla_call_min_seconds``; the larger of a call's operations over the peak and
its bytes over the bandwidth). The harness's ``counts.flash_call_flops``
takes one width for q, k and v alike, so the readers of the other cells
would hold v to q's width. Where the trace names no such call, or the
architecture says no latent attention's needs, there is nothing to read."""

from __future__ import annotations

import named_kernels

KINDS = ("fwd", "dq", "dkv")


def sums(run: dict, kinds=KINDS) -> tuple:
    """(least seconds, seconds taken, {kind: (calls, binding bound)}) of the
    calls the trace names ``flash_<kind>*``, for each of ``kinds``."""
    arch_counts = named_kernels.architecture_counts(run)
    if not hasattr(arch_counts, "mla_flash_needs"):
        return 0.0, 0.0, {}
    needs = arch_counts.mla_flash_needs(run["config"], run["traffic"])
    least = took = 0.0
    seen = {}
    for kind in kinds:
        calls, seconds = named_kernels.taken(run, "flash_" + kind)
        one, binds = arch_counts.mla_call_min_seconds(
            kind, needs, named_kernels.peak(run))
        least += calls * one
        took += seconds
        seen[kind] = (calls, binds)
    return least, took, seen


def read(run: dict, kinds=KINDS):
    least, took, _ = sums(run, kinds)
    return named_kernels.share(least, took)


def note(run: dict, kinds=KINDS) -> str:
    least, took, seen = sums(run, kinds)
    return (f"least {least:.6f} s of {took:.6f} s; (calls, binding bound) "
            f"{seen}")
