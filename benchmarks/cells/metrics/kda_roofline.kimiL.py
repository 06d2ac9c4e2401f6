"""The KDA scan kernels' share of their roofline in the cell ``kimiL.seq32k``:
the calls the device trace names ``kda_fwd*`` and ``kda_bwd*``, each held to
what one call needs (the architecture's ``kda_needs``: the larger of its
operations over the peak and its bytes over the bandwidth), over the device
time they took. Where the trace names no such call, or the architecture has
no such scan, there is nothing to read."""
import named_kernels

NAME, UNIT, SOURCE = "kda_roofline.kimiL", "%", "device_trace"
LAYER, MOVES = "linear-attention mixer", "tokens_per_s_per_chip"
KINDS = ("fwd", "bwd")


def _sums(run):
    arch_counts = named_kernels.architecture_counts(run)
    if not hasattr(arch_counts, "kda_needs"):
        return 0.0, 0.0, {}
    needs = arch_counts.kda_needs(run["config"], run["traffic"])
    least = took = 0.0
    seen = {}
    for kind in KINDS:
        calls, seconds = named_kernels.taken(run, "kda_" + kind)
        one, binds = arch_counts.kda_call_min_seconds(
            kind, needs, named_kernels.peak(run))
        least += calls * one
        took += seconds
        seen[kind] = (calls, binds)
    return least, took, seen


def read(run):
    least, took, _ = _sums(run)
    return named_kernels.share(least, took)


def note(run):
    least, took, seen = _sums(run)
    return (f"least {least:.6f} s of {took:.6f} s; (calls, binding bound) "
            f"{seen}")
