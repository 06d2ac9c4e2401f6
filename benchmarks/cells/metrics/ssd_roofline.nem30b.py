"""The state-space scan kernels' share of their roofline in the cell
``nem30b.seq8k``: the calls the device trace names ``ssd_fwd*`` and
``ssd_bwd*``, each held to what one call needs (the architecture's
``ssd_needs``: the larger of its operations over the peak and its bytes over
the bandwidth), over the device time they took. Where the trace names no such
call, or the architecture has no scan, there is nothing to read."""
import named_kernels

NAME, UNIT, SOURCE = "ssd_roofline.nem30b", "%", "device_trace"
LAYER, MOVES = "state-space mixer", "tokens_per_s_per_chip"
KINDS = ("fwd", "bwd")


def _sums(run):
    arch_counts = named_kernels.architecture_counts(run)
    if not hasattr(arch_counts, "ssd_needs"):
        return 0.0, 0.0, {}
    needs = arch_counts.ssd_needs(run["config"], run["traffic"])
    least = took = 0.0
    seen = {}
    for kind in KINDS:
        calls, seconds = named_kernels.taken(run, "ssd_" + kind)
        one, binds = arch_counts.ssd_call_min_seconds(
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
