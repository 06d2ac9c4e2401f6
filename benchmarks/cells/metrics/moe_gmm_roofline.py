"""The grouped matmuls' share of their roofline: the calls the device trace
names ``moe_gmm*`` (forward and input gradient) and ``moe_tgmm*`` (weight
gradient), each held to what one call needs at the rows EXPECTED under even
routing (the architecture's ``moe_gmm_needs``: the larger of its operations
over the peak and its bytes over the bandwidth), over the device time they
took. The rows that came are an operator's counter (``moe_rows_routed``) and
reach no reader; rows of padding and of a fuller expert count against the
kernel, as they should."""
import named_kernels

NAME, UNIT, SOURCE = "moe_gmm_roofline", "%", "device_trace"
LAYER, MOVES = "expert layer", "tokens_per_s_per_chip"


def _sums(run):
    arch_counts = named_kernels.architecture_counts(run)
    if not hasattr(arch_counts, "moe_gmm_needs"):
        return 0.0, 0.0, {}
    needs = arch_counts.moe_gmm_needs(run["config"], run["traffic"])
    least = took = 0.0
    seen = {}
    for kind in ("gmm", "tgmm"):
        calls, seconds = named_kernels.taken(run, "moe_" + kind)
        one, binds = arch_counts.moe_call_min_seconds(
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
