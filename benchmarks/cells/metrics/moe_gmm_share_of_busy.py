"""The grouped matmuls' share of the device's busy time: the device seconds
of the calls the trace names ``moe_gmm*`` and ``moe_tgmm*`` over the union of
all operations' intervals."""
import named_kernels

NAME, UNIT, SOURCE = "moe_gmm_share_of_busy", "%", "device_trace"
LAYER, MOVES = "expert layer", "tokens_per_s_per_chip"


def read(run):
    busy = run["worker"].get("trace", {}).get("busy_s")
    took = sum(named_kernels.taken(run, "moe_" + k)[1]
               for k in ("gmm", "tgmm"))
    return 100.0 * took / busy if busy and took > 0 else None
