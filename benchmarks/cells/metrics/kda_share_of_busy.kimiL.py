"""The KDA scan kernels' share of the device's busy time in the cell
``kimiL.seq32k``: the device seconds of the calls the trace names ``kda_*``
over the union of all operations' intervals."""
import named_kernels

NAME, UNIT, SOURCE = "kda_share_of_busy.kimiL", "%", "device_trace"
LAYER, MOVES = "linear-attention mixer", "tokens_per_s_per_chip"


def read(run):
    busy = run["worker"].get("trace", {}).get("busy_s")
    took = named_kernels.taken(run, "kda_")[1]
    return 100.0 * took / busy if busy and took > 0 else None
