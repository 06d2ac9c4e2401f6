"""The state-space scan kernels' share of the device's busy time in the cell
``nem30b.seq8k``: the device seconds of the calls the trace names ``ssd_*``
over the union of all operations' intervals."""
import named_kernels

NAME, UNIT, SOURCE = "ssd_share_of_busy.nem30b", "%", "device_trace"
LAYER, MOVES = "state-space mixer", "tokens_per_s_per_chip"


def read(run):
    busy = run["worker"].get("trace", {}).get("busy_s")
    took = named_kernels.taken(run, "ssd_")[1]
    return 100.0 * took / busy if busy and took > 0 else None
