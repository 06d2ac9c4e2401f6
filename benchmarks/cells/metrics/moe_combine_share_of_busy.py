"""The share of the device's busy time in the expert layer's combine, every pass
(the weighted gather back and its transpose), under ``tony.moe.combine``.
Joined to the program's record of its compiled step's scopes
(``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "moe_combine_share_of_busy", "%", "device_trace"
LAYER, MOVES = "expert layer", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, scopes=("tony.moe.combine",))
