"""The share of the device's busy time in the expert layer's layout, token order
and dispatch gather, every pass, under ``tony.moe.dispatch``. Joined to the
program's record of its compiled step's scopes (``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "moe_dispatch_share_of_busy", "%", "device_trace"
LAYER, MOVES = "expert layer", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, scopes=("tony.moe.dispatch",))
