"""The router's share of the device's busy time, every pass (its product, the
top-k and the weights), under ``tony.moe.route``. Joined to the program's
record of its compiled step's scopes (``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "moe_route_share_of_busy", "%", "device_trace"
LAYER, MOVES = "expert layer", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, scopes=("tony.moe.route",))
