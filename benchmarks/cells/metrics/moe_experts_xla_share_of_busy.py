"""XLA's side of the experts' products' share of the device's busy time, every
pass: what lies under ``tony.moe.experts`` without the Mosaic calls
(``moe_gmm*``, ``moe_tgmm*``: ``moe_gmm_share_of_busy`` reads those): the row
passes, ``act(gate) · up`` and its transpose, the segments' copies. Joined to
the program's record of its compiled step's scopes (``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "moe_experts_xla_share_of_busy", "%", "device_trace"
LAYER, MOVES = "expert layer", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, scopes=("tony.moe.experts",), mosaic=False)
