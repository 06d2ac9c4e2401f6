"""XLA's side of the KDA scan's share of the device's busy time in the cell
``kimiL.seq32k``, every pass: what lies under ``tony.kda.scan`` without the
Mosaic calls (``kda_*``: ``kda_share_of_busy.kimiL`` reads those): the
decays' counters, β's layout, the cotangents' sums. Joined to the program's
record of its compiled step's scopes (``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "kda_scan_xla_share_of_busy.kimiL", "%", "device_trace"
LAYER, MOVES = "linear-attention mixer", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, scopes=("tony.kda.scan",), mosaic=False)
