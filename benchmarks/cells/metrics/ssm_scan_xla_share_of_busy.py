"""XLA's side of the scan's share of the device's busy time, every pass: what
lies under ``tony.ssm.scan`` without the Mosaic calls (``ssd_*``:
``ssd_share_of_busy.nem30b`` reads those): cumulative sums, the decays'
layouts, splits. Joined to the program's record of its compiled step's scopes
(``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "ssm_scan_xla_share_of_busy", "%", "device_trace"
LAYER, MOVES = "state-space mixer", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, scopes=("tony.ssm.scan",), mosaic=False)
