"""The state-space mixer's input and output projections' share of the device's
busy time, every pass, under ``tony.ssm.in_proj`` and ``tony.ssm.out_proj``.
Joined to the program's record of its compiled step's scopes
(``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "ssm_proj_share_of_busy", "%", "device_trace"
LAYER, MOVES = "state-space mixer", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(
        run, scopes=("tony.ssm.in_proj", "tony.ssm.out_proj"))
