"""The state-space mixer's gate and group norm's share of the device's busy
time, every pass (the copies XLA puts between the square and its sum
included, where they take the scope of their operand), under
``tony.ssm.gate_norm``. Joined to the program's record of its compiled step's
scopes (``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "ssm_gate_norm_share_of_busy", "%", "device_trace"
LAYER, MOVES = "state-space mixer", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, scopes=("tony.ssm.gate_norm",))
