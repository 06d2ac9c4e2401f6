"""The loss head's share of the device's busy time, every pass: the head's
products, the log-sum-exp, their recompute and their backward, under
``tony.loss_head``. Joined to the program's record of its compiled step's
scopes (``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "loss_head_share_of_busy", "%", "device_trace"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, scopes=("tony.loss_head",))
