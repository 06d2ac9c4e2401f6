"""The share of the device's busy time that ``jax.checkpoint`` spends running
forward operations again in the backward pass (the blocks' remat, the loss
head's chunks): ``op_name``s that hold ``rematted_computation``, any scope.
Joined to the program's record of its compiled step's scopes
(``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "recompute_share_of_busy", "%", "device_trace"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, passes=("recompute",))
