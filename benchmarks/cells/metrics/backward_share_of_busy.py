"""The backward pass's share of the device's busy time: the self seconds of the
traced operations whose ``op_name`` lies under ``tony.loss_and_grad`` and a
``transpose(`` and not under jax's ``rematted_computation``, any scope.
Joined to the program's record of its compiled step's scopes
(``scope_times.py``). What a hand-written backward computes again (the expert
layer's layout) is in here, not under ``recompute``."""
import scope_times

NAME, UNIT, SOURCE = "backward_share_of_busy", "%", "device_trace"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, passes=("backward",))
