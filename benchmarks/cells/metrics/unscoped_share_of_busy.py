"""The share of the device's busy time that lies under no layer's scope: the
traced operations whose ``op_name`` is under ``tony.loss_and_grad`` and no
``tony.<layer>`` scope, and every traced operation whose name the program's
record of its compiled step does not hold (``scope_times.py``). Its note is
the run's whole table by pass and scope."""
import scope_times

NAME, UNIT, SOURCE = "unscoped_share_of_busy", "%", "device_trace"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    return scope_times.unscoped_share(run)


def note(run):
    return scope_times.report(run)
