"""The optimizer's share of the device's busy time: the self seconds of the
traced operations under ``tony.optimizer`` (the AdamW update of every leaf).
Joined to the program's record of its compiled step's scopes
(``scope_times.py``). A fusion is one operation under one scope, so where XLA
fuses a leaf's update into its weight gradient's product as an epilogue the
whole fusion reads as the backward pass: the note says how much time lies in
such fusions."""
import scope_times

NAME, UNIT, SOURCE = "optimizer_share_of_busy", "%", "device_trace"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, passes=("optimizer",))


def note(run):
    calls, seconds = scope_times.with_update(run)
    trace = run["worker"]["trace"]
    return (f"besides, {100 * seconds / trace['busy_s']:.2f} % of busy in "
            f"{calls / (trace.get('steps') or 1):.0f} fusions a step of "
            f"another pass that hold a leaf's update")
