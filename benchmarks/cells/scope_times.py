"""A traced step's device time by the program's own scopes.

The reduced trace names an operation by its instruction (``fusion.123
fusion bf16[...]``); the program says which pass and scope every instruction
of its compiled step belongs to, once a job, as the span ``user.step_scopes``
(``tony_tpu/profiling/scopes.py``: ``{"<pass>/<scope>": "name name ..."}``,
pass one of forward, backward, recompute, optimizer, other; scope a
``tony.<layer>[.<part>]`` or ``-``), which ``cold_start_breakdown`` hands on
as ``run["spans"]["step_scopes"]``. Joined by the first word of each ``ops``
key, the two give the device's self seconds under any set of passes and
scopes. Where the program records no map (a program older than the record)
or the run has no trace there is nothing to read.

A name of another module run inside the traced window (the feed's) can meet
a name of the step's. One instruction has one result type, so a name the
trace shows under two is such a meeting: ``met`` gives what it can misplace.
"""

from __future__ import annotations

MOSAIC = " tpu_custom_call "
NO_SCOPE = "-"
IN_THE_GRADIENT = ("forward", "backward", "recompute")
#: the key of the traced operations whose name the map lacks: a pass of
#: their own, under no scope
UNMAPPED = "unmapped/" + NO_SCOPE


def table(run: dict):
    """``{"<pass>/<scope>": [calls, seconds, seconds in Mosaic calls]}`` over
    the traced window, ``"unmapped/-"`` among the keys; None without a map
    or without a trace."""
    record = run["spans"].get("step_scopes") or {}
    ops = run["worker"].get("trace", {}).get("ops")
    if not record.get("scopes") or not ops:
        return None
    key_of = {name: key for key, names in record["scopes"].items()
              for name in names.split()}
    out: dict = {}
    for op, (calls, seconds) in ops.items():
        row = out.setdefault(key_of.get(op.split(" ", 1)[0], UNMAPPED),
                             [0.0, 0.0, 0.0])
        row[0] += calls
        row[1] += seconds
        row[2] += seconds if MOSAIC in op else 0.0
    return out


def share(run: dict, passes=None, scopes=None, mosaic: bool = True):
    """Percent of the device's busy time under the keys whose pass is one of
    ``passes`` and whose scope one of ``scopes`` (None: any), the Mosaic
    calls among them left out where ``mosaic`` is false."""
    rows, busy = table(run), run["worker"].get("trace", {}).get("busy_s")
    if rows is None or not busy:
        return None
    took = 0.0
    for key, (_, seconds, in_mosaic) in rows.items():
        which, _, scope = key.partition("/")
        if (passes is None or which in passes) \
                and (scopes is None or scope in scopes):
            took += seconds if mosaic else seconds - in_mosaic
    return 100.0 * took / busy


def unscoped_share(run: dict):
    """Percent of busy time under no layer's scope: scope ``-`` inside
    ``tony.loss_and_grad``, and every operation the map does not hold."""
    return share(run, passes=IN_THE_GRADIENT + ("unmapped",),
                 scopes=(NO_SCOPE,))


def with_update(run: dict) -> tuple:
    """(calls, seconds) of the traced fusions that the map says hold the
    optimizer's operations under another pass's ``op_name``: a weight
    gradient's product with the leaf's update as its epilogue is one
    instruction, and its time is all under the product's scope."""
    names = set((run["spans"].get("step_scopes") or {}).get(
        "with_update", "").split())
    mine = [v for op, v in run["worker"]["trace"]["ops"].items()
            if op.split(" ", 1)[0] in names]
    return sum(v[0] for v in mine), sum(v[1] for v in mine)


def met(run: dict) -> tuple:
    """(names the trace shows under more than one opcode or result type,
    the seconds of all but the longest showing of each): what another
    module's operations can have added to the step's scopes at most."""
    by_name: dict = {}
    for op, (_, seconds) in run["worker"]["trace"]["ops"].items():
        by_name.setdefault(op.split(" ", 1)[0], []).append(seconds)
    twice = [sorted(s) for s in by_name.values() if len(s) > 1]
    return len(twice), sum(sum(s[:-1]) for s in twice)


def report(run: dict) -> str:
    """The whole table of the run, a line a key: milliseconds a step, share
    of busy, calls a step; then what the map held and what it can have
    misplaced."""
    rows, trace = table(run), run["worker"]["trace"]
    steps, busy = trace.get("steps") or 1, trace["busy_s"]
    record = run["spans"]["step_scopes"]
    lines = [f"{key}: {1e3 * s / steps:.3f} ms a step, "
             f"{100 * s / busy:.2f} %, {calls / steps:.0f} calls"
             + (f" ({1e3 * m / steps:.3f} ms in Mosaic calls)" if m else "")
             for key, (calls, s, m) in sorted(rows.items(),
                                              key=lambda kv: -kv[1][1])]
    held = 100.0 * (1 - rows.get(UNMAPPED, [0, 0.0, 0])[1]
                    / sum(r[1] for r in rows.values()))
    calls, seconds = with_update(run)
    lines.append(
        f"fusions of another pass that hold tony.optimizer operations (a "
        f"gradient's product with the leaf's update fused in): "
        f"{1e3 * seconds / steps:.3f} ms a step, {100 * seconds / busy:.2f} "
        f"%, {calls / steps:.0f} calls")
    names, seconds = met(run)
    lines.append(
        f"the map ({record.get('module')}: {record.get('instructions')} "
        f"instructions, {record.get('inherited')} by their operand, "
        f"{record.get('unscoped')} unscoped) held the names of {held:.2f} % "
        f"of the traced device time over {steps} steps; {names} names met "
        f"under two result types (another module's: at most "
        f"{1e3 * seconds / steps:.3f} ms a step misplaced)")
    return "\n  ".join(lines)
