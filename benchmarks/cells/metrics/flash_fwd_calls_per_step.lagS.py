"""How many times one traced step runs a flash forward kernel in the cell
``lagS.seq8k``: the Mosaic calls named ``flash_fwd*`` and ``flash_win_fwd*``
(``flash_by_kind.sums``) over the whole steps traced. One a layer, five here,
where the backward keeps the forward's o and lse; two a layer where the
block's remat runs the kernel again to get them back."""
import flash_by_kind

NAME, UNIT, SOURCE = "flash_fwd_calls_per_step.lagS", "count", "device_trace"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    steps = run["worker"].get("trace", {}).get("steps")
    calls = flash_by_kind.sums(run, "fwd")[2]
    return calls / steps if steps and calls else None


def note(run):
    calls = flash_by_kind.sums(run, "fwd")[2]
    return f"{calls:g} calls in {run['worker']['trace'].get('steps')} steps"
