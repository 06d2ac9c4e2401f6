"""Collective device time during which no other operation runs on that
device, over the traced window."""
NAME, UNIT, SOURCE = "collective_exposed_share", "%", "device_trace"
LAYER, MOVES = "collectives", "tokens_per_s_per_chip"


def read(run):
    trace = run["worker"]["trace"]
    if not trace.get("collective_s"):
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]


def note(run):
    trace = run["worker"]["trace"]
    return (f"collectives {trace['collective_s']:.6f} s, exposed "
            f"{trace['collective_exposed_s']:.6f} s of "
            f"{trace['window_s']:.6f} s")
