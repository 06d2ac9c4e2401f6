"""1 − the union of the device's operation intervals over the traced window.
"""
NAME, UNIT, SOURCE = "device_idle_share", "%", "device_trace"
LAYER, MOVES = "device", "tokens_per_s_per_chip"


def read(run):
    trace = run["worker"]["trace"]
    if not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
