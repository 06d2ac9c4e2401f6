"""95th percentile step of the window, or the highest that still has a
sample beyond it."""
NAME, UNIT, SOURCE = "step_s_p95", "s", "host_clock"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    return run["worker"]["window"]["step_s_p95"]


def note(run):
    window = run["worker"]["window"]
    return (f"{window['steps']} steps, "
            f"{window['step_s_p95_samples_beyond']} beyond the one reported")
