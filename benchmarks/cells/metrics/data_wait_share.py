"""Share of the window spent in ``next(batches)``."""
NAME, UNIT, SOURCE = "data_wait_share", "%", "host_clock"
LAYER, MOVES = "input pipeline", "tokens_per_s_per_chip"


def read(run):
    window = run["worker"]["window"]
    return 100.0 * window["data_wait_s"] / window["seconds"]
