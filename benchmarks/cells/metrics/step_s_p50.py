"""Median step of the window (host clock ended by ``block_until_ready``)."""
NAME, UNIT, SOURCE = "step_s_p50", "s", "host_clock"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    return run["worker"]["window"]["step_s_p50"]
