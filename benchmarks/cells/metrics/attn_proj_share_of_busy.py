"""Attention's q, k, v and output projections' share of the device's busy time,
every pass, under ``tony.attn.proj``. Joined to the program's record of its
compiled step's scopes (``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "attn_proj_share_of_busy", "%", "device_trace"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, scopes=("tony.attn.proj",))
