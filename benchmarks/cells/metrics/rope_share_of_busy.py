"""RoPE's share of the device's busy time, every pass (the rotation of q and k,
its splits and copies), under ``tony.attn.rope``. Joined to the program's
record of its compiled step's scopes (``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "rope_share_of_busy", "%", "device_trace"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, scopes=("tony.attn.rope",))
