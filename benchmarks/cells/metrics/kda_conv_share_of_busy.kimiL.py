"""The KDA mixer's causal convs' share of the device's busy time in the cell
``kimiL.seq32k``, every pass, under ``tony.kda.conv``. Joined to the
program's record of its compiled step's scopes (``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "kda_conv_share_of_busy.kimiL", "%", "device_trace"
LAYER, MOVES = "linear-attention mixer", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(run, scopes=("tony.kda.conv",))
