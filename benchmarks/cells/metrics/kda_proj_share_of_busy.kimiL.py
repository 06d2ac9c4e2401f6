"""The KDA mixer's projections' share of the device's busy time in the cell
``kimiL.seq32k``, every pass: W_q, W_k, W_v, the low-rank decay and gate
pairs and β's W_b under ``tony.kda.in_proj``, W_o under
``tony.kda.out_proj``. Joined to the program's record of its compiled step's
scopes (``scope_times.py``)."""
import scope_times

NAME, UNIT, SOURCE = "kda_proj_share_of_busy.kimiL", "%", "device_trace"
LAYER, MOVES = "linear-attention mixer", "tokens_per_s_per_chip"


def read(run):
    return scope_times.share(
        run, scopes=("tony.kda.in_proj", "tony.kda.out_proj"))
