"""``mfu`` in the cell ``g4hm.seq8k``: that metric's reader under a name this
cell's entry can list (``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "mfu.g4hm", "%", "host_clock"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"

read = same_reader.of("mfu").read
