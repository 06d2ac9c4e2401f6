"""``data_wait_share`` in the cell ``g4hm.seq8k``: that metric's reader under a
name this cell's entry can list (``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "data_wait_share.g4hm", "%", "host_clock"
LAYER, MOVES = "input pipeline", "tokens_per_s_per_chip"

read = same_reader.of("data_wait_share").read
