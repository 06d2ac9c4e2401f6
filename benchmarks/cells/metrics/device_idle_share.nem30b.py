"""``device_idle_share`` in the cell ``nem30b.seq8k``: that metric's reader under a
name this cell's entry can list (``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "device_idle_share.nem30b", "%", "device_trace"
LAYER, MOVES = "device", "tokens_per_s_per_chip"

read = same_reader.of("device_idle_share").read
