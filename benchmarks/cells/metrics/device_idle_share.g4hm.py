"""``device_idle_share`` in the cell ``g4hm.seq8k``: that metric's reader under
a name this cell's entry can list (``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "device_idle_share.g4hm", "%", "device_trace"
LAYER, MOVES = "device", "tokens_per_s_per_chip"

read = same_reader.of("device_idle_share").read
