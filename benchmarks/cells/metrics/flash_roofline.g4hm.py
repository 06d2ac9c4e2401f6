"""The flash calls' share of their roofline in the cell ``g4hm.seq8k``: the
reader of ``flash_roofline.st21b`` (selected by name, each kind of call held to
the attention of its own kind of layer) under a name this cell's entry can
list. The architecture's ``flash_calls`` has one kind: full causal, 32 q heads
over 8 kv heads of 64, one layer."""
import same_reader

NAME, UNIT, SOURCE = "flash_roofline.g4hm", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"

read = same_reader.of("flash_roofline.st21b").read

note = same_reader.of("flash_roofline.st21b").note
