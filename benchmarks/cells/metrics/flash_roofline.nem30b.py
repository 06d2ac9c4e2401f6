"""The flash calls' share of their roofline in the cell ``nem30b.seq8k``:
the reader of ``flash_roofline.st21b`` (selected by name, each kind of call
held to the attention of its own kind of layer) under a name this cell's
entry can list. The architecture's ``flash_calls`` has one kind: full causal,
32 q heads over 2 kv heads, one layer."""
import same_reader

NAME, UNIT, SOURCE = "flash_roofline.nem30b", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"

read = same_reader.of("flash_roofline.st21b").read

note = same_reader.of("flash_roofline.st21b").note
