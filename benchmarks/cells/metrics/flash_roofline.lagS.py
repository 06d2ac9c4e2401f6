"""Every flash call's share of its roofline in the cell ``lagS.seq8k``: the
reader of ``flash_roofline.st21b`` (all six kernel names, each kind of call
held to the attention of its own kind of layer) under a name this cell's
entry can list. The architecture's ``flash_calls`` gives the full calls 24 q
heads and the windowed ones 36."""
import same_reader

NAME, UNIT, SOURCE = "flash_roofline.lagS", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"

read = same_reader.of("flash_roofline.st21b").read

note = same_reader.of("flash_roofline.st21b").note
