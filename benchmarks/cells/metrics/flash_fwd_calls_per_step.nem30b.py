"""How many times one traced step runs the flash forward kernel in the cell
``nem30b.seq8k``: the reader of ``flash_fwd_calls_per_step.lagS`` (the Mosaic
calls named ``flash_fwd*`` and ``flash_win_fwd*`` over the whole steps
traced) under a name this cell's entry can list. One here, the one attention
layer's, where the backward keeps the forward's o and lse; two where the
block's remat runs the kernel again to get them back."""
import same_reader

NAME, UNIT, SOURCE = "flash_fwd_calls_per_step.nem30b", "count", "device_trace"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"

read = same_reader.of("flash_fwd_calls_per_step.lagS").read

note = same_reader.of("flash_fwd_calls_per_step.lagS").note
