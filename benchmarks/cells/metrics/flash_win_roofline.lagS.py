"""The windowed flash kernels' share of their roofline in the cell
``lagS.seq8k``: the reader of ``flash_win_roofline`` (the ``flash_win_*``
calls against the band ``s*w - w*w/2`` a head) under a name this cell's
entry can list."""
import same_reader

NAME, UNIT, SOURCE = "flash_win_roofline.lagS", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"

read = same_reader.of("flash_win_roofline").read

note = same_reader.of("flash_win_roofline").note
