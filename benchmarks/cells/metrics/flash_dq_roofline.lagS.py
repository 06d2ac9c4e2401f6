"""The flash dq kernel's share of its roofline in the cell ``lagS.seq8k``,
whose full layers have 24 q heads and whose windowed ones 36: the calls
named ``flash_dq*`` against the full entry of the architecture's
``flash_calls`` and those named ``flash_win_dq*`` against the windowed one
(``flash_by_kind``)."""
import functools

import flash_by_kind

NAME, UNIT, SOURCE = "flash_dq_roofline.lagS", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"

read = functools.partial(flash_by_kind.read, kind="dq")
note = functools.partial(flash_by_kind.note, kind="dq")
