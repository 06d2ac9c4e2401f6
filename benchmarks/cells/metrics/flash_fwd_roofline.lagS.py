"""The flash fwd kernel's share of its roofline in the cell ``lagS.seq8k``,
whose full layers have 24 q heads and whose windowed ones 36: the calls
named ``flash_fwd*`` against the full entry of the architecture's
``flash_calls`` and those named ``flash_win_fwd*`` against the windowed one
(``flash_by_kind``)."""
import functools

import flash_by_kind

NAME, UNIT, SOURCE = "flash_fwd_roofline.lagS", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"

read = functools.partial(flash_by_kind.read, kind="fwd")
note = functools.partial(flash_by_kind.note, kind="fwd")
