"""The flash dq kernel's share of its roofline in the cell ``nem30b.seq8k``,
whose one attention layer is full causal at 32 q heads over 2 kv heads, 16 q
heads a kv head: the calls named ``flash_dq*`` against the one entry of
the architecture's ``flash_calls`` (``flash_by_kind``; the trace names no
windowed call here)."""
import functools

import flash_by_kind

NAME, UNIT, SOURCE = "flash_dq_roofline.nem30b", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"

read = functools.partial(flash_by_kind.read, kind="dq")
note = functools.partial(flash_by_kind.note, kind="dq")
