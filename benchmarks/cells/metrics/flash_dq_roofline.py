"""The flash dq kernel's share of its roofline: the operations the device
trace names ``flash_dq*`` (``kernel_roofline``)."""
import functools

import kernel_roofline

NAME, UNIT, SOURCE = "flash_dq_roofline", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"

read = functools.partial(kernel_roofline.read, kind="dq",
                         prefix="flash_dq")
note = functools.partial(kernel_roofline.note, kind="dq",
                         prefix="flash_dq")
