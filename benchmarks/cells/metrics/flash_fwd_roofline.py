"""The flash fwd kernel's share of its roofline: the operations the device
trace names ``flash_fwd*`` (``kernel_roofline``)."""
import functools

import kernel_roofline

NAME, UNIT, SOURCE = "flash_fwd_roofline", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"

read = functools.partial(kernel_roofline.read, kind="fwd",
                         prefix="flash_fwd")
note = functools.partial(kernel_roofline.note, kind="fwd",
                         prefix="flash_fwd")
