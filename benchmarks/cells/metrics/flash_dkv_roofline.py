"""The flash dkv kernel's share of its roofline: the operations the device
trace names ``flash_dkv*`` (``kernel_roofline``)."""
import functools

import kernel_roofline

NAME, UNIT, SOURCE = "flash_dkv_roofline", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"

read = functools.partial(kernel_roofline.read, kind="dkv",
                         prefix="flash_dkv")
note = functools.partial(kernel_roofline.note, kind="dkv",
                         prefix="flash_dkv")
