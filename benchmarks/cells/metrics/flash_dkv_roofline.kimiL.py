"""The flash dkv kernel's share of its roofline in the cell ``kimiL.seq32k``:
the calls the device trace names ``flash_dkv*``, held to what one
latent-attention call needs at q and k 192 wide and v 128
(``mla_flash.py``), over the device time they took."""
import functools

import mla_flash

NAME, UNIT, SOURCE = "flash_dkv_roofline.kimiL", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"

read = functools.partial(mla_flash.read, kinds=("dkv",))
note = functools.partial(mla_flash.note, kinds=("dkv",))
