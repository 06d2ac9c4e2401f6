"""The flash calls' share of their roofline in the cell ``kimiL.seq32k``:
the calls the device trace names ``flash_fwd*``, ``flash_dq*`` and
``flash_dkv*``, each held to what one latent-attention call needs at its own
widths, q and k 192 wide, v 128 (``mla_flash.py``), over the device time
they took."""
import mla_flash

NAME, UNIT, SOURCE = "flash_roofline.kimiL", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"

read, note = mla_flash.read, mla_flash.note
