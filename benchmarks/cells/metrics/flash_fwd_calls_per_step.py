"""How many times one traced step runs the flash forward kernel on a device:
the Mosaic calls the device trace names ``flash_fwd*``
(``kernel_roofline.sums``) over the whole steps traced. One a layer where the
backward keeps the forward's o and lse, two a layer where the block's remat
runs the kernel again to get them back. Where the trace names no such call,
as with a program that does not name its kernels, there is nothing to read.
"""
import kernel_roofline

NAME, UNIT, SOURCE = "flash_fwd_calls_per_step", "count", "device_trace"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    steps = run["worker"]["trace"].get("steps")
    calls = kernel_roofline.sums(run, "fwd", "flash_fwd")[2]
    return calls / steps if steps and calls else None


def note(run):
    calls = kernel_roofline.sums(run, "fwd", "flash_fwd")[2]
    return f"{calls:g} calls in {run['worker']['trace'].get('steps')} steps"
