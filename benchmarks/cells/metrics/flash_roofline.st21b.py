"""The least time the chip could take for every flash call a trace shows,
full (``flash_fwd``, ``flash_dq``, ``flash_dkv``) and windowed
(``flash_win_*``), each held to the attention of its own kind of layer (the
architecture's ``flash_calls``: the full triangle, or the band ``s*w -
w*w/2``), over the device time they took. Selected by name alone."""
import named_kernels

NAME, UNIT, SOURCE = "flash_roofline.st21b", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"


def _sums(run):
    full = named_kernels.flash_sums(
        run, "flash_", named_kernels.flash_needs(run, windowed=False))
    banded = named_kernels.flash_sums(
        run, "flash_win_", named_kernels.flash_needs(run, windowed=True))
    return tuple(a + b for a, b in zip(full, banded))


def read(run):
    least, took, _ = _sums(run)
    return named_kernels.share(least, took)


def note(run):
    least, took, calls = _sums(run)
    return f"{calls:g} calls, least {least:.6f} s of {took:.6f} s"
