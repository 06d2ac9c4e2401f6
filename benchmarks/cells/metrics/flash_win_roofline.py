"""The windowed flash kernels' share of their roofline: the calls the device
trace names ``flash_win_fwd*``, ``flash_win_dq*`` and ``flash_win_dkv*``
against the windowed entry of the architecture's ``flash_calls`` alone (least
time by the band ``s*w - w*w/2`` a head), over the device time they took."""
import named_kernels

NAME, UNIT, SOURCE = "flash_win_roofline", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"


def _sums(run):
    return named_kernels.flash_sums(
        run, "flash_win_", named_kernels.flash_needs(run, windowed=True))


def read(run):
    least, took, _ = _sums(run)
    return named_kernels.share(least, took)


def note(run):
    least, took, calls = _sums(run)
    return f"{calls:g} calls, least {least:.6f} s of {took:.6f} s"
