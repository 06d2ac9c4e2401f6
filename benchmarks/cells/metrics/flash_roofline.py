"""The least time the chip could take for the flash forward, dq and dkv
kernels' calls (``counts.least_seconds``: the calls the trace shows, at the
per-device shapes and masks the configuration's architecture says a step
needs) over the device time they took."""
import re

import arch
import counts

NAME, UNIT, SOURCE = "flash_roofline", "%", "device_trace"
LAYER, MOVES = "flash kernels", "tokens_per_s_per_chip"
# The kernels in the device trace, as ``trace_reduce.short_name`` gives them:
# the program names none of them, so they are told apart by what they take
# and give. Forward takes q, k, v and gives (o, row statistics); dq takes
# six operands and gives one array; dkv takes six and gives (dk, dv).
KERNELS = {"fwd": re.compile(r" tpu_custom_call \(.*\) operands=3$"),
           "dq": re.compile(r" tpu_custom_call [^(].* operands=6$"),
           "dkv": re.compile(r" tpu_custom_call \(.*\) operands=6$")}


def _sums(run):
    ops = run["worker"]["trace"].get("ops", {})
    peak = counts.peaks(run["worker"]["device"]["kind"])
    needs = arch.load(run["architecture"], "counts").flash_calls(
        run["config"], run["traffic"])
    least = took = 0.0
    binds = {}
    for kind, pattern in KERNELS.items():
        calls = sum(v[0] for k, v in ops.items() if pattern.search(k))
        seconds, binds[kind] = counts.least_seconds(kind, calls, needs, peak)
        least += seconds
        took += sum(v[1] for k, v in ops.items() if pattern.search(k))
    return least, took, binds


def read(run):
    least, took, _ = _sums(run)
    return 100.0 * least / took if took > 0 else None


def note(run):
    least, took, binds = _sums(run)
    return f"least {least:.6f} s of {took:.6f} s; binding bound {binds}"
