"""One named flash kernel's share of its roofline.

The program names its Mosaic kernels (``pallas_call(name="flash_fwd")``, …)
and the device trace shows each call under that name, numbered by the
compiler (``flash_fwd.1``). A reader selects its kernel's operations by that
name alone: the least time the chip could take for the calls seen
(``counts.least_seconds`` for the attention calls the configuration's
architecture says a step needs) over the device time they took. Where the
trace names no such operation, as with a program that does not name its
kernels, there is nothing to read.
"""
import arch
import counts


def sums(run: dict, kind: str, prefix: str) -> tuple:
    """(least seconds, seconds taken, calls, which bound binds) of the
    Mosaic calls whose instruction name begins ``prefix``."""
    ops = run["worker"]["trace"].get("ops", {})
    mine = [v for name, v in ops.items()
            if name.startswith(prefix) and " tpu_custom_call " in name]
    calls = sum(v[0] for v in mine)
    took = sum(v[1] for v in mine)
    needs = arch.load(run["architecture"], "counts").flash_calls(
        run["config"], run["traffic"])
    least, binds = counts.least_seconds(
        kind, calls, needs, counts.peaks(run["worker"]["device"]["kind"]))
    return least, took, calls, binds


def read(run: dict, kind: str, prefix: str):
    least, took, _, _ = sums(run, kind, prefix)
    return 100.0 * least / took if took > 0 else None


def note(run: dict, kind: str, prefix: str) -> str:
    least, took, calls, binds = sums(run, kind, prefix)
    return (f"{calls:g} calls, least {least:.6f} s of {took:.6f} s; "
            f"binding bound {binds}")
