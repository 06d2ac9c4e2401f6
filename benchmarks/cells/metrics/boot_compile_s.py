"""Of the user process's boot, the self time of its ``user.compile`` spans:
every jit compile before the first step's end (trace, lowering, and the
backend compile or the fetch from the persistent cache). From the job's
``trace.spans.jsonl`` (``cold_start_breakdown``'s ``user_boot``); a program
that records no such span gives nothing to read."""
NAME, UNIT, SOURCE = "boot_compile_s", "s", "program_span"
LAYER, MOVES = "user process boot", "setup_s"


def read(run):
    return run["spans"].get("user_boot", {}).get("user.compile")
