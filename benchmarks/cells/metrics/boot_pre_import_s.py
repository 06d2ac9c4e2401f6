"""Of the user process's boot, the self time of its ``user.pre_import`` span:
process start → the end of ``import tony_tpu``, so the interpreter and
whatever the script imported first (the benchmark's worker brings jax and the
backend up before it imports the program, so both are in here). From the
job's ``trace.spans.jsonl`` (``cold_start_breakdown``'s ``user_boot``); a
program that records no such span gives nothing to read."""
NAME, UNIT, SOURCE = "boot_pre_import_s", "s", "program_span"
LAYER, MOVES = "user process boot", "setup_s"


def read(run):
    return run["spans"].get("user_boot", {}).get("user.pre_import")
