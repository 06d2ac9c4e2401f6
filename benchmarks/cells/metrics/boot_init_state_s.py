"""Of the user process's boot, the self time of its ``user.init_state`` span:
``init_sharded_state``'s own host time, less the compiles inside it (those
are ``boot_compile_s``). From the job's ``trace.spans.jsonl``
(``cold_start_breakdown``'s ``user_boot``); a program that records no such
span gives nothing to read."""
NAME, UNIT, SOURCE = "boot_init_state_s", "s", "program_span"
LAYER, MOVES = "user process boot", "setup_s"


def read(run):
    return run["spans"].get("user_boot", {}).get("user.init_state")
