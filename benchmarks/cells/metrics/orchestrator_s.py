"""Submit → the user process is exec'd: the orchestrator's own share of
set-up, from the job's ``trace.spans.jsonl`` (``cold_start_breakdown``: every
phase before ``user_boot``)."""
NAME, UNIT, SOURCE = "orchestrator_s", "s", "program_span"
LAYER, MOVES = "submit path", "setup_s"


def read(run):
    phases = run["spans"]["phases"]
    if "user_boot" not in phases:
        return None
    return sum(v for k, v in phases.items() if k != "user_boot")
