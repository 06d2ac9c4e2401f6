"""Of ``boot_compile_s``, the ``user.compile`` spans of stage ``lower``: jax's
lowering of the jaxprs to MLIR modules, as self time inside the ``user_boot``
phase (``cold_start_breakdown``'s ``user_boot_compile``). A program that does
not part its compiles by stage gives nothing to read."""
NAME, UNIT, SOURCE = "boot_compile_lower_s", "s", "program_span"
LAYER, MOVES = "user process boot", "setup_s"


def read(run):
    return run["spans"].get("user_boot_compile", {}).get("lower")
