"""Programs that set-up had to compile because the persistent cache did not
hold them (``jax.monitoring``): 0 in every run after a cell's first."""
NAME, UNIT, SOURCE = "compile_cache_misses", "count", "program_counter"
LAYER, MOVES = "user process boot", "setup_s"


def read(run):
    return float(run["worker"]["setup"]["cache_misses"])


def note(run):
    setup = run["worker"]["setup"]
    return (f"{setup['cache_hits']} hits in set-up; "
            f"{run['worker']['window']['compiles_inside']} compile lookups "
            f"inside the window")
