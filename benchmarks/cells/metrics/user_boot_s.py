"""User process start → its first step done (imports, libtpu init,
``init_sharded_state``, the first step), by the worker's own clock."""
NAME, UNIT, SOURCE = "user_boot_s", "s", "host_clock"
LAYER, MOVES = "user process boot", "setup_s"


def read(run):
    return run["worker"]["setup"]["phases"].get("first_step_s")
