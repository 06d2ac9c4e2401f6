"""The compiled step's bytes on the fullest device
(``compiled.memory_analysis()``: arguments + outputs - aliased + temporaries).
"""
NAME, UNIT, SOURCE = "step_hbm_gb_per_chip", "GB", "program_counter"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"


def read(run):
    n = run["worker"]["compiled_bytes_per_device"]
    return None if not n else n / 1e9
