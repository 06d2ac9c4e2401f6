"""``step_hbm_gb_per_chip`` in the cell ``g4hm.seq8k``: that metric's reader
under a name this cell's entry can list (``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "step_hbm_gb_per_chip.g4hm", "GB", "program_counter"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"

read = same_reader.of("step_hbm_gb_per_chip").read
