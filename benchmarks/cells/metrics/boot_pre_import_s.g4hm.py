"""``boot_pre_import_s`` in the cell ``g4hm.seq8k``: that metric's reader under
a name this cell's entry can list (``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "boot_pre_import_s.g4hm", "s", "program_span"
LAYER, MOVES = "user process boot", "setup_s"

read = same_reader.of("boot_pre_import_s").read
