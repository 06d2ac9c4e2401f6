"""``user_boot_s`` in the cell ``g4hm.seq8k``: that metric's reader under a
name this cell's entry can list (``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "user_boot_s.g4hm", "s", "host_clock"
LAYER, MOVES = "user process boot", "setup_s"

read = same_reader.of("user_boot_s").read
