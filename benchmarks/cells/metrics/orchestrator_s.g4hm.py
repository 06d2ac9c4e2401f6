"""``orchestrator_s`` in the cell ``g4hm.seq8k``: that metric's reader under a
name this cell's entry can list (``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "orchestrator_s.g4hm", "s", "program_span"
LAYER, MOVES = "submit path", "setup_s"

read = same_reader.of("orchestrator_s").read
