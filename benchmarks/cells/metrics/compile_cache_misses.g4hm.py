"""``compile_cache_misses`` in the cell ``g4hm.seq8k``: that metric's reader
under a name this cell's entry can list (``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "compile_cache_misses.g4hm", "count", "program_counter"
LAYER, MOVES = "user process boot", "setup_s"

read = same_reader.of("compile_cache_misses").read

note = same_reader.of("compile_cache_misses").note
