"""``step_s_p95`` in the cell ``nem30b.seq8k``: that metric's reader under a
name this cell's entry can list (``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "step_s_p95.nem30b", "s", "host_clock"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"

read = same_reader.of("step_s_p95").read

note = same_reader.of("step_s_p95").note
