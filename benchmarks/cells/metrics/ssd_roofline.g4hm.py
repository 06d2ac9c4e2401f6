"""The state-space scan kernels' share of their roofline in the cell
``g4hm.seq8k`` (one group of 64 heads at chunk 256): the reader of
``ssd_roofline.nem30b`` under a name this cell's entry can list
(``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "ssd_roofline.g4hm", "%", "device_trace"
LAYER, MOVES = "state-space mixer", "tokens_per_s_per_chip"

read = same_reader.of("ssd_roofline.nem30b").read

note = same_reader.of("ssd_roofline.nem30b").note
