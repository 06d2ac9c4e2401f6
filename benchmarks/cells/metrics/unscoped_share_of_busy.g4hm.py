"""The share of the device's busy time under no layer's scope in the cell
``g4hm.seq8k`` (one group of 64 heads at chunk 256): the reader of
``unscoped_share_of_busy`` under a name this cell's entry can list
(``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "unscoped_share_of_busy.g4hm", "%", "device_trace"
LAYER, MOVES = "train step", "tokens_per_s_per_chip"

read = same_reader.of("unscoped_share_of_busy").read

note = same_reader.of("unscoped_share_of_busy").note
