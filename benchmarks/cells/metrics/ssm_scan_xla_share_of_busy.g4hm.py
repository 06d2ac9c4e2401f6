"""The scan's XLA side's share of the device's busy time in the cell
``g4hm.seq8k`` (one group of 64 heads at chunk 256): the reader of
``ssm_scan_xla_share_of_busy`` under a name this cell's entry can list
(``same_reader``)."""
import same_reader

NAME, UNIT, SOURCE = "ssm_scan_xla_share_of_busy.g4hm", "%", "device_trace"
LAYER, MOVES = "state-space mixer", "tokens_per_s_per_chip"

read = same_reader.of("ssm_scan_xla_share_of_busy").read
