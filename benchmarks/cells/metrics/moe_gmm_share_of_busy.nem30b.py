"""The grouped matmuls' share of the device's busy time in the cell
``nem30b.seq8k``: the reader of ``moe_gmm_share_of_busy`` under a name this
cell's entry can list."""
import same_reader

NAME, UNIT, SOURCE = "moe_gmm_share_of_busy.nem30b", "%", "device_trace"
LAYER, MOVES = "expert layer", "tokens_per_s_per_chip"

read = same_reader.of("moe_gmm_share_of_busy").read
