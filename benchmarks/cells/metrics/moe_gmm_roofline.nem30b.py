"""The grouped matmuls' share of their roofline in the cell ``nem30b.seq8k``:
the reader of ``moe_gmm_roofline`` under a name this cell's entry can list.
This architecture's ``moe_gmm_needs`` counts experts of two matrices (six
``gmm`` and two ``tgmm`` calls a chunk and layer) and a ``tgmm`` call's
bytes with the running sum it reads."""
import same_reader

NAME, UNIT, SOURCE = "moe_gmm_roofline.nem30b", "%", "device_trace"
LAYER, MOVES = "expert layer", "tokens_per_s_per_chip"

read = same_reader.of("moe_gmm_roofline").read

note = same_reader.of("moe_gmm_roofline").note
