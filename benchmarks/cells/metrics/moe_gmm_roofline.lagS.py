"""The grouped matmuls' share of their roofline in the cell ``lagS.seq8k``:
the reader of ``moe_gmm_roofline`` under a name this cell's entry can list.
This architecture's ``moe_gmm_needs`` counts a ``tgmm`` call's bytes with
the running sum it reads."""
import same_reader

NAME, UNIT, SOURCE = "moe_gmm_roofline.lagS", "%", "device_trace"
LAYER, MOVES = "expert layer", "tokens_per_s_per_chip"

read = same_reader.of("moe_gmm_roofline").read

note = same_reader.of("moe_gmm_roofline").note
