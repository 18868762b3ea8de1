"""``moe_experts_roofline`` where the chip holds a share of the experts and
whole layers are recomputed: the Qwen3-Next cell's reader (the nine grouped
products REQUIRED of each expert layer over the pairs the counters say were
computed here, against the held experts' weights).  The measured time of the
``hetu_moe_gmm_*`` events holds the recomputed forward's three products a
layer too: they earn nothing."""
from chipbench.run import reader

read = reader("moe_experts_roofline", "qwen3_next")
