"""``moe_experts_roofline`` where the chip holds 8 of 16 experts and a token
takes one choice of 17: the Qwen3-Next cell's reader (the nine grouped products
REQUIRED of each expert layer over the pairs the counters say were computed
here, against the held experts' weights).  Pairs that chose no expert, or an
expert held elsewhere, are not among them."""
from chipbench.run import reader

read = reader("moe_experts_roofline", "qwen3_next")
