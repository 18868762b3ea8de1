"""``moe_experts_roofline`` where the chip holds 16 of 128 experts and both
copies of a sequence go through them: the Qwen3-Next cell's reader (the nine
grouped products REQUIRED of each expert layer over the pairs the counters say
were computed here, against the held experts' weights); a recomputed forward's
products earn nothing."""
from chipbench.run import reader

read = reader("moe_experts_roofline", "qwen3_next")
