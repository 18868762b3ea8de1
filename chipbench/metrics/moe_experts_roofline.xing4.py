"""``moe_experts_roofline`` where the chip holds 8 of 64 experts and whole
layers are recomputed: the Qwen3-Next cell's reader (the nine grouped products
REQUIRED of each expert layer, the MTP depth's among the five, over the pairs
the counters say were computed here, against the held experts' weights), given
the held count under the key that reader reads (``num_experts``; this
family's published key is ``n_routed_experts``).  A recomputed forward's
products earn nothing."""
from chipbench.run import reader


def read(ctx):
    if ctx["trace"] is None:
        return None
    c = ctx["config"]
    return reader("moe_experts_roofline", "qwen3_next")(
        dict(ctx, config=dict(c, num_experts=c["n_routed_experts"])))
