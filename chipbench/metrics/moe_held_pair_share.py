"""Pairs routed to experts this chip holds over all pairs routed, all layers
and steps of the run, in percent: ``hetu_moe_pairs_routed_total`` over itself
plus ``hetu_moe_pairs_elsewhere_total`` (``hetu_tpu/layers/moe.py
record_moe_load``).  Held experts over all experts where the routing is even
(32 / 512 = 6.25%); it is the share of the expert work of a layer that this
chip does, and it moves as the router learns."""
from chipbench.metrics._moe import sample


def read(ctx):
    here = sample(ctx, "hetu_moe_pairs_routed_total")
    elsewhere = sample(ctx, "hetu_moe_pairs_elsewhere_total")
    if not here or elsewhere is None:
        return None
    total = sum(here.values()) + sum(elsewhere.values())
    return 100.0 * sum(here.values()) / total if total else None
