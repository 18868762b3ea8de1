"""(token, choice) pairs no expert computed over pairs routed, all layers
and steps of the run, in percent: counters
``hetu_moe_pairs_dropped_total`` / ``hetu_moe_pairs_routed_total``
(``hetu_tpu/layers/moe.py record_moe_load``).  0 on the dropless path."""
from chipbench.metrics._moe import sample


def read(ctx):
    routed = sample(ctx, "hetu_moe_pairs_routed_total")
    dropped = sample(ctx, "hetu_moe_pairs_dropped_total")
    if not routed or dropped is None or not sum(routed.values()):
        return None
    return 100.0 * sum(dropped.values()) / sum(routed.values())
