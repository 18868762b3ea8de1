"""(token, choice) pairs that chose no expert over all pairs routed, all
layers and steps of the run, in percent: ``hetu_moe_pairs_skipped_total`` over
itself plus ``hetu_moe_pairs_routed_total`` (held here) plus
``hetu_moe_pairs_elsewhere_total`` (``hetu_tpu/layers/moe.py
record_moe_load``).  It says how much of the expert block's work the skip
choice removed, so that a change in routing is not read as a change in speed.
A program without the counter gives nothing."""
from chipbench.metrics._moe import sample


def read(ctx):
    skipped = sample(ctx, "hetu_moe_pairs_skipped_total")
    here = sample(ctx, "hetu_moe_pairs_routed_total")
    elsewhere = sample(ctx, "hetu_moe_pairs_elsewhere_total")
    if skipped is None or not here or elsewhere is None:
        return None
    total = (sum(skipped.values()) + sum(here.values())
             + sum(elsewhere.values()))
    return 100.0 * sum(skipped.values()) / total if total else None
