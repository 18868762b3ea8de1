"""Pairs at the fullest expert over the mean, at the run's last step, the
worst layer: gauge ``hetu_moe_expert_load_max_over_mean`` (1.0 is perfectly
even; it bounds how ragged the grouped products' groups are)."""
from chipbench.metrics._moe import sample


def read(ctx):
    load = sample(ctx, "hetu_moe_expert_load_max_over_mean")
    return max(load.values()) if load else None
