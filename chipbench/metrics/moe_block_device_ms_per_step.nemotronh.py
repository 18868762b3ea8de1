"""``moe_block_device_ms_per_step`` of the Nemotron-H expert blocks: device
time a step under ``hetu_moe_route`` (the sigmoid scores, the choice by score
plus bias, the bias's update), ``_dispatch``, ``_experts``, ``_combine`` and
``hetu_moe_shared`` (the ungated relu2 shared expert), all expert blocks,
forward and backward (``_scopes.py``)."""
from chipbench.metrics._moe import SCOPES, sample
from chipbench.metrics._scopes import scoped_ms


def read(ctx):
    if sample(ctx, "hetu_moe_pairs_routed_total") is None:
        return None
    ms = scoped_ms(ctx, SCOPES + ("hetu_moe_shared",), "moe")
    return None if ms is None else sum(ms.values())
