"""``moe_block_device_ms_per_step`` of a block read by its scopes, shared
expert or none: device time a step under ``hetu_moe_route``, ``_dispatch``,
``_experts``, ``_combine`` and ``hetu_moe_shared``, all expert layers, forward
and backward (``_scopes.py``); where whole layers are recomputed the time
holds the recomputed forward pass too.  Nothing without the program's
``hetu_moe_pairs_routed_total``."""
from chipbench.metrics._moe import SCOPES, sample
from chipbench.metrics._scopes import scoped_ms


def read(ctx):
    if sample(ctx, "hetu_moe_pairs_routed_total") is None:
        return None
    ms = scoped_ms(ctx, SCOPES + ("hetu_moe_shared",), "moe")
    return None if ms is None else sum(ms.values())
