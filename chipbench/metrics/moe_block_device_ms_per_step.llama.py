"""Device time a step inside the MoE block: router, dispatch (sort and
gather), the grouped expert products and their activation, combine; forward
and backward.  Which device operations count is read from the compiled
step's scopes (``_moe.py``); the reader prints them."""
from chipbench.metrics._moe import block_ms


def read(ctx):
    ms = block_ms(ctx)
    return None if ms is None else sum(ms.values())
