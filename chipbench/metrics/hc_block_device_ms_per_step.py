"""Device time a step inside the hyper-connections, all sublayers, forward,
recomputed forward and backward: the block ``hetu_hc`` (the maps' product and
norm, the Sinkhorn rounds, ``u = Hpre X``, ``X' = Hres X + Hpost^T y``, the
streams' copies at the start and their sum at the end) (``_blocks.py``).  A
program without the scope gives nothing."""
from chipbench.metrics._blocks import block_ms


def read(ctx):
    return block_ms(ctx, "hetu_hc")
