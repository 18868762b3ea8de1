"""Device time a step inside the attention sublayers, all layers, forward and
backward: the block ``hetu_attn`` (projections, QK-norm, rotary, the copies of
K and V for their query heads, the flash kernels, the output gate and
projection, its dropout) (``_blocks.py``)."""
from chipbench.metrics._blocks import block_ms


def read(ctx):
    return block_ms(ctx, "hetu_attn")
