"""Device time a step inside the dense feed-forward sublayers, all layers,
forward and backward: the block ``hetu_mlp`` (``_blocks.py``)."""
from chipbench.metrics._blocks import block_ms


def read(ctx):
    return block_ms(ctx, "hetu_mlp")
