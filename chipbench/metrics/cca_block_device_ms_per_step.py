"""Device time a step inside what compressed convolutional attention adds
between the down-projections and the rotary, all layers, forward and backward:
the block ``hetu_cca`` (the depthwise taps, the head-mixing taps, the q-k
mean, the sums, the two L2 norms with the temperature, the values' shift)
(``_blocks.py``).  A program without the scope gives nothing."""
from chipbench.metrics._blocks import block_ms


def read(ctx):
    return block_ms(ctx, "hetu_cca")
