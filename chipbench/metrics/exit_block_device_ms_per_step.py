"""Device time a step of the looped decoder's exit block: the row
``hetu_exit`` of the by-block table (the gate's product and sigmoid a pass,
the distribution over the exits, the weights of the expectation, the entropy
term and the mean shares, forward and backward; ``_blocks.py``).  None where
the program names no such block (another family's, a parent commit's)."""
from chipbench.metrics._blocks import block_ms


def read(ctx):
    return block_ms(ctx, "hetu_exit")
