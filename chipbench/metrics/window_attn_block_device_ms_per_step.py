"""Device time a step inside the WINDOW layers' attention sublayers, forward,
recomputed forward and backward: the block ``hetu_window_attn`` (projections,
rotary, the copies of K and V for their query heads, the ``hetu_swa_*``
kernels, the gate a head and the output product) (``_blocks.py``).  The full
layers' sublayers stay under ``hetu_attn``.  None where the program has no
such block."""
from chipbench.metrics._blocks import block_ms


def read(ctx):
    return block_ms(ctx, "hetu_window_attn")
