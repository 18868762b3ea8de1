"""Device time a step inside EVA attention itself, forward, recomputed forward
and backward: the blocks ``hetu_eva`` (the attention node: the ``hetu_eva_*``
kernels) and ``hetu_chunk_summary`` (the chunks' summaries) (``_blocks.py``).
The projections, the rotation and the output product stay under
``hetu_attn``.  None where the program has no such block."""
from chipbench.metrics._blocks import block_ms


def read(ctx):
    return block_ms(ctx, "hetu_eva", "hetu_chunk_summary")
