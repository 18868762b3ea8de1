"""Device time a step of the multi-token-prediction depth's combine, forward
and backward: the block ``hetu_mtp`` (the two norms, the concatenation and
``W_eh``) alone.  The depth's decoder layer, its pass of the head and its loss
are counted in their own blocks (``hetu_attn``, ``hetu_hc``, ``hetu_moe_*``,
``hetu_head``, ``hetu_loss``) beside the main stack's (``_blocks.py``).  A
program without the scope gives nothing."""
from chipbench.metrics._blocks import block_ms


def read(ctx):
    return block_ms(ctx, "hetu_mtp")
