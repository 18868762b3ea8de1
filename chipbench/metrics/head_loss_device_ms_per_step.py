"""Device time a step at the two ends of the model: the blocks ``hetu_embed``
(the embeddings, their norm and dropout), ``hetu_head`` (the final norm, the
head's products, BERT's pooler and NSP head) and ``hetu_loss`` (the softmax-CE
kernels, the auxiliary-loss sums, the loss's scaling) (``_blocks.py``)."""
from chipbench.metrics._blocks import block_ms


def read(ctx):
    return block_ms(ctx, "hetu_embed", "hetu_head", "hetu_loss")
