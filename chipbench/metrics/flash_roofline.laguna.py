"""``flash_roofline`` of the FULL layers (the window layers' kernels go by
``hetu_swa_*`` and have ``window_attn_roofline``): the Ling-3.0 cell's reader,
which requires each pass once a full layer and step (the builder's
``attention_passes`` x the traced steps) whether or not whole layers are
recomputed and however often the forward kernel runs; scores and values are
both ``head_dim`` wide here."""
from chipbench.run import reader

read = reader("flash_roofline", "ling3")
