"""``flash_roofline`` of the FULL layer (the window layers' kernels go by
``hetu_swa_*`` and have ``window_attn_roofline``): the Ling-3.0 cell's reader,
which requires each pass once a full layer, step AND DEVICE on which a pass's
events ran (``_lib.passes_due``) at the local shard's shape (the builder's
``flash_rows``: one chip's sequences), over the events of all devices: like by
like.  Scores and values are both ``head_dim`` wide here."""
from chipbench.run import reader

read = reader("flash_roofline", "ling3")
