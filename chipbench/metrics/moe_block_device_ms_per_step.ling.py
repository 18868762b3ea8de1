"""``moe_block_device_ms_per_step`` where the block has a shared expert and
its layer is recomputed: the Qwen3-Next cell's reader (the four ``hetu_moe_*``
scopes and ``hetu_moe_shared``, all expert layers), whose time here holds the
recomputed forward pass too."""
from chipbench.run import reader

read = reader("moe_block_device_ms_per_step.qwen3next")
