"""``flash_roofline`` under a causal mask at head 64 with 32 query heads over
8,192 keys (the eight KV heads repeated before the kernel): half the
products, the tensors read and written whole (``_lib.flash_roofline``)."""
from chipbench.metrics._lib import flash_roofline


def read(ctx):
    return flash_roofline(ctx, causal=True)
