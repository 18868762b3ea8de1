"""``flash_roofline`` under a causal mask: half the products, the tensors
read and written whole (``_lib.flash_roofline``)."""
from chipbench.metrics._lib import flash_roofline


def read(ctx):
    return flash_roofline(ctx, causal=True)
