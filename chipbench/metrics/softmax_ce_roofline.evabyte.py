"""``softmax_ce_roofline`` where the logits are f32 (``fp32_logits``): the
shared reader's arithmetic with four bytes an element, as the program's
``expected_kernel_shapes()`` states (``ce_itemsize``)."""
from chipbench import flops
from chipbench.metrics._lib import roofline_share


def read(ctx):
    want = ctx["program"].expected_kernel_shapes()
    return roofline_share(
        ctx, ("hetu_softmax_ce_fwd", "hetu_softmax_ce_bwd"),
        lambda k: flops.softmax_ce_call(k, want["ce_rows"],
                                        ctx["config"]["vocab_size"],
                                        want.get("ce_itemsize", 2)))
