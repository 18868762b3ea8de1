"""The fused softmax-cross-entropy kernels' least possible time over their
measured device time (trace events named ``hetu_softmax_ce_*``); bound by
HBM.  Bytes from the local shard's shapes, ``chipbench/flops.py``."""
from chipbench import flops
from chipbench.metrics._lib import roofline_share


def read(ctx):
    want = ctx["program"].expected_kernel_shapes()
    return roofline_share(
        ctx, ("hetu_softmax_ce_fwd", "hetu_softmax_ce_bwd"),
        lambda k: flops.softmax_ce_call(k, want["ce_rows"],
                                        ctx["config"]["vocab_size"]))
