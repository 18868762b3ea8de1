"""The hyper-connections of a train step, forward and backward: least possible
time over the measured device time of the block ``hetu_hc``.  The work is
``flops_xing4.hc_sublayer``'s, stated once a sublayer application whatever
implements it: the LEAST bytes the streams must move (``7 n + 5`` stream-widths
a token: read before and behind the sublayer's function, forward and backward)
against the chip's HBM bandwidth.  The measured time holds what the program
runs there: the recomputed forward of a recomputed layer too, the maps'
product, the Sinkhorn rounds, every pass XLA did not fuse.  Recomputation
earns nothing, so the share reads under 100% whatever implements the mixes.
A program without the scope gives nothing."""
from chipbench import flops
from chipbench.metrics._blocks import block_ms


def read(ctx):
    ms = block_ms(ctx, "hetu_hc")
    if not ms:
        return None
    from chipbench import flops_xing4 as fl
    c, p = ctx["config"], ctx["program"]
    sublayers = p.expected_kernel_shapes()["hc_sublayers"]
    ops, nbytes = fl.hc_sublayer(c, p.tokens_per_step)
    t_min, limit = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    ctx["say"](f"roofline of the hyper-connections: {sublayers} sublayer "
               f"applications a step, each {ops / 1e9:.1f} G operations and "
               f"at least {nbytes / 1e6:.0f} MB; least "
               f"{sublayers * t_min * 1e3:.3f} ms over the measured "
               f"{ms:.3f} ms a step; bound by {limit}")
    return 100.0 * sublayers * t_min * 1e3 / ms
