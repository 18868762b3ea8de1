"""The mixing inside compressed convolutional attention, forward and backward:
least possible time over the measured device time of the block ``hetu_cca``.
The work is ``flops_zaya1.cca_sublayer``'s, stated once a sublayer application
whatever implements it: the LEAST bytes it must move (``[q~ | k~]`` in and
``[q^ | k^]`` out forward, the operands again and the cotangents backward, the
values' shifted half each way) against the chip's HBM bandwidth.  The measured
time holds what the program runs there: every pass XLA did not fuse, the
recomputed forward of a recomputed layer too.  The bytes are the least, so the
share reads under 100% whatever implements the mixing.  A program without the
scope gives nothing."""
from chipbench import flops
from chipbench.metrics._blocks import block_ms


def read(ctx):
    ms = block_ms(ctx, "hetu_cca")
    if not ms:
        return None
    from chipbench import flops_zaya1 as fl
    c, p = ctx["config"], ctx["program"]
    sublayers = p.expected_kernel_shapes()["cca_sublayers"]
    ops, nbytes = fl.cca_sublayer(c, p.tokens_per_step)
    t_min, limit = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    ctx["say"](f"roofline of CCA's mixing: {sublayers} sublayer applications "
               f"a step, each {ops / 1e9:.1f} G operations and at least "
               f"{nbytes / 1e6:.0f} MB; least {sublayers * t_min * 1e3:.3f} "
               f"ms over the measured {ms:.3f} ms a step; bound by {limit}")
    return 100.0 * sublayers * t_min * 1e3 / ms
