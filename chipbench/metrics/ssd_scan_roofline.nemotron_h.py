"""The chunked state-space scan of a train step, forward and backward: least
possible time over the measured device time under the scope
``hetu_ssm_scan``.  The work is the chunked algorithm's at the
configuration's chunk (``flops_nemotronh.ssd_step``: its four products; x,
dt, B, C, y and one f32 state a chunk and head moved once; the backward pass
twice the forward), once a Mamba-2 block.  The measured time holds what the
program runs there: the recomputed forward of a recomputed block too, and
the gates and the skip beside the scan.  Recomputation and padding earn
nothing."""
from chipbench import flops, flops_nemotronh as fn
from chipbench.metrics._scopes import scoped_ms


def read(ctx):
    ms = scoped_ms(ctx, ("hetu_ssm_scan",), "ssd_scan")
    if ms is None or not ms["hetu_ssm_scan"]:
        return None
    c, p = ctx["config"], ctx["program"]
    blocks = c["hybrid_override_pattern"].count("M")
    ops, nbytes = fn.ssd_step(c, p.tokens_per_step)
    t_min, limit = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    measured = ms["hetu_ssm_scan"] * 1e-3
    ctx["say"](f"roofline of the state-space scan: {blocks} block(s) a step, "
               f"each {ops / 1e9:.1f} G operations and {nbytes / 1e6:.0f} "
               f"MB; least {blocks * t_min * 1e3:.3f} ms over the measured "
               f"{measured * 1e3:.3f} ms a step; bound by {limit}")
    return 100.0 * blocks * t_min / measured
