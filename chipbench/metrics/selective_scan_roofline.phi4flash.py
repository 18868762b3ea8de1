"""The Mamba-1 selective scan of a train step, forward and backward: least
possible time over the measured device time under the scope
``hetu_ssm_scan``.  The work is the algorithm's, whatever runs
(``flops_phi4flash.selective_scan_step``: a state update and a read-out of
``N`` states a channel and position, each operand and result moved once, the
recurrence run once more in the backward pass), once a Mamba layer.  The
measured time holds what the program runs there: the recomputed forward of a
recomputed layer too, the softplus and the skip.  Recomputation and padding
earn nothing.  None where the compiled step has no such scope (a program
without the layer)."""
from chipbench import flops
from chipbench.metrics._scopes import scoped_ms


def read(ctx):
    ms = scoped_ms(ctx, ("hetu_ssm_scan",), "selective_scan")
    if ms is None or not ms["hetu_ssm_scan"]:
        return None
    from chipbench import flops_phi4flash as fl
    c, p = ctx["config"], ctx["program"]
    layers = fl.kinds(c).count("mamba")
    ops, nbytes = fl.selective_scan_step(c, p.tokens_per_step)
    t_min, limit = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    measured = ms["hetu_ssm_scan"] * 1e-3
    ctx["say"](f"roofline of the selective scan: {layers} layer(s) a step, "
               f"each {ops / 1e9:.1f} G operations and {nbytes / 1e6:.0f} MB;"
               f" least {layers * t_min * 1e3:.3f} ms over the measured "
               f"{measured * 1e3:.3f} ms a step; bound by {limit}")
    return 100.0 * layers * t_min / measured
