"""The delta rule with a decay a channel of a train step, forward and
backward: least possible time over the measured device time under the scope
``hetu_kda_scan``.  The work is the chunked algorithm's at the chunk size the
program uses (``flops_ling3.kda_step``: its products; q, k, v, o, g, beta and
one f32 state a chunk and head moved once; the backward pass twice the
forward), once a KDA layer.  The measured time holds what the program runs
there: the recomputed forward of a recomputed layer too, the sub-chunks'
extra products, and the gates and normalisation before the rule.
Recomputation and padding earn nothing."""
from chipbench import flops
from chipbench.metrics._scopes import scoped_ms


def read(ctx):
    ms = scoped_ms(ctx, ("hetu_kda_scan",), "kda_scan")
    if ms is None or not ms["hetu_kda_scan"]:
        return None
    from chipbench import flops_ling3 as fl
    c, p = ctx["config"], ctx["program"]
    layers = fl.layer_counts(c)[0]
    ops, nbytes = fl.kda_step(c, p.tokens_per_step, c["job"]["scan_chunk"])
    t_min, limit = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    measured = ms["hetu_kda_scan"] * 1e-3
    ctx["say"](f"roofline of the delta rule with a decay a channel: {layers} "
               f"layer(s) a step, each {ops / 1e9:.1f} G operations and "
               f"{nbytes / 1e6:.0f} MB; least {layers * t_min * 1e3:.3f} ms "
               f"over the measured {measured * 1e3:.3f} ms a step; bound by "
               f"{limit}")
    return 100.0 * layers * t_min / measured
