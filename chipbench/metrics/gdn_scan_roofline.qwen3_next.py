"""The gated delta rule of a train step, forward and backward: least possible
time over the measured device time under the scope ``hetu_gdn_scan``.  The
work is the chunked algorithm's at the chunk size the program uses
(``flops_qwen3next.delta_rule_step``: its products; q, k, v, o, g, beta and
one f32 state a chunk and head moved once; the backward pass twice the
forward), once a DeltaNet layer.  The measured time holds what the program
runs there: the recomputed forward of a recomputed layer too, and the gates
and normalisation before the rule.  Recomputation and padding earn
nothing."""
from chipbench import flops, flops_qwen3next as fq
from chipbench.metrics._scopes import scoped_ms


def read(ctx):
    ms = scoped_ms(ctx, ("hetu_gdn_scan",), "gdn_scan")
    if ms is None or not ms["hetu_gdn_scan"]:
        return None
    from hetu_tpu.ops.gated_delta import CHUNK
    c, p = ctx["config"], ctx["program"]
    layers = fq.layer_kinds(c).count("linear_attention")
    ops, nbytes = fq.delta_rule_step(c, p.tokens_per_step, CHUNK)
    t_min, limit = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    measured = ms["hetu_gdn_scan"] * 1e-3
    ctx["say"](f"roofline of the delta rule: {layers} layer(s) a step, each "
               f"{ops / 1e9:.1f} G operations and {nbytes / 1e6:.0f} MB; "
               f"least {layers * t_min * 1e3:.3f} ms over the measured "
               f"{measured * 1e3:.3f} ms a step; bound by {limit}")
    return 100.0 * layers * t_min / measured
