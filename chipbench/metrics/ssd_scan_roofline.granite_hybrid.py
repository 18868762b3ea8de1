"""``ssd_scan_roofline`` where ALL of a mixer's heads read one group's ``B``
and ``C`` (Granite 4.0-H: 64 heads, one group): least possible time over the
measured device time under the scope ``hetu_ssm_scan``.  The work is the
chunked algorithm's at the chunk the program runs (``job.scan_chunk``, not
the published ``mamba_chunk_size``), ``C B^T`` credited ONCE for the group's
heads and ``B`` and ``C`` read once (``flops_granitehybrid.ssd_step``), once
a Mamba-2 layer.  The measured time holds everything the program runs
there: the recomputed forward of a recomputed layer, the gates and the skip
beside the scan, and whatever cutting a wide group into programs costs (a
block of heads reading ``B`` and ``C`` again, the sum of the blocks' ``dB``
and ``dC``).  Recomputation, padding and re-reading earn nothing."""
from chipbench import flops, flops_granitehybrid as fg
from chipbench.metrics._scopes import scoped_ms


def read(ctx):
    ms = scoped_ms(ctx, ("hetu_ssm_scan",), "ssd_scan")
    if ms is None or not ms["hetu_ssm_scan"]:
        return None
    c, p = ctx["config"], ctx["program"]
    layers = c["layer_types"].count("mamba")
    ops, nbytes = fg.ssd_step(c, p.tokens_per_step, c["job"]["scan_chunk"])
    t_min, limit = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    measured = ms["hetu_ssm_scan"] * 1e-3
    ctx["say"](f"roofline of the state-space scan: {layers} layer(s) a step, "
               f"each {ops / 1e9:.1f} G operations and {nbytes / 1e6:.0f} "
               f"MB; least {layers * t_min * 1e3:.3f} ms over the measured "
               f"{measured * 1e3:.3f} ms a step; bound by {limit}")
    return 100.0 * layers * t_min / measured
