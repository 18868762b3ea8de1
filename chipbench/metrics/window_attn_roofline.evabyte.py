"""``window_attn_roofline`` of EVA attention (attention kernels that walk less
than all earlier keys): the least possible time of the ``hetu_eva_fwd*`` and
``hetu_eva_bwd*`` events (and of ``hetu_eva_prep_*``, should the summaries get
a kernel pair) over their measured device time, both passes together, in
percent.  A pass is REQUIRED once a layer and step (``_lib.passes_due``: the
configuration's layers x the traced steps x the devices the pass's events ran
on), never a count of forward events.  Operations and bytes are the plan's
(``flops_evabyte.eva_pass``: the local and the remote pairs a head, 2 products
forward and 5 backward; q, o, k, v and the summaries read).  The time of a
prep pair's events is measured and earns nothing beyond the pass's work.  None
where no such event ran or the program states no EVA layer."""
from chipbench import flops, flops_evabyte as fl, trace_reduce as tr
from chipbench.metrics._lib import passes_due


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    want = ctx["program"].expected_kernel_shapes()
    if "eva_dims" not in want:
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    by_device = {name: tr.events_holding(t["reduced"], lo, hi, events)
                 for name, events in fl.EVA_EVENTS.items()}
    found = {name: [d for ev in by.values() for _, d, _ in ev]
             for name, by in by_device.items()}
    if not all(found.values()):
        return None
    prep = sum(d for events in fl.PREP_EVENTS.values()
               for ev in tr.events_holding(t["reduced"], lo, hi,
                                           events).values() for _, d, _ in ev)
    other = {name: sum(len(ev) for ev in tr.events_holding(
        t["reduced"], lo, hi, name).values())
        for name in ("hetu_flash_", "hetu_swa_")}
    batch, heads, seq, dim = want["eva_dims"]
    required = passes_due(ctx, want["eva_layers"], by_device)
    least, measured, limits = 0.0, prep * 1e-9, {}
    for name, durs in found.items():
        t_min, limits[name] = flops.roofline_seconds(
            *fl.eva_pass(name, batch, heads, seq, dim, want["window"],
                         want["chunk"]), ctx["peaks"])
        least += t_min * required
        measured += sum(durs) * 1e-9
    local, remote = fl.eva_pairs(seq, want["window"], want["chunk"])
    ctx["say"](f"roofline of EVA attention (window {want['window']}, chunk "
               f"{want['chunk']}, {heads} heads of {dim}, {seq} positions: "
               f"{local:.0f} local + {remote:.0f} remote pairs a head): "
               f"{required} passes required ({want['eva_layers']} a step), "
               f"events a pass { {k: len(v) for k, v in found.items()} }, "
               f"summaries' kernels {prep * 1e-9:.4f} s; least {least:.4f} s "
               f"over measured {measured:.4f} s; bound by {limits}; events "
               f"of other attention kernels in the window: {other}")
    return 100.0 * least / measured
