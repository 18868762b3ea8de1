"""The roofline share of differential attention's kernel events, for the full
and cross layers (``hetu_flash_*``) and for the window layer (``hetu_swa_*``):
least possible time of the passes a step REQUIRES over the measured device
time of the events, both passes together, in percent.  A pass is required once
a layer and step (``_lib.passes_due``), never a count of events: a forward
pass run again inside the backward pass is measured and earns nothing.
Operations and bytes are ``flops_phi4flash.differential_pass``'s."""
from chipbench import flops, flops_phi4flash as fl, trace_reduce as tr
from chipbench.metrics._lib import passes_due


def share(ctx, events, layers, window, what):
    t = ctx["trace"]
    if t is None or not layers:
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    by_device = {name: tr.events_holding(t["reduced"], lo, hi, ev)
                 for name, ev in events.items()}
    found = {name: [d for ev in by.values() for _, d, _ in ev]
             for name, by in by_device.items()}
    if not all(found.values()):
        return None
    p = ctx["program"]
    required = passes_due(ctx, layers, by_device)
    least = measured = 0.0
    limits = {}
    for name, durs in found.items():
        t_min, limits[name] = flops.roofline_seconds(
            *fl.differential_pass(name, p.batch, p.seq, ctx["config"],
                                  window), ctx["peaks"])
        least += t_min * required
        measured += sum(durs) * 1e-9
    ctx["say"](f"roofline of differential attention {what} (two score "
               f"products at 64 and two value products at 128 a query pair "
               f"forward, ten products backward, {p.seq} positions): "
               f"{required} passes required ({layers} a step), events a pass "
               f"{ {k: len(v) for k, v in found.items()} }; least "
               f"{least:.4f} s over measured {measured:.4f} s; bound by "
               f"{limits}")
    return 100.0 * least / measured
