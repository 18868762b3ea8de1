"""Attention over a window: the least possible time of the ``hetu_swa_fwd*``
and ``hetu_swa_bwd*`` events over their measured device time, both passes
together, in percent.  A pass is REQUIRED once a window layer and step: the
configuration's ``window_layers`` x the traced steps x the devices the
pass's events ran on (``_lib.passes_due``), never a count of forward events,
so a forward pass run again inside the backward pass is measured and earns
nothing, and a program that runs none again is due the same work.
Operations and bytes are the band's (``flops_laguna.window_pass``: ``w S - w
(w - 1) / 2`` pairs a head, 2 products forward and 5 backward, q and o at
the query heads, k and v at the key heads).  None where no such event ran or
the program states no window."""
from chipbench import flops, flops_laguna as fl, trace_reduce as tr
from chipbench.metrics._lib import passes_due


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    want = ctx["program"].expected_kernel_shapes()
    if "window_dims" not in want:
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    by_device = {name: tr.events_holding(t["reduced"], lo, hi, events)
                 for name, events in fl.WINDOW_EVENTS.items()}
    found = {name: [d for ev in by.values() for _, d, _ in ev]
             for name, by in by_device.items()}
    if not all(found.values()):
        return None
    batch, heads, seq, dim = want["window_dims"]
    required = passes_due(ctx, want["window_layers"], by_device)
    least = measured = 0.0
    limits = {}
    for name, durs in found.items():
        t_min, limits[name] = flops.roofline_seconds(
            *fl.window_pass(name, batch, heads, want["key_heads"], seq, dim,
                            want["window"]), ctx["peaks"])
        least += t_min * required
        measured += sum(durs) * 1e-9
    ctx["say"](f"roofline of attention over a window of {want['window']} "
               f"({heads} heads on {want['key_heads']} key heads of {dim}, "
               f"{seq} positions): {required} passes required "
               f"({want['window_layers']} a step), events a pass "
               f"{ {k: len(v) for k, v in found.items()} }; least "
               f"{least:.4f} s over measured {measured:.4f} s; bound by "
               f"{limits}")
    return 100.0 * least / measured
