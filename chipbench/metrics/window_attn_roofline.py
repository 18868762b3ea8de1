"""Attention over a window: the least possible time of the ``hetu_swa_fwd*``
and ``hetu_swa_bwd*`` events over their measured device time, both passes
together, in percent.  A pass is REQUIRED once a window layer and step (the
forward events seen over the program's ``window_forward_passes``: a
recomputed forward pass is measured and earns nothing); operations and bytes
are the band's (``flops_laguna.window_pass``: ``w S - w (w - 1) / 2`` pairs a head, 2
products forward and 5 backward, q and o at the query heads, k and v at the
key heads).  None where no such event ran or the program states no window."""
from chipbench import flops, flops_laguna as fl, trace_reduce as tr


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    want = ctx["program"].expected_kernel_shapes()
    if "window_dims" not in want:
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    found = {name: [d for ev in tr.events_holding(
                        t["reduced"], lo, hi, events).values()
                    for _, d, _ in ev]
             for name, events in fl.WINDOW_EVENTS.items()}
    if not all(found.values()):
        return None
    batch, heads, seq, dim = want["window_dims"]
    required = len(found["forward"]) / getattr(
        ctx["program"], "window_forward_passes", 1)
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
               f"{seq} positions): {required:g} passes required, events a "
               f"pass { {k: len(v) for k, v in found.items()} }; least "
               f"{least:.4f} s over measured {measured:.4f} s; bound by "
               f"{limits}")
    return 100.0 * least / measured
