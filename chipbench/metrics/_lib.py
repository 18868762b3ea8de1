"""Arithmetic the readers share.  A reader is ``read(ctx) -> number | None``:
``ctx`` holds the loop's record (``rec``), the benchmark's host spans
(``spans``), a registry snapshot (``registry``), the reduced trace and its
summary (``trace``, None in an untraced run), the configuration and traffic
files (``config``, ``mix``), the cell, the program and the device's peaks.
A reader that finds nothing to read returns None."""

from __future__ import annotations

from chipbench import flops, trace_reduce as tr
from chipbench.loops import STEP_SPANS


def idle_share(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    s = t["summary"]
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


def kernel_events(ctx, names):
    """``{kernel: [durations ns]}`` of device events whose key holds one of
    ``names``, longest name first so that ``_bwd_dq`` is not taken for
    ``_bwd``."""
    t = ctx["trace"]
    if t is None:
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    out = {n: [] for n in names}
    ordered = sorted(names, key=len, reverse=True)
    for events in t["reduced"]["devices"].values():
        for s, d, key in events:
            if lo <= s <= hi:
                for n in ordered:
                    if n in key:
                        out[n].append(d)
                        break
    return out


def passes_due(ctx, a_step, by_device):
    """The passes of each kind the configuration REQUIRES in the traced
    window: ``a_step`` (one a layer application, whatever the program
    recomputes) x the steps of the window, as ``loops.trace_checks`` counts
    them, x the devices on which an event of a pass ran (``by_device``:
    ``{pass: tr.events_holding(..)}``).  Never a count of events: a program
    that runs a forward kernel once where another runs it twice is due the
    same work."""
    steps = tr.count_spans(ctx["trace"]["reduced"]["host"], STEP_SPANS)
    devices = {d for by in by_device.values() for d, ev in by.items() if ev}
    return a_step * steps * len(devices)


def roofline_share(ctx, names, call):
    """Least time over measured time, in percent, of all calls of the
    kernels ``names``; ``call(kernel) -> (operations, bytes)``."""
    found = kernel_events(ctx, names)
    if not found or not any(found.values()):
        return None
    least = measured = 0.0
    limits = set()
    for kernel, durs in found.items():
        if not durs:
            continue
        t_min, limit = flops.roofline_seconds(*call(kernel), ctx["peaks"])
        limits.add(limit)
        least += t_min * len(durs)
        measured += sum(durs) * 1e-9
    ctx["say"](f"roofline of {sorted(k for k, v in found.items() if v)}: "
               f"least {least:.4f} s over measured {measured:.4f} s; bound "
               f"by {sorted(limits)}")
    return 100.0 * least / measured


def flash_roofline(ctx):
    """Flash attention's least possible time over the measured device time
    of its events, forward and backward pass together, in percent.  A
    pass's time is that of every event whose key holds the pass's name
    (``flops.FLASH_PASSES``), however many kernels carry the pass out; a
    training step runs each pass once for every forward call.  Operations
    and bytes are the algorithm's, from the local shard's shapes; under a
    causal mask, which the program states (``expected_kernel_shapes()``'s
    ``causal``, which every builder has to state), it needs half the products
    (keys after the query contribute nothing) and reads and writes the
    tensors whole."""
    t = ctx["trace"]
    if t is None:
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    found = {name: [d for ev in tr.events_holding(
                        t["reduced"], lo, hi, p["events"]).values()
                    for _, d, _ in ev]
             for name, p in flops.FLASH_PASSES.items()}
    if not all(found.values()):
        return None
    want = ctx["program"].expected_kernel_shapes()
    causal = want["causal"]
    calls = len(found["forward"])
    least = measured = 0.0
    limits = {}
    for name, durs in found.items():
        ops, nbytes = flops.flash_pass(name, want["flash_rows"],
                                       ctx["program"].seq, want["head_dim"])
        t_min, limits[name] = flops.roofline_seconds(
            ops / 2.0 if causal else ops, nbytes, ctx["peaks"])
        least += t_min * calls
        measured += sum(durs) * 1e-9
    ctx["say"](f"roofline of {'causal ' if causal else ''}flash attention: "
               f"{calls} forward calls, events a pass "
               f"{ {k: len(v) for k, v in found.items()} }; least "
               f"{least:.4f} s over measured {measured:.4f} s; bound by "
               f"{limits}")
    return 100.0 * least / measured
