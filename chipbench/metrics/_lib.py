"""Arithmetic the readers share.  A reader is ``read(ctx) -> number | None``:
``ctx`` holds the loop's record (``rec``), the benchmark's host spans
(``spans``), a registry snapshot (``registry``), the reduced trace and its
summary (``trace``, None in an untraced run), the configuration and traffic
files (``config``, ``mix``), the cell, the program and the device's peaks.
A reader that finds nothing to read returns None."""

from __future__ import annotations

import numpy as np

from chipbench import flops, trace_reduce as tr


def host_ms_outside_device(ctx, span):
    """Mean over the traced spans named ``span`` of the span's length less
    the time an operation ran on the device inside it, in ms: what the
    host spent with the device waiting.  Averaged over the devices."""
    t = ctx["trace"]
    if t is None:
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    spans = [(s, s + d) for s, d, n in t["reduced"]["host"]
             if n == span and lo <= s and s + d <= hi]
    busy = [tr.busy(ev, lo, hi) for ev in t["reduced"]["devices"].values()]
    busy = [b for b in busy if b]
    if not spans or not busy:
        return None
    covered = np.mean([[tr.overlap(b, s, e) for s, e in spans]
                       for b in busy], axis=0)
    return float(np.mean([(e - s) - c for (s, e), c
                          in zip(spans, covered)]) * 1e-6)


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
