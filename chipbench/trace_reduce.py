"""From a profiler trace to intervals and from intervals to numbers.

Stage one (``load``) reads an ``.xplane.pb`` with ``jax.profiler
.ProfileData`` and keeps two things: per device, the events of its
"XLA Ops" line (every operation that ran on that device), and from the host
plane the benchmark's own ``TraceAnnotation`` spans.  Both are on the
profiler's one clock, in nanoseconds.  Stage two is interval arithmetic on
plain tuples, so it can be checked on a small recorded trace
(``python -m chipbench.selfcheck``) and read by a reviewer.

A copy, with the missing half added, of the idea in ``hetu_tpu/timeline
.trace_aggregates`` (per-op totals from the Chrome JSON): this one has the
union of busy intervals, the idle share and the attribution of idle gaps.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: control-flow operations contain the operations of their bodies, which
#: the line lists too: they are left out of per-operation totals (the union
#: of busy intervals does not care)
CONTAINERS = ("while", "conditional", "call")


def newest_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


HLO_EVENT = re.compile(r"^%?([\w.-]+?)(?:\.\d+)? = (.*?) [\w-]+\(")


def op_key(name):
    """A stable name for one device operation.  The chip's trace names an
    event by its HLO text, ``%copy.12 = f32[2049,8,8,16,128]{...} copy(...)``:
    the key is the operation without its numeric suffix (for a Pallas kernel
    that is the name its ``pallas_call`` was given) and the result's shape
    without its layout, so ``copy.12`` and ``copy.40`` of different arrays
    stay apart and the same operation keeps its key across compilations."""
    m = HLO_EVENT.match(name)
    if not m:
        return re.sub(r"\.\d+$", "", name)[:96]
    dims = re.sub(r"\{[^}]*\}", "", m.group(2))
    return (m.group(1) + "_" + re.sub(r"[^A-Za-z0-9]+", "_", dims)
            .strip("_"))[:96]


#: element types as HLO text spells them, by the name jax.numpy gives them
HLO_DTYPES = {"bfloat16": "bf16", "float16": "f16", "float32": "f32"}
RESULT = re.compile(r"(?:^|_)(pred|bf16|[fsuc]\d+)((?:_\d+)*)(?=_|$)")


def first_result(key, after=""):
    """``(dtype, dims)`` of the first result in an ``op_key``, looked for
    after the first occurrence of ``after`` (an operation's own name may
    hold what reads like a type), or None: ``("bf16", (768, 512, 64))`` of
    ``jvp_hetu_flash_fwd__bf16_768_512_64_f32_768_1_512``.  A key is cut at
    96 characters; a first result that does not fit reads short, not right."""
    at = key.find(after)
    m = RESULT.search(key, at + len(after)) if at >= 0 else None
    if not m:
        return None
    return m.group(1), tuple(int(d) for d in m.group(2).split("_")[1:])


def load(path, annotations):
    """``{"devices": {id: [(start, dur, key), ...]}, "modules": {id:
    [(start, dur, program), ...]}, "host": [(start, dur, name), ...]}`` of
    one ``.xplane.pb``, each list sorted by start: per device the
    operations and the whole-program executions, and the host spans named
    in ``annotations``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = sorted(
                        (float(e.start_ns), float(e.duration_ns),
                         op_key(e.name))
                        for e in line.events)
                elif line.name == MODULES_LINE:
                    modules[int(m.group(1))] = sorted(
                        (float(e.start_ns), float(e.duration_ns), e.name)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in annotations:
                        host.append((float(e.start_ns),
                                     float(e.duration_ns), e.name))
    return {"devices": devices, "modules": modules, "host": sorted(host)}


def save(reduced, path):
    with gzip.open(path, "wt") as f:
        json.dump({"devices": {str(k): v
                               for k, v in reduced["devices"].items()},
                   "modules": {str(k): v
                               for k, v in reduced["modules"].items()},
                   "host": reduced["host"]}, f)


def load_saved(path):
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {"devices": {int(k): [tuple(e) for e in v]
                        for k, v in raw["devices"].items()},
            "modules": {int(k): [tuple(e) for e in v]
                        for k, v in raw["modules"].items()},
            "host": [tuple(e) for e in raw["host"]]}


# -- interval arithmetic ------------------------------------------------------

def union(intervals):
    """Disjoint sorted ``[(start, end), ...]`` covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals):
    return sum(e - s for s, e in intervals)


def busy(events, lo, hi):
    """Disjoint intervals inside ``[lo, hi]`` in which some operation of
    ``events`` ran."""
    return clip(union((s, s + d) for s, d, _ in events), lo, hi)


def gaps(busy_intervals, lo, hi):
    """The complement of ``busy_intervals`` inside ``[lo, hi]``."""
    out, at = [], lo
    for s, e in busy_intervals:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def overlap(disjoint, lo, hi):
    """Length of ``[lo, hi]`` covered by the disjoint sorted intervals."""
    i = bisect.bisect_left(disjoint, (lo, lo))
    if i:
        i -= 1
    got = 0.0
    while i < len(disjoint) and disjoint[i][0] < hi:
        got += max(0.0, min(disjoint[i][1], hi) - max(disjoint[i][0], lo))
        i += 1
    return got


def window_of(host, step_names):
    """``(lo, hi)``: from the start of the first step-level host span to
    the end of the last one.  The traced window is what the benchmark's
    loop ran, not what the profiler was switched on for."""
    steps = [(s, s + d) for s, d, n in host if n in step_names]
    if not steps:
        raise ValueError(f"the trace holds no host span of {step_names}")
    return min(s for s, _ in steps), max(e for _, e in steps)


def count_spans(host, names):
    """How many host spans named in ``names`` the trace holds: the steps of
    the traced window, which ``window_of`` draws around them."""
    return sum(1 for _, _, n in host if n in names)


def events_holding(reduced, lo, hi, part):
    """``{device: [(start, dur, key), ...]}``: for every device on which an
    operation started in ``[lo, hi]``, those of them whose key holds
    ``part`` (an empty list where none does; all of them for ``""``)."""
    out = {}
    for dev, events in reduced["devices"].items():
        inside = [e for e in events if lo <= e[0] <= hi]
        if inside:
            out[dev] = [e for e in inside if part in e[2]]
    return out


def op_totals(events, lo, hi):
    """``{key: seconds}`` of the operations that started in ``[lo, hi]``,
    control-flow containers left out."""
    out = {}
    for s, d, key in events:
        if lo <= s <= hi and not key.startswith(CONTAINERS):
            out[key] = out.get(key, 0.0) + d * 1e-9
    return out


def attribute_gaps(gap_list, host, order):
    """Name each idle gap by the host span that covers most of it.
    ``order`` lists span names from innermost to outermost: of the spans
    that overlap a gap, the innermost kind with the largest overlap names
    it, and ``"unattributed"`` where none does.  Returns ``[(name, seconds),
    ...]``, longest first."""
    by_name = {n: [] for n in order}
    for s, d, n in host:
        if n in by_name:
            by_name[n].append((s, s + d))
    by_name = {n: union(v) for n, v in by_name.items()}
    out = []
    for lo, hi in gap_list:
        name = "unattributed"
        for n in order:
            if overlap(by_name[n], lo, hi) >= 0.5 * (hi - lo):
                name = n
                break
        out.append((name, (hi - lo) * 1e-9))
    return sorted(out, key=lambda g: -g[1])


def summary(reduced, step_names, span_order, top=10):
    """The numbers every traced run reports: the window, per device the
    busy seconds, their mean, the largest operations and the longest idle
    gaps by what the host was doing."""
    lo, hi = window_of(reduced["host"], step_names)
    per_device, ops, gap_names = {}, {}, []
    for dev, events in sorted(reduced["devices"].items()):
        b = busy(events, lo, hi)
        if not b:
            continue
        per_device[dev] = total(b) * 1e-9
        for key, sec in op_totals(events, lo, hi).items():
            ops[key] = ops.get(key, 0.0) + sec
        if not gap_names:       # gaps of the first device that did work
            gap_names = attribute_gaps(gaps(b, lo, hi), reduced["host"],
                                       span_order)
    n = max(1, len(per_device))
    return {
        "lo": lo, "hi": hi, "window_s": (hi - lo) * 1e-9,
        "busy_s_per_device": per_device,
        "busy_s": sum(per_device.values()) / n,
        "device_ops": sorted(((k, v / n) for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": gap_names[:top],
    }
