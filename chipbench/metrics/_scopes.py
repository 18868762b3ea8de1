"""Device time a step under named scopes of the program, for any scopes:
what ``_moe.block_ms`` does for the MoE block's four, with control flow.

The program wraps regions in ``jax.named_scope(...)``; XLA keeps the scope in
each instruction's ``op_name``.  As in ``_moe.py`` the compiled step's ENTRY
instructions are keyed by ``trace_reduce.op_key`` in the order of the text,
and within one execution of the step program the i-th device event of a key
is the i-th ENTRY instruction of that key.  Two additions.  A scoped region
may hold a loop (the delta rule's walk over chunk states is a ``while``,
forward and backward): a control-flow event covers the events of its bodies,
so it is taken whole where its ENTRY instruction carries a scope, and the
events that start inside any control-flow event are left to it (their keys
are the bodies', not ENTRY's).  And the scopes are the caller's."""

from __future__ import annotations

import bisect

from chipbench import trace_reduce as tr
from chipbench.metrics._moe import NO_EVENT, OP_NAME, step_hlo
from chipbench.metrics._phases import step_program


def entry_scopes(hlo_text, scopes):
    """``{op_key: [scope or None, ...]}`` of the ENTRY computation's
    instructions, in its order."""
    out, inside = {}, False
    for line in hlo_text.splitlines():
        if line.startswith("ENTRY "):
            inside = True
            continue
        if inside and line.startswith("}"):
            break
        if not inside or " = " not in line:
            continue
        text = line.strip()
        if text.startswith("ROOT "):
            text = text[5:]
        m = OP_NAME.search(text)
        scope = None
        if m:
            scope = next((s for s in scopes if s in m.group(1)), None)
        key = tr.op_key(text)
        if not key.startswith(NO_EVENT):
            out.setdefault(key, []).append(scope)
    return out


def scoped_ms(ctx, scopes, label):
    """``{scope: device ms a step}`` of the operations under ``scopes``, or
    None (no trace, no compiled step, no whole execution in the window, or
    no instruction of the step carries any of the scopes)."""
    t = ctx["trace"]
    if t is None:
        return None
    hlo = step_hlo(ctx)
    if hlo is None:
        return None
    by_key = entry_scopes(hlo, scopes)
    if not any(s for sc in by_key.values() for s in sc):
        ctx["say"](f"{label}: no instruction of the compiled step carries "
                   f"one of {list(scopes)}")
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    program = step_program(t["reduced"]["modules"], lo, hi)
    ms, taken, split = dict.fromkeys(scopes, 0.0), {}, set()
    steps = 0
    for dev, events in t["reduced"]["devices"].items():
        runs = [(s, s + d) for s, d, n in t["reduced"]["modules"].get(dev, ())
                if n == program and lo <= s and s + d <= hi]
        starts = [e[0] for e in events]
        for r_lo, r_hi in runs:
            steps += 1
            inside = events[bisect.bisect_left(starts, r_lo):
                            bisect.bisect_left(starts, r_hi)]
            # outermost control-flow events, in order: one that starts
            # inside another is its parent's
            loops, end = [], r_lo
            for s, d, key in inside:
                if key.startswith(tr.CONTAINERS) and s >= end:
                    loops.append((s, s + d))
                    end = s + d
            loop_starts = [a for a, _ in loops]
            seen = {}
            for s, d, key in inside:
                i = bisect.bisect_right(loop_starts, s) - 1
                within = i >= 0 and s < loops[i][1]
                if within and (s, s + d) != loops[i]:
                    continue            # a body's event: its loop's
                seen.setdefault(key, []).append(d)
            for key, durs in seen.items():
                sc = by_key.get(key)
                if not sc or not any(sc):
                    continue
                if len(sc) != len(durs):
                    split.add(key)
                    durs = [sum(durs) / len(sc)] * len(sc)
                for scope, d in zip(sc, durs):
                    if scope is not None:
                        ms[scope] += d * 1e-6
                        taken[key, scope] = taken.get((key, scope), 0) + d
    if not steps:
        ctx["say"](f"{label}: no whole execution of the step program in the "
                   "traced window")
        return None
    ms = {k: v / steps for k, v in ms.items()}
    top = sorted(taken.items(), key=lambda kv: -kv[1])[:12]
    ctx["say"](f"{label}: {steps} executions of {program!r} in the traced "
               "window; device ms a step by scope: "
               + ", ".join(f"{k} {v:.3f}" for k, v in ms.items()))
    ctx["say"](f"{label}: largest operations taken: " + "; ".join(
        f"{k} [{s}] {d * 1e-6 / steps:.3f} ms" for (k, s), d in top))
    if split:
        ctx["say"](f"{label}: {len(split)} keys showed another number of "
                   f"events than ENTRY has instructions and were split by "
                   f"counts: {sorted(split)[:6]}")
    return ms
