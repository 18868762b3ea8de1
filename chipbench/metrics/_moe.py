"""What the ``moe_*`` readers share.

Counters.  ``hetu_tpu.layers.moe.record_moe_load`` counts, per layer and
step, the (token, choice) pairs routed and dropped and sets the gauge of the
fullest expert's load over the mean, from the ``[2, E]`` vector the step
fetches beside its loss.  A program without them gives None.

Device time of the MoE block.  The program wraps the block's regions in
``jax.named_scope("hetu_moe_route" | "_dispatch" | "_experts" |
"_combine")``; XLA keeps the scope in each instruction's ``op_name``
(backward instructions as ``transpose(jvp(hetu_moe_...))``).  The reduced
trace names a device event by ``trace_reduce.op_key`` of its HLO text
(operation and result shape), which drops the scope.  So the reader asks the
program for the compiled step's HLO (``SubExecutor.lower_compiled``, a
lowering and a compile-cache read) and applies the same ``op_key`` to every
instruction of the ENTRY computation, in the order of the text, which for a
scheduled module is the order of execution.  Within one execution of the
step program (the device's modules line) the i-th event of a key is then
the i-th ENTRY instruction of that key, and takes its scope.  Where a step
shows another number of events of a key than ENTRY has instructions (an
instruction that leaves no event of its own, or more than one), the key's
time is split over its instructions' scopes by their counts, and the reader
says for which keys.
"""

from __future__ import annotations

import bisect
import re

from chipbench import trace_reduce as tr
from chipbench.metrics._phases import step_program

SCOPES = ("hetu_moe_route", "hetu_moe_dispatch", "hetu_moe_experts",
          "hetu_moe_combine")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def sample(ctx, name):
    """``{layer: value}`` of the registry series ``name``."""
    metric = ctx["registry"].get(name)
    if not metric or not metric["samples"]:
        return None
    return {s["labels"].get("layer"): s["value"] for s in metric["samples"]}


#: operations that run nothing on the device
NO_EVENT = ("bitcast", "get-tuple-element", "tuple", "parameter", "constant",
            "after-all", "partition-id", "replica-id")


def entry_scopes(hlo_text):
    """``{op_key: [scope or None, ...]}`` of the instructions of the ENTRY
    computation of an HLO module's text, in its order."""
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
            scope = next((s for s in SCOPES if s in m.group(1)), None)
        key = tr.op_key(text)
        if not key.startswith(NO_EVENT):
            out.setdefault(key, []).append(scope)
    return out


def step_hlo(ctx):
    """The compiled train step's HLO text, or None with the reason said."""
    try:
        sub = ctx["program"].ex.subexecutor["train"]
        return sub.lower_compiled().as_text()
    except Exception as e:      # noqa: BLE001 - any program, any jax
        ctx["say"](f"moe: the program gave no compiled step to read scopes "
                   f"from ({type(e).__name__}: {e})")
        return None


def block_ms(ctx):
    """``({scope: ms a step}, taken)`` of the device operations inside the
    MoE block's scopes, or None."""
    t = ctx["trace"]
    if t is None or sample(ctx, "hetu_moe_pairs_routed_total") is None:
        return None
    hlo = step_hlo(ctx)
    if hlo is None:
        return None
    scopes = entry_scopes(hlo)
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    program = step_program(t["reduced"]["modules"], lo, hi)
    ms, taken, split = dict.fromkeys(SCOPES, 0.0), {}, set()
    steps = 0
    for dev, events in t["reduced"]["devices"].items():
        runs = [(s, s + d) for s, d, n in t["reduced"]["modules"].get(dev, ())
                if n == program and lo <= s and s + d <= hi]
        starts = [e[0] for e in events]
        for r_lo, r_hi in runs:
            steps += 1
            by_key = {}
            for s0, d, key in events[bisect.bisect_left(starts, r_lo):
                                     bisect.bisect_left(starts, r_hi)]:
                if not key.startswith(tr.CONTAINERS):
                    by_key.setdefault(key, []).append(d)
            for key, durs in by_key.items():
                sc = scopes.get(key)
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
        ctx["say"]("moe: no whole execution of the step program in the "
                   "traced window")
        return None
    ms = {k: v / steps for k, v in ms.items()}
    top = sorted(taken.items(), key=lambda kv: -kv[1])[:12]
    ctx["say"](f"moe: {steps} executions of {program!r} in the traced "
               "window; device ms a step by scope (the i-th event of a key "
               "in an execution is the i-th ENTRY instruction of that key in "
               "the compiled step, whose op_name holds the scope): "
               + ", ".join(f"{k} {v:.3f}" for k, v in ms.items()))
    ctx["say"]("moe: largest operations taken: " + "; ".join(
        f"{k} [{s[9:]}] {d * 1e-6 / steps:.3f} ms" for (k, s), d in top))
    if split:
        ctx["say"](f"moe: {len(split)} keys showed another number of events "
                   f"than ENTRY has instructions and were split by counts: "
                   f"{sorted(split)[:6]}")
    return ms
