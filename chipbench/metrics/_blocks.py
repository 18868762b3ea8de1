"""The step program's device time a step by named block: what the readers
``step_unscoped_device_share``, ``optim_``, ``attn_block_``, ``head_loss_`` and
``mlp_block_device_ms_per_step`` share.

The program names its blocks itself: a graph node built under ``with
ht.scope("hetu_attn")`` is traced under ``jax.named_scope("hetu_attn")``, XLA
keeps the name in every instruction's ``op_name`` (backward instructions as
``transpose(jvp(hetu_attn))``), and ``ht.scopes()`` reads back every name the
program has given.  This reader holds no list of its own: one row for every
name of ``ht.scopes()``, so a new layer's scope shows up by itself.  An
instruction belongs to the name that stands LAST in its ``op_name`` (a region
named inside one node's computation wins over the node's own name).  Three
more rows make the account whole:

* ``collectives``: all-reduce, all-gather, reduce-scatter, all-to-all,
  collective-permute and their ``-start`` / ``-done``, whatever scope the
  producer that GSPMD hung them on carries;
* ``unscoped``: an ``op_name`` with no block's name in it: the program's to
  mend (``docs/PROFILING.md``);
* ``no_op_name``: XLA left the instruction no metadata (its own copies for
  aliasing and layout, operations a pass rewrote), and events whose key no
  ENTRY instruction has, or only one that ``_scopes.py`` leaves out for its
  name (a fusion named ``bitcast_...``; its block is said).  No scope can reach them; for the largest keys the
  reader says the block of the instruction that produces the first operand
  of the key's longest instruction, and of its first user.

Events are keyed to instructions as ``_scopes.py`` does it (the i-th event of a
key inside one execution of the step program is the i-th ENTRY instruction of
that key; a control-flow event is taken whole and the events of its bodies
are left to it; a key that shows another number of events than ENTRY has
instructions is split over them by counts), so a block's row is what
``_scopes.scoped_ms`` reads for the same name.  The rows add up to every
event inside the step program's executions.  A program without
``ht.scopes`` (a parent commit's) gives None, said, not raised."""

from __future__ import annotations

import bisect
import re

from chipbench import trace_reduce as tr
from chipbench.metrics._scopes import (NO_EVENT, OP_NAME, step_hlo,
                                       step_program)

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
OTHER_ROWS = ("collectives", "unscoped", "no_op_name")
INSTRUCTION = re.compile(r"^%?([\w.-]+) = .*? ([\w-]+)\((.*)$")
OPERAND = re.compile(r"%?([A-Za-z_][\w.-]*)")
TOP = 12


def row_of(op_name, names):
    """The block an ``op_name`` belongs to: the name that stands last in
    it, ``unscoped`` where it holds none, ``no_op_name`` where there is no
    ``op_name``."""
    if not op_name:
        return "no_op_name"
    at, row = max((op_name.rfind(n), n) for n in names)
    return row if at >= 0 else "unscoped"


def entry_instructions(hlo_text, names):
    """The ENTRY computation's instructions in its order, each ``{"name",
    "key", "opcode", "operands", "op_name", "row"}``."""
    out, inside = [], False
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
        m = INSTRUCTION.match(text)
        if not m:
            continue
        op = OP_NAME.search(text)
        op_name = op.group(1) if op else None
        args = m.group(3).split(")")[0]
        row = ("collectives" if m.group(2).startswith(COLLECTIVES)
               else row_of(op_name, names))
        out.append({"name": m.group(1), "key": tr.op_key(text),
                    "opcode": m.group(2), "operands": OPERAND.findall(args),
                    "op_name": op_name, "row": row})
    return out


def neighbours(insts):
    """``describe(i) -> (producer, user)``: in words, the block of the ENTRY
    instruction that produces instruction ``i``'s first operand and of its
    first user, looking through operations that run nothing (bitcasts,
    tuple elements) and through others without metadata (the two halves of
    an asynchronous copy); a parameter is said by its own ``op_name``."""
    at = {inst["name"]: i for i, inst in enumerate(insts)}
    users = {}
    for i, inst in enumerate(insts):
        for name in inst["operands"]:
            users.setdefault(name, []).append(i)

    def quiet(inst):
        return inst["opcode"] != "parameter" and (
            inst["opcode"] in NO_EVENT or inst["row"] == "no_op_name")

    def say(inst):
        if inst["opcode"] == "parameter":
            return f"parameter {inst['op_name'] or inst['name']}"
        return f"{inst['row']} ({inst['key']})"

    def producer(i):
        for _ in range(8):
            first = next((at[n] for n in insts[i]["operands"] if n in at),
                         None)
            if first is None:
                return "none"
            if not quiet(insts[first]):
                return say(insts[first])
            i = first
        return "none"

    def user(i):
        for _ in range(8):
            first = users.get(insts[i]["name"], [None])[0]
            if first is None:
                return "none (a result of the step)"
            if not quiet(insts[first]):
                return say(insts[first])
            i = first
        return "none"

    return lambda i: (producer(i), user(i))


def compute(ctx):
    """``{row: device ms a step}`` over ``ht.scopes()`` and ``OTHER_ROWS``,
    or None with the reason said."""
    say, t = ctx["say"], ctx["trace"]
    if t is None:
        return None
    import hetu_tpu as ht
    if not hasattr(ht, "scopes"):
        say("blocks: this program has no `ht.scopes()`: its graph nodes "
            "carry no block names to account by")
        return None
    names = tuple(ht.scopes())
    hlo = step_hlo(ctx)
    if hlo is None or not names:
        return None
    insts = entry_instructions(hlo, names)
    by_key, hidden = {}, {}
    for i, inst in enumerate(insts):
        if not inst["key"].startswith(NO_EVENT):
            by_key.setdefault(inst["key"], []).append(i)
        elif inst["opcode"] not in NO_EVENT:
            # a fusion NAMED after a bitcast runs, but `_scopes.py` takes
            # its key for one that does not: it stays out of the blocks'
            # rows here too, so that they read what `_scopes.py` reads
            hidden.setdefault(inst["key"], set()).add(inst["row"])
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    modules = t["reduced"]["modules"]
    program = step_program(modules, lo, hi)
    ms = dict.fromkeys(names + OTHER_ROWS, 0.0)
    taken, longest, split, steps, stray = {}, {}, set(), 0, 0.0
    busy_in = busy_all = 0.0
    others = {}
    for dev, events in t["reduced"]["devices"].items():
        runs = [(s, s + d) for s, d, n in modules.get(dev, ())
                if n == program and lo <= s and s + d <= hi]
        for s, d, n in modules.get(dev, ()):
            if n != program and lo <= s <= hi:
                others[n] = others.get(n, 0.0) + d
        busy = tr.busy(events, lo, hi)
        busy_all += tr.total(busy)
        starts = [e[0] for e in events]
        for r_lo, r_hi in runs:
            steps += 1
            busy_in += tr.overlap(busy, r_lo, r_hi)
            inside = events[bisect.bisect_left(starts, r_lo):
                            bisect.bisect_left(starts, r_hi)]
            loops, end = [], r_lo
            for s, d, key in inside:
                if key.startswith(tr.CONTAINERS) and s >= end:
                    loops.append((s, s + d))
                    end = s + d
            loop_starts = [a for a, _ in loops]
            seen = {}
            for s, d, key in inside:
                i = bisect.bisect_right(loop_starts, s) - 1
                if i >= 0 and s < loops[i][1] and (s, s + d) != loops[i]:
                    continue            # a body's event: its loop's
                seen.setdefault(key, []).append(d)
            for key, durs in seen.items():
                idx = by_key.get(key)
                if not idx:
                    ms["no_op_name"] += sum(durs) * 1e-6
                    stray += sum(durs)
                    taken[key, "no_op_name"] = taken.get(
                        (key, "no_op_name"), 0) + sum(durs)
                    continue
                if len(idx) != len(durs):
                    split.add(key)
                    durs = [sum(durs) / len(idx)] * len(idx)
                for i, d in zip(idx, durs):
                    row = insts[i]["row"]
                    ms[row] += d * 1e-6
                    taken[key, row] = taken.get((key, row), 0) + d
                    if d > longest.get((key, row), (0, None))[0]:
                        longest[key, row] = (d, i)
    if not steps:
        say("blocks: no whole execution of the step program in the traced "
            "window")
        return None
    ms = {k: v / steps for k, v in ms.items()}
    total = sum(ms.values())
    say(f"blocks: {steps} executions of {program!r} in the traced window; "
        "device ms a step by block (an instruction belongs to the name that "
        "stands last in its op_name; loops taken whole):")
    for row, v in sorted(ms.items(), key=lambda kv: -kv[1]):
        say(f"blocks:   {row:<20} {v:10.3f} ms  {100 * v / total:6.2f}%")
    s = t["summary"]
    say(f"blocks:   {'sum':<20} {total:10.3f} ms: every event inside the "
        f"step program's executions; their union {busy_in * 1e-6 / steps:.3f}"
        f" ms; the device's busy time a step {busy_all * 1e-6 / steps:.3f} ms"
        f" ((1 - device_idle_share) x window / steps, window "
        f"{s['window_s']:.3f} s, busy {s['busy_s']:.3f} s); the difference "
        f"{(busy_all - busy_in) * 1e-6 / steps:.3f} ms is other programs and "
        "executions cut by the window's edges: "
        + ("; ".join(f"{n} {d * 1e-6 / steps:.3f} ms a step"
                     for n, d in sorted(others.items(),
                                        key=lambda kv: -kv[1])[:6])
           or "no other program ran"))
    top = sorted(taken.items(), key=lambda kv: -kv[1])
    say("blocks: largest operations, by key and block: " + "; ".join(
        f"{key} [{row}] {d * 1e-6 / steps:.3f} ms"
        for (key, row), d in top[:3 * TOP]))
    describe = neighbours(insts)
    for want in OTHER_ROWS[1:]:
        for (key, row), d in [kv for kv in top if kv[0][1] == want][:TOP]:
            line = f"blocks: {row}: {key} {d * 1e-6 / steps:.3f} ms"
            i = longest.get((key, row), (0, None))[1]
            if i is None:
                line += (" (no ENTRY instruction that `_scopes.py` keys has "
                         "this key"
                         + (f"; by its opcode it runs, in {sorted(hidden[key])}"
                            if key in hidden else "") + ")")
            elif row == "unscoped":
                line += f" op_name={insts[i]['op_name']!r}"
            else:
                made, used = describe(i)
                line += (f" in {len(by_key[key])} instructions; the longest's"
                         f" operand from {made}; its first user {used}")
            say(line)
    if stray:
        say(f"blocks: {stray * 1e-6 / steps:.3f} ms a step of events whose "
            "key no ENTRY instruction has stand under no_op_name")
    if split:
        say(f"blocks: {len(split)} keys showed another number of events than "
            f"ENTRY has instructions and were split by counts: "
            f"{sorted(split)[:6]}")
    return ms


def block_ms(ctx, *rows):
    """The sum of ``rows`` of the table, computed once a run; None where
    there is no table or it has none of ``rows``."""
    if "blocks" not in ctx:
        ctx["blocks"] = compute(ctx)
    table = ctx["blocks"]
    if table is None or not any(r in table for r in rows):
        return None
    return sum(table.get(r, 0.0) for r in rows)


def unscoped_share(ctx):
    """``unscoped`` + ``no_op_name`` over the table's sum, in percent."""
    rest = block_ms(ctx, "unscoped", "no_op_name")
    if rest is None:
        return None
    return 100.0 * rest / sum(ctx["blocks"].values())

