"""The device's idle time a step, split over the executor's phases: what the
five readers ``idle_{h2d,dispatch,fetch,run_self,outside_run}_ms_per_step``
share.

Where the numbers come from.  The program instruments itself
(``hetu_tpu/graph/executor.py``): every ``SubExecutor.run`` opens a root span
``run`` keyed ``<subgraph>:<global step>`` with the children ``h2d`` (feeds
canonicalised, cast and uploaded), ``dispatch`` (the jitted call) and
``fetch`` (the wait for the device and the copy of the results to the host).
``hetu_tpu/telemetry/tracing.py`` writes each into the process's ring as
``(name, start_s, dur_s, parent, key, thread)`` on ``time.perf_counter``.
``run.py`` calls ``telemetry.enable()`` in every run, so a reader reaches the
ring with ``hetu_tpu.telemetry.get_tracer().spans()``; nothing is handed
over through ``ctx``.  A program without these spans (the ring's records
have three fields, or no ``run`` root) gives None for all five, with the
reason said.

Clock.  The ring is on the host clock, the device's operations on the
profiler's.  The benchmark's own ``executor_run`` spans are on both, stamped
microseconds apart by one ``with``: ``ctx["spans"]["executor_run"]`` and
``ctx["trace"]["reduced"]["host"]``.  The host spans that began at or after
``rec["trace_started_at"]`` pair with the trace's in order from the last
backwards; the offset is the median difference of their starts.  Fewer than
three pairs, or differences spread (largest less smallest) beyond 50 us,
give None.  (The same spans are in the capture as ``hetu:<name>`` events, on
the profiler's clock with no pairing; reading them there needs
``trace_reduce.load`` to keep them, which is a change to a file that is
there.)

Attribution.  Inside the summary's window, per device that did work, the idle
gaps (``trace_reduce.gaps(busy(...))``) are laid over the spans of the thread
that called ``run``: a nanosecond goes to the innermost of ``h2d``,
``dispatch``, ``fetch`` that covers it, else to ``run`` (its own bookkeeping,
and any other child: ``compile``, ``numerics``, ``guard_check``), else to
"outside run" (the caller holds the thread).  One correction: the jitted call
returns before the device has begun, so idle time inside ``fetch`` before
the start of that step's program on the device (``reduced["modules"]``, the
program with the most device time) is ``dispatch``'s: the launch was still
under way.  Idle time inside ``fetch`` from the program's start on stays the
fetch's; the reader says how much of it lies before the program's end (gaps
between its operations) and how much after.  The five are averaged over the
devices, divided by the number of ``run`` roots wholly inside the window, and
add up to the device's idle time a step.

How sharp.  The pairing puts the ring on the profiler's HOST plane to a
microsecond.  The device planes' clock is the profiler's own affair: on a v5e
the upload's cast programs show on the device 0.7 to 1.6 ms before the
``h2d`` span that issues them opens (my chip runs, PR 24).  The reader says
the largest such lead it sees; the sum of the five does not depend on it,
the line between two neighbouring parts does.  For the same reason a step's
program is looked for from 2 ms before its ``dispatch`` opened.
"""

from __future__ import annotations

import bisect
import statistics

from chipbench import trace_reduce as tr

PHASES = ("h2d", "dispatch", "fetch")
PARTS = PHASES + ("run_self", "outside_run")
MIN_PAIRS = 3
MAX_SPREAD_S = 50e-6
CLOCK_SLACK_NS = 2e6
STEP_SPAN = "executor_run"


def program_spans(say):
    """The ring's records, or None with the reason said."""
    from hetu_tpu import telemetry
    tracer = telemetry.get_tracer()
    if tracer.dropped:
        say(f"phases: the program's ring dropped {tracer.dropped} spans; "
            "nothing is attributed from a ring with holes")
        return None
    spans = tracer.spans()
    if not any(len(r) >= 6 and r[0] == "run" for r in spans):
        say("phases: the program's ring holds no `run` root span (this "
            "program does not instrument its executor)")
        return None
    return spans


def clock_offset(ctx):
    """Seconds to add to a host time to get the profiler's, or None with
    the reason said."""
    since = ctx["rec"]["trace_started_at"]
    on_host = sorted(s for s, _ in ctx["spans"].get(STEP_SPAN, ())
                     if s >= since)
    in_trace = sorted(s for s, _, n in ctx["trace"]["reduced"]["host"]
                      if n == STEP_SPAN)
    n = min(len(on_host), len(in_trace))
    if n < MIN_PAIRS:
        ctx["say"](f"phases: {n} `{STEP_SPAN}` spans pair between the host "
                   f"clock and the trace; {MIN_PAIRS} are needed")
        return None
    diffs = [t * 1e-9 - h for h, t in zip(on_host[-n:], in_trace[-n:])]
    spread = max(diffs) - min(diffs)
    offset = statistics.median(diffs)
    ctx["say"](f"phases: clock: {n} traced `{STEP_SPAN}` spans paired (host "
               f"clock has {len(on_host)}, trace {len(in_trace)}); profiler "
               f"= perf_counter {offset:+.6f} s, differences spread "
               f"{spread * 1e6:.2f} us")
    if spread > MAX_SPREAD_S:
        ctx["say"](f"phases: the spread exceeds {MAX_SPREAD_S * 1e6:.0f} "
                   "us: the two clocks are not aligned well enough")
        return None
    return offset


def step_program(modules, lo, hi):
    """The name of the program with the most device time in the window."""
    seconds = {}
    for events in modules.values():
        for s, d, name in events:
            if lo <= s <= hi:
                seconds[name] = seconds.get(name, 0.0) + d
    return max(seconds, key=seconds.get) if seconds else None


def run_thread(spans):
    """The records of the thread that opened the most ``run`` roots."""
    threads = [r[5] for r in spans if r[0] == "run"]
    thread = max(set(threads), key=threads.count)
    return [r for r in spans if r[5] == thread]


def steps_of(spans, offset, lo, hi):
    """``[{name: (start, end)}, ...]`` in profiler ns: per ``run`` root of
    ``spans`` wholly inside the window, the root and its children."""
    def ns(rec):
        start = (rec[1] + offset) * 1e9
        return start, start + rec[2] * 1e9

    steps = {r[4]: {"run": ns(r)} for r in spans if r[0] == "run"
             and lo <= ns(r)[0] and ns(r)[1] <= hi}
    for r in spans:
        if r[3] == "run" and r[4] in steps:
            steps[r[4]][r[0]] = ns(r)
    return sorted(steps.values(), key=lambda st: st["run"])


def programs_of(steps, program_runs):
    """For each step, in order, the ``(start, end)`` of the execution of the
    step program it launched, or None: the first execution not yet taken
    that began no more than ``CLOCK_SLACK_NS`` before the step's
    ``dispatch`` opened.  A program cannot begin before it is dispatched,
    but the device planes' clock runs ahead of the host plane's (see "How
    sharp"), so with a launch of a few hundred microseconds it seems to."""
    out, j = [], 0
    for st in steps:
        since = st.get("dispatch", st["run"])[0] - CLOCK_SLACK_NS
        while j < len(program_runs) and program_runs[j][0] < since:
            j += 1
        out.append(program_runs[j] if j < len(program_runs) else None)
        j += 1
    return out


def attribute(gap_list, steps, programs):
    """Nanoseconds of the disjoint sorted ``gap_list`` by part, and of the
    fetch's share the nanoseconds before each step's program ended.
    ``programs`` is ``programs_of(steps, ...)`` for this device, or None
    where the trace has no modules line: then nothing moves from ``fetch``
    to ``dispatch``."""
    out = dict.fromkeys(PARTS, 0.0)
    fetch_in_program = 0.0
    in_run = 0.0
    for st, program in zip(steps, programs or [None] * len(steps)):
        in_run += tr.overlap(gap_list, *st["run"])
        for name in ("h2d", "dispatch"):
            if name in st:
                out[name] += tr.overlap(gap_list, *st[name])
        if "fetch" not in st:
            continue
        f_lo, f_hi = st["fetch"]
        begun, ended = f_lo, f_lo
        if programs:
            begun, ended = program or (f_hi, f_hi)
            begun = min(max(begun, f_lo), f_hi)
            ended = min(max(ended, begun), f_hi)
        out["dispatch"] += tr.overlap(gap_list, f_lo, begun)
        out["fetch"] += tr.overlap(gap_list, begun, f_hi)
        fetch_in_program += tr.overlap(gap_list, begun, ended)
    out["run_self"] = in_run - sum(out[p] for p in PHASES)
    out["outside_run"] = tr.total(gap_list) - in_run
    return out, fetch_in_program


def clock_lead(programs, steps):
    """Nanoseconds by which each of ``programs`` (start times on a device)
    began before the next ``h2d`` or ``dispatch`` span opened, for those
    that began inside neither.  The host issues a program only from inside
    one of the two (``fetch`` and the rest of ``run`` launch nothing), so
    the device plane's clock leads the host plane's by at least the
    largest."""
    issuing = sorted(st[name] for st in steps for name in ("h2d", "dispatch")
                     if name in st)
    leads = []
    for t in programs:
        i = bisect.bisect_right(issuing, (t, float("inf")))
        if (i == 0 or issuing[i - 1][1] < t) and i < len(issuing):
            leads.append(issuing[i][0] - t)
    return leads


def host_means(spans, t_lo, t_hi):
    """``(means, n)``: mean seconds of each phase over the ``n`` ``run``
    roots of ``spans`` that began in ``[t_lo, t_hi)`` on the host clock."""
    keys = {r[4] for r in spans if r[0] == "run" and t_lo <= r[1] < t_hi}
    tot = {}
    for r in spans:
        if r[4] in keys and (r[0] == "run" or r[3] == "run"):
            tot[r[0]] = tot.get(r[0], 0.0) + r[2]
    means = {k: v / len(keys) for k, v in tot.items()}
    if keys:
        means["run_self"] = 2 * means["run"] - sum(means.values())
    return means, len(keys)


def counter(registry, name, subgraph):
    for s in registry.get(name, {}).get("samples", ()):
        if s.get("labels", {}).get("subgraph") == subgraph:
            return s["value"]
    return None


def compute(ctx):
    """``{part: ms a step}`` of ``PARTS``, or None with the reason said."""
    say = ctx["say"]
    if ctx["trace"] is None:
        return None
    reduced, summ = ctx["trace"]["reduced"], ctx["trace"]["summary"]
    lo, hi = summ["lo"], summ["hi"]
    worked = {dev: b for dev, b in (
        (dev, tr.busy(ev, lo, hi))
        for dev, ev in sorted(reduced["devices"].items())) if b}
    if not worked:
        say("phases: the trace has no device plane with work in the "
            "window; nothing to attribute")
        return None
    spans = program_spans(say)
    offset = None if spans is None else clock_offset(ctx)
    if offset is None:
        return None
    spans = run_thread(spans)
    steps = steps_of(spans, offset, lo, hi)
    if not steps:
        say("phases: no `run` root lies wholly inside the traced window")
        return None

    program = step_program(reduced["modules"], lo, hi)
    parts = dict.fromkeys(PARTS, 0.0)
    in_program = idle = 0.0
    launches, leads, executions = [], [], {}
    for dev, b in worked.items():
        runs = sorted((s, s + d) for s, d, name
                      in reduced["modules"].get(dev, ()) if name == program)
        executions[dev] = sum(1 for s, _ in runs if lo <= s <= hi)
        gap_list = tr.gaps(b, lo, hi)
        programs = programs_of(steps, runs) if runs else None
        got, inside = attribute(gap_list, steps, programs)
        for k, v in got.items():
            parts[k] += v
        in_program += inside
        idle += tr.total(gap_list)
        launches += [program[0] - st["dispatch"][0]
                     for st, program in zip(steps, programs or ())
                     if "dispatch" in st and program]
        leads += clock_lead([s for s, _, name in reduced["modules"].get(
            dev, ()) if name != program and lo <= s <= hi], steps)
    per = 1e-6 / (len(worked) * len(steps))      # ns summed -> ms a step
    parts = {k: v * per for k, v in parts.items()}

    say(f"phases: {len(steps)} `run` roots wholly inside the traced window; "
        f"step program {program!r} ran {executions} times on the devices' "
        "modules lines")
    say("phases: device-idle ms a step: "
        + ", ".join(f"{k} {parts[k]:.4f}" for k in PARTS)
        + f"; sum {sum(parts.values()):.4f} against idle time a step "
        f"{idle * per:.4f} (device_idle_share x window / steps)")
    say(f"phases: of fetch's {parts['fetch']:.4f} ms, {in_program * per:.4f}"
        " lie before the step program's end (gaps between its operations) "
        f"and {parts['fetch'] - in_program * per:.4f} after it")
    if launches:
        say(f"phases: from a `dispatch` span's start to the step program's "
            f"start on the device: mean {statistics.mean(launches) * 1e-6:.4f}"
            f" ms, least {min(launches) * 1e-6:.4f}, most "
            f"{max(launches) * 1e-6:.4f} over {len(launches)}")

    if leads:
        say(f"phases: {len(leads)} device programs other than the step's "
            "began outside every `h2d` and `dispatch` span, up to "
            f"{max(leads) * 1e-6:.4f} ms before the next one opened: the "
            "device plane's clock leads the host plane's by at least that, "
            "and the line between two neighbouring parts is no sharper")
    rec = ctx["rec"]
    started = rec["trace_started_at"]
    for label, t_lo, t_hi in (
            ("before the profiler started", rec["t0"], started),
            ("with the profiler on", started, float("inf"))):
        means, n = host_means(spans, t_lo, t_hi)
        say(f"phases: host clock, {n} steps {label}: mean ms "
            + ", ".join(f"{k} {v * 1e3:.4f}" for k, v in sorted(
                means.items())))
    subgraph = next(r[4] for r in reversed(spans)
                    if r[0] == "run").split(":")[0]
    sent = counter(ctx["registry"], "hetu_executor_h2d_bytes_total", subgraph)
    n_run = counter(ctx["registry"], "hetu_executor_steps_total", subgraph)
    h2d = host_means(spans, rec["t0"], float("inf"))[0].get("h2d")
    if sent is not None and n_run and h2d:
        say(f"phases: h2d uploads {sent / n_run:.0f} bytes a step "
            f"({subgraph}: {sent:.0f} bytes over {n_run:.0f} steps), "
            f"{sent / n_run / h2d / 1e6:.1f} MB/s over the mean h2d span "
            f"of {h2d * 1e3:.4f} ms")
    return parts


def reader(part):
    """``read(ctx)`` for one of ``PARTS``; the work is done once a run."""
    def read(ctx):
        if "phases" not in ctx:
            ctx["phases"] = compute(ctx)
        return None if ctx["phases"] is None else ctx["phases"][part]
    return read
