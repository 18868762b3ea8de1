"""What a step's root says once it has closed: which steps stalled, in
which phase and why, and where a run's seconds went.

Two users, one rule.  ``SubExecutor`` keeps a :class:`StepWatch` a
subgraph and hands it every ``run`` root as it closes (wall time, the
seconds of its children, its ``extra``); the watch answers with the time
over the running median and, for a stalled step, its phase and cause.
:func:`step_report` replays the ring's roots through fresh watches, so
what it lists is what the executor counted, from the ring alone.

The rule.  The median is over the last :data:`WINDOW` roots that carried
no XLA event; it stands once :data:`STEADY` are behind it.  A root over
it by :data:`OVER_SHARE` of it and by :data:`OVER_S` is a stall, and so
is any root that carries an XLA event once the median stands, whatever
its length (a recompile or a cache read inside a run).  ``phase`` is the
one of :data:`PARTS` with the largest excess over its own running median
(``run_self``: the root less all its children).  ``cause`` is the first
of :data:`CAUSES` that holds: ``xla`` (an XLA event under the root),
``paging`` (a major fault), ``runq`` (run-queue wait at least half the
excess: another thread had the core), ``host_cpu`` (the thread's own CPU
seconds at least half the excess: Python, the allocator, the collector),
else ``blocked`` (the thread slept: it waited on the device or the
runtime).
"""

from __future__ import annotations

import bisect
import os
import statistics
from collections import deque

__all__ = ["StepWatch", "step_report", "PHASES", "PARTS", "CAUSES",
           "WINDOW", "STEADY", "OVER_SHARE", "OVER_S"]

PHASES = ("h2d", "dispatch", "fetch")
PARTS = PHASES + ("run_self",)
CAUSES = ("xla", "paging", "runq", "host_cpu", "blocked")
WINDOW = 64
STEADY = 8
OVER_SHARE = 0.25
OVER_S = 0.050

# what by_second sums of a root's account
_SUMMED = ("vol_switches", "invol_switches", "minor_faults", "major_faults",
           "runq_wait_s")


def parts_of(wall, kids):
    """Seconds of each of :data:`PARTS` of one root, in that order."""
    get = kids.get
    return (get("h2d", 0.0), get("dispatch", 0.0), get("fetch", 0.0),
            wall - sum(kids.values()))


def has_xla(extra):
    return extra is not None and "xla_s" in extra


def cause_of(extra, excess):
    if has_xla(extra):
        return "xla"
    acct = extra or {}
    if (acct.get("major_faults") or 0) > 0:
        return "paging"
    if (acct.get("runq_wait_s") or 0.0) >= excess / 2:
        return "runq"
    cpu = (acct.get("cpu_user_s") or 0.0) + (acct.get("cpu_sys_s") or 0.0)
    return "host_cpu" if cpu >= excess / 2 else "blocked"


class StepWatch:
    """The running medians of one subgraph's roots."""

    def __init__(self):
        self._steady = deque()     # (wall, *parts) of the last WINDOW
        self._walls = []           # their walls, sorted

    def median(self):
        """The running median, or None before :data:`STEADY` roots."""
        n = len(self._walls)
        return self._walls[n // 2] if n >= STEADY else None

    def close(self, wall, kids, extra):
        """One closed root -> ``(excess_s, stall)``: the seconds over the
        median (0.0 under it, or with none yet) and, for a stalled step,
        ``{"phase", "cause", "median_s", "excess_s", "phases"}``."""
        walls = self._walls
        n = len(walls)
        xla = has_xla(extra)
        excess, stall = 0.0, None
        if n >= STEADY:
            med = walls[n // 2]
            excess = wall - med
            if xla or (excess > OVER_SHARE * med and excess > OVER_S):
                parts = parts_of(wall, kids)
                over = [v - statistics.median(s[i + 1] for s in self._steady)
                        for i, v in enumerate(parts)]
                stall = {"phase": PARTS[over.index(max(over))],
                         "cause": cause_of(extra, excess),
                         "median_s": med, "excess_s": excess,
                         "phases": dict(zip(PARTS, parts))}
        if not xla:
            steady = self._steady
            if n == WINDOW:
                del walls[bisect.bisect_left(walls, steady.popleft()[0])]
            steady.append((wall, *parts_of(wall, kids)))
            bisect.insort(walls, wall)
        return (excess if excess > 0.0 else 0.0), stall


def _roots(spans):
    """``(record, kids)`` of every root span, in the order they closed;
    ``kids`` the seconds of a root's direct children by name."""
    pending = {}
    for rec in spans:
        if rec[3] is None:
            kids = {}
            for name, start, dur in pending.pop(rec[5], ()):
                if start >= rec[1]:
                    kids[name] = kids.get(name, 0.0) + dur
            yield rec, kids
        elif rec[3] == "run" or rec[3] == "executor_init":
            pending.setdefault(rec[5], []).append((rec[0], rec[1], rec[2]))


def _quantile(values, q):
    values = sorted(values)
    return values[min(len(values) - 1, int(q * len(values)))]


def _affinity():
    try:
        return sorted(os.sched_getaffinity(0))
    except (AttributeError, OSError):
        return None


def step_report(tracer, subgraph=None, since=None, until=None, say=None):
    """Where the steps' time went, from the ring alone.

    ``subgraph`` keeps one subgraph's ``run`` roots; ``since`` / ``until``
    (``time.perf_counter`` seconds, the ring's clock) keep what began in
    ``[since, until)``: hand the start of a measured window as ``until``
    for the set-up's records and as ``since`` for the window's.  None
    where the ring dropped spans (``say``, if given, is told why): the
    medians of a ring with holes are not the executor's.

    * ``steps`` and ``wall_s`` (``p50`` / ``p90`` / ``p99`` / ``max``, over
      the roots with no XLA event), ``excess_share`` (the roots' time over
      the running median as a share of their time: what the executor
      counts in ``hetu_executor_step_excess_seconds_total``);
    * ``stalls``: ``key``, ``start_s``, ``wall_s``, ``phase``, ``cause``,
      ``median_s``, ``excess_s``, ``phases``, ``account`` (the root's
      ``extra``) of each, by the rule of this module;
    * ``by_second``: for each second since ``since`` (else the first
      root) that holds a root: ``steps``, mean ``wall_s`` and ``h2d_s`` /
      ``dispatch_s`` / ``fetch_s`` / ``run_self_s``, the summed switches,
      faults, ``runq_wait_s`` and ``cpu_s``, the ``cpus`` seen, ``load1``
      where a root stamped it;
    * ``first_steps``: each ``run`` root with XLA events (``key``,
      ``start_s``, ``wall_s``, the ``xla_*`` fields); ``executor_init``:
      each such root with its ``extra``; ``xla_by_root``: ``xla_s`` summed
      by root name over every root; ``outside``: the fields heard with
      no span open, summed over threads (no times: never filtered).
      Together they are the program's share of a set-up;
    * ``host``: ``cpu_count``, ``affinity``, ``load1_first`` /
      ``load1_last`` (the first and the last stamp in the range).
    """
    if tracer.dropped:
        if say is not None:
            say(f"steps: the ring dropped {tracer.dropped} spans; nothing "
                "is reported from a ring with holes")
        return None
    lo = float("-inf") if since is None else since
    hi = float("inf") if until is None else until
    watches = {}
    steady, stalls, first, inits, by_root = [], [], [], [], {}
    seconds, loads = {}, []
    excess_s = wall_s = n_steps = 0
    origin = since
    for rec, kids in _roots(tracer.spans()):
        name, start, wall, _, key, _, extra = rec
        inside = lo <= start < hi
        if has_xla(extra) and inside:
            by_root[name] = by_root.get(name, 0.0) + extra["xla_s"]
        if name == "executor_init":
            if inside:
                inits.append({"key": key, "start_s": start, "wall_s": wall,
                              "phases": kids, **(extra or {})})
            continue
        if name != "run":
            continue
        sub = str(key).rsplit(":", 1)[0]
        if subgraph is not None and sub != subgraph:
            continue
        excess, stall = watches.setdefault(sub, StepWatch()).close(
            wall, kids, extra)
        if not inside:
            continue
        n_steps += 1
        if has_xla(extra):
            first.append({"key": key, "start_s": start, "wall_s": wall,
                          **{k: v for k, v in extra.items()
                             if k.startswith("xla_")}})
        else:
            steady.append(wall)
        excess_s += excess
        wall_s += wall
        if stall is not None:
            stalls.append({"key": key, "start_s": start, "wall_s": wall,
                           **stall, "account": extra})
        acct = extra or {}
        if "load1" in acct:
            loads.append(acct["load1"])
        if origin is None:
            origin = start
        sec = seconds.get(int(start - origin))
        if sec is None:
            sec = seconds[int(start - origin)] = {
                "steps": 0, "wall_s": 0.0, "cpu_s": 0.0, "cpus": set(),
                **{p + "_s": 0.0 for p in PARTS},
                **dict.fromkeys(_SUMMED, 0)}
        sec["steps"] += 1
        sec["wall_s"] += wall
        for p, v in zip(PARTS, parts_of(wall, kids)):
            sec[p + "_s"] += v
        for k in _SUMMED:
            sec[k] += acct.get(k) or 0
        sec["cpu_s"] += ((acct.get("cpu_user_s") or 0.0)
                         + (acct.get("cpu_sys_s") or 0.0))
        if acct.get("cpu") is not None:
            sec["cpus"].add(acct["cpu"])
        if "load1" in acct:
            sec["load1"] = acct["load1"]
    by_second = []
    for i, sec in sorted(seconds.items()):
        n = sec["steps"]
        for k in ("wall_s",) + tuple(p + "_s" for p in PARTS):
            sec[k] /= n
        by_second.append({"second": i, **sec, "cpus": sorted(sec["cpus"])})
    outside = {}
    for fields in tracer.outside().values():
        for k, v in fields.items():
            outside[k] = outside.get(k, 0) + v
    return {
        "steps": n_steps,
        "wall_s": ({"p50": _quantile(steady, 0.5),
                    "p90": _quantile(steady, 0.9),
                    "p99": _quantile(steady, 0.99),
                    "max": max(steady)} if steady else None),
        "excess_share": excess_s / wall_s if wall_s else None,
        "stalls": stalls,
        "by_second": by_second,
        "first_steps": first,
        "executor_init": inits,
        "xla_by_root": by_root,
        "outside": outside,
        "host": {"cpu_count": os.cpu_count(), "affinity": _affinity(),
                 "load1_first": loads[0] if loads else None,
                 "load1_last": loads[-1] if loads else None},
    }
