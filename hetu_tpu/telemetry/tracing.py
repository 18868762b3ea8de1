"""Host-side step-phase span tracer: one span, two clocks.

``jax.profiler`` answers "what did the DEVICE do"; this tracer answers
"where does the HOST step time go".  Hot paths open named spans around
their phases (prefetch ``data_wait``/``prefetch_h2d``, executor ``run``
with its children ``h2d``/``dispatch``/``fetch``/``guard_check``,
serving ``serve_prefill``/``serve_decode``).  An enabled span does two
things from one ``__enter__``/``__exit__``:

* it opens a ``jax.profiler.TraceAnnotation`` named ``hetu:<name>`` (a
  TraceMe: a flag check while no profile is being taken), so while a
  ``jax.profiler`` trace is running the region is in the capture
  itself, on the profiler's clock, on the host lane beside the device's
  operations.  A root given ``step=<n>`` opens a
  ``StepTraceAnnotation`` instead, so XProf's step view finds the
  program's own steps;
* it stamps ``time.perf_counter`` and writes one record into a fixed
  ring: ``(name, start_s, dur_s, parent, key, thread, extra)``.
  ``parent`` is the name of the span open on the same thread when this
  one was opened (None at a root), ``key`` the identifier the root was
  given (children inherit it), ``thread`` is ``threading.get_ident()``.
  Steady-state tracing never allocates unboundedly and never syncs the
  device.

What lay beneath a root.  A span records its wall time; ``extra`` (a
dict, or None) says what the thread did with it:

* a span opened with ``account=True`` (the executor's ``run`` root and
  ``executor_init``) reads the thread's OS account at enter and exit
  and stores the deltas (:data:`ACCOUNT_FIELDS`): user and system CPU
  seconds, voluntary and involuntary context switches, minor and major
  faults (``getrusage(RUSAGE_THREAD)``), the seconds the thread stood
  runnable on a run queue (``/proc/thread-self/schedstat``), the cpu
  it closed on (``sched_getcpu``) and whether that is another than the
  thread's previous accounted span closed on (None at its first).  A source
  the platform lacks reads None.  Once a second such a span also
  stamps the one-minute load average (``load1``);
* XLA's own phases (:data:`XLA_EVENTS`, reported by ``jax.monitoring``
  and handed to :meth:`SpanTracer.xla_event` by the listeners that
  ``telemetry.enable()`` registers) land in the ``extra`` of the ROOT
  of the stack of the thread they arrive on, whatever that root is.
  A duration arrives when its phase ends, so its interval is ``[now -
  dur, now]`` on ``time.perf_counter``: these intervals are built
  after the fact, they are in the ring only, never in a
  ``jax.profiler`` capture.  Durations of one kind are a UNION, not a
  sum: a step's trace holds the traces of every inner ``jit``, which
  end first, and an event whose interval holds earlier ones replaces
  them.  ``xla_s`` is the union over all kinds.  No event is a ring
  record.  With no span open (parameter initialisation by a caller, a
  program built outside ``run``) the same fields accumulate on the
  tracer by thread (:meth:`SpanTracer.outside`).

The ``hetu:`` prefix keeps program spans apart from annotations a
caller puts around the program from outside.

Disabled (the default), ``span()`` hands back a shared no-op context
manager — the whole per-span cost is one flag check plus the ``with``
protocol (~a hundred ns), cheap enough to leave in the executor step
path unconditionally (pinned by the micro-benchmark in
``tests/test_telemetry.py``).

Export: ``aggregate()`` for per-phase totals (the bench's host_gap
decomposition) and ``chrome_trace()`` for chrome://tracing /
Perfetto — optionally MERGED with a ``jax.profiler.trace`` capture's
events in one viewer document.  The ring's events there sit on the
tracer's own clock base; the capture's ``hetu:`` events are the ones on
the device's clock.
"""

from __future__ import annotations

import json
import os
import threading
import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

try:
    import resource
    _RUSAGE_THREAD = resource.RUSAGE_THREAD
except (ImportError, AttributeError):      # not Linux
    resource = _RUSAGE_THREAD = None

__all__ = ["SpanTracer", "NULL_SPAN", "ANNOTATION_PREFIX",
           "ACCOUNT_FIELDS", "XLA_EVENTS", "XLA_FIELDS"]

#: what a program span's name carries in a ``jax.profiler`` capture
ANNOTATION_PREFIX = "hetu:"

#: the deltas of the thread's OS account in an accounted span's ``extra``
#: (beside ``cpu``, ``cpu_changed`` and, once a second, ``load1``)
ACCOUNT_FIELDS = ("cpu_user_s", "cpu_sys_s", "vol_switches",
                  "invol_switches", "minor_faults", "major_faults",
                  "runq_wait_s")

#: ``jax.monitoring`` event -> the field of a root's ``extra`` it feeds.
#: ``backend_compile_duration`` covers the compile OR the read from the
#: persistent cache, with the executable's load; the cache's retrieval
#: time lies inside it.
XLA_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "xla_trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "xla_lower_s",
    "/jax/core/compile/backend_compile_duration": "xla_compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "xla_cache_load_s",
    "/jax/compilation_cache/cache_hits": "xla_cache_hits",
    "/jax/compilation_cache/cache_misses": "xla_cache_misses",
}
XLA_FIELDS = tuple(XLA_EVENTS.values()) + ("xla_s",)
_XLA_COUNTS = ("xla_cache_hits", "xla_cache_misses")
# a listener hears of a phase a few microseconds after it ended, so an
# outer interval's start is known no better than this
_XLA_SLACK_S = 50e-6


def _getcpu():
    """``sched_getcpu`` with the GIL held, or None where there is none."""
    fn = getattr(os, "sched_getcpu", None)
    if fn is not None:
        return fn
    try:
        import ctypes
        fn = ctypes.PyDLL(None).sched_getcpu
        fn.restype, fn.argtypes = ctypes.c_int, ()
        return fn if fn() >= 0 else None
    except (ImportError, OSError, AttributeError):
        return None


class _SchedStat:
    """One thread's ``/proc/thread-self/schedstat``, kept open; the
    descriptor goes with the thread's locals."""

    __slots__ = ("fd",)

    def __init__(self):
        try:
            self.fd = os.open("/proc/thread-self/schedstat", os.O_RDONLY)
        except OSError:         # not Linux, or a sandbox kernel (gVisor)
            self.fd = None

    def runq_ns(self):
        if self.fd is None:
            return None
        try:
            return int(os.pread(self.fd, 96, 0).split()[1])
        except (OSError, IndexError, ValueError):
            return None

    def __del__(self, _close=os.close):     # os may be gone at shutdown
        if self.fd is not None:
            _close(self.fd)


class _Xla:
    """XLA's phases under one root, or on one thread outside any: per
    kind the disjoint intervals heard so far (newest last) after the
    seconds of those too old to be held by a later one, and the cache's
    counts."""

    __slots__ = ("spans", "counts")
    KEEP = 1024          # intervals a kind holds before it looks for old
    HORIZON_S = 3600.0   # no phase of XLA lasts as long: older ones fold

    def __init__(self):
        self.spans = {}
        self.counts = {}

    def _union(self, kind, start, end):
        """Add ``[start, end]``; returns the seconds the union grew by.
        Events on one thread nest or are disjoint and arrive in the
        order they end, so what the new one holds is at the tail (a
        step's trace holds thousands: none is folded while one that
        could hold it may still come)."""
        spans = self.spans.get(kind)
        if spans is None:
            spans = self.spans[kind] = [0.0]
        grew = end - start
        while len(spans) > 1 and spans[-1][0] >= start - _XLA_SLACK_S:
            s, e = spans.pop()
            grew -= e - s
        spans.append((start, end))
        if len(spans) > self.KEEP and spans[1][1] < end - self.HORIZON_S:
            i = 1
            while spans[i][1] < end - self.HORIZON_S:
                spans[0] += spans[i][1] - spans[i][0]
                i += 1
            del spans[1:i]
        return grew

    def add(self, field, value, now):
        if field in _XLA_COUNTS:
            self.counts[field] = self.counts.get(field, 0) + int(value)
            return value
        self._union("xla_s", now - value, now)
        return self._union(field, now - value, now)

    def fields(self):
        out = dict.fromkeys(XLA_FIELDS, 0.0)
        out.update(dict.fromkeys(_XLA_COUNTS, 0))
        for kind, spans in self.spans.items():
            out[kind] = spans[0] + sum(e - s for s, e in spans[1:])
        out.update(self.counts)
        return out


class _NullSpan:
    """Shared do-nothing span (disabled tracer / allocation-free)."""

    __slots__ = ()
    dur = 0.0
    extra = kids = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    """One open span.  After it closed: ``dur``, ``start`` and ``extra``
    (what its ring record holds), and on an accounted span ``kids``,
    the seconds of its direct children by name."""

    __slots__ = ("_tracer", "name", "key", "dur", "start", "extra",
                 "kids", "_step", "_parent", "_ann", "_acct", "_xla")

    def __init__(self, tracer, name, key, step, account):
        self._tracer = tracer
        self.name = name
        self.key = key
        self._step = step
        self._acct = account
        self._xla = None
        self.kids = {} if account else None
        self.extra = None

    def __enter__(self):
        stack = self._tracer._stack()
        if stack:
            top = stack[-1]
            self._parent = top.name
            if self.key is None:
                self.key = top.key
        else:
            self._parent = None
        stack.append(self)
        label = ANNOTATION_PREFIX + self.name
        self._ann = (TraceAnnotation(label) if self._step is None
                     else StepTraceAnnotation(label, step_num=self._step))
        self._ann.__enter__()
        if self._acct:
            self._acct = self._tracer._account()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dur = time.perf_counter() - self.start
        tracer = self._tracer
        extra = None
        if self._acct:
            extra = tracer._account_delta(self._acct, self.start + self.dur)
        self._ann.__exit__(*exc)
        stack = tracer._stack()
        stack.pop()
        if stack:
            kids = stack[-1].kids
            if kids is not None:
                kids[self.name] = kids.get(self.name, 0.0) + self.dur
        if self._xla is not None:
            extra = {**(extra or {}), **self._xla.fields()}
            tracer._xla_closed(self.name, extra["xla_s"])
        self.extra = extra
        tracer._record(self.name, self.start, self.dur, self._parent,
                       self.key, threading.get_ident(), extra)
        return False


class SpanTracer:
    """Fixed-capacity ring of host spans ``(name, start_s, dur_s, parent,
    key, thread, extra)``; each enabled span is also a ``hetu:<name>``
    annotation in a running ``jax.profiler`` trace."""

    def __init__(self, capacity=16384, enabled=False):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        self._buf = [None] * self.capacity
        self._n = 0                      # total spans ever recorded
        self._epoch = time.perf_counter()
        self._open = threading.local()   # per thread: the open spans
        self._getcpu = _getcpu()
        self._load_at = 0.0              # when load1 was last stamped
        self._outside = {}               # thread -> _Xla with no span open
        self._xla_roots = {}             # root name -> xla_s, cumulative

    def span(self, name, key=None, step=None, account=False):
        """Context manager timing one phase; no-op while disabled.

        ``key`` identifies the unit of work a root span covers (a step,
        a request); spans opened inside it on the same thread inherit
        it.  ``step`` marks the span as one step of the program: its
        annotation is a ``StepTraceAnnotation`` with that step number.
        ``account`` has the span read the thread's OS account at enter
        and exit and keep the deltas in its ``extra`` (see the module's
        docstring): two ``getrusage``, two ``pread``, one
        ``sched_getcpu``, for roots and not for their children."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, key, step, account)

    def _stack(self):
        try:
            return self._open.stack
        except AttributeError:
            stack = self._open.stack = []
            return stack

    # -- the thread's OS account ------------------------------------------
    def _account(self):
        """The calling thread's counters now: ``(rusage, run-queue ns)``;
        a source that is not there reads None."""
        try:
            sched = self._open.sched
        except AttributeError:
            sched = self._open.sched = _SchedStat()
        return (resource.getrusage(_RUSAGE_THREAD)
                if _RUSAGE_THREAD is not None else None, sched.runq_ns())

    def _account_delta(self, before, now):
        u0, q0 = before
        u1, q1 = self._account()
        if u0 is not None and u1 is not None:
            out = {"cpu_user_s": u1.ru_utime - u0.ru_utime,
                   "cpu_sys_s": u1.ru_stime - u0.ru_stime,
                   "vol_switches": u1.ru_nvcsw - u0.ru_nvcsw,
                   "invol_switches": u1.ru_nivcsw - u0.ru_nivcsw,
                   "minor_faults": u1.ru_minflt - u0.ru_minflt,
                   "major_faults": u1.ru_majflt - u0.ru_majflt}
        else:
            out = dict.fromkeys(ACCOUNT_FIELDS[:6])
        out["runq_wait_s"] = (None if q0 is None or q1 is None
                              else (q1 - q0) * 1e-9)
        # the cpu is read once, at the close: a system call costs 6 us
        # under a sandbox kernel (gVisor), so the change is told against
        # the cpu this thread's previous accounted span closed on
        cpu = self._getcpu() if self._getcpu is not None else None
        was = getattr(self._open, "cpu", None)
        self._open.cpu = cpu
        out["cpu"] = cpu
        out["cpu_changed"] = (None if cpu is None or was is None
                              else cpu != was)
        if now - self._load_at >= 1.0:
            self._load_at = now
            try:
                out["load1"] = os.getloadavg()[0]
            except OSError:     # no load average here: stop asking
                self._load_at = float("inf")
        return out

    # -- XLA's phases -----------------------------------------------------
    def xla_event(self, event, value=1):
        """One ``jax.monitoring`` event heard on this thread: a duration
        in seconds that has just ended, or a count.  It goes to the root
        of this thread's open spans, or to :meth:`outside`.  Returns
        ``(field, grew)``, what the field's union grew by, or None for an
        event that is not one of :data:`XLA_EVENTS`."""
        field = XLA_EVENTS.get(event)
        if field is None or not self.enabled:
            return None
        now = time.perf_counter()
        stack = self._stack()
        if stack:
            xla = stack[0]._xla
            if xla is None:
                xla = stack[0]._xla = _Xla()
            return field, xla.add(field, value, now)
        thread = threading.get_ident()
        with self._lock:
            xla = self._outside.get(thread)
            if xla is None:
                xla = self._outside[thread] = _Xla()
            return field, xla.add(field, value, now)

    def outside(self):
        """``{thread: {field: value}}`` of the XLA events heard with no
        span open."""
        with self._lock:
            return {t: x.fields() for t, x in self._outside.items()}

    def _xla_closed(self, name, seconds):
        with self._lock:
            self._xla_roots[name] = self._xla_roots.get(name, 0.0) + seconds

    def xla_seconds(self):
        """``{root name: seconds}``: ``xla_s`` of every closed root since
        the last :meth:`clear`, whatever the ring still holds (the
        goodput ledger's ``compile`` sink)."""
        with self._lock:
            return dict(self._xla_roots)

    def _record(self, name, t0, dur, parent=None, key=None, thread=None,
                extra=None):
        with self._lock:
            self._buf[self._n % self.capacity] = (name, t0, dur, parent,
                                                  key, thread, extra)
            self._n += 1

    def __len__(self):
        return min(self._n, self.capacity)

    @property
    def dropped(self):
        """Spans that fell off the ring (total recorded - retained)."""
        return max(0, self._n - self.capacity)

    def clear(self):
        with self._lock:
            self._buf = [None] * self.capacity
            self._n = 0
            self._epoch = time.perf_counter()
            self._outside = {}
            self._xla_roots = {}
            self._load_at = 0.0

    def spans(self):
        """Retained spans, oldest first (by the time they closed):
        ``[(name, start_s, dur_s, parent, key, thread, extra)]``."""
        with self._lock:
            n, cap = self._n, self.capacity
            if n <= cap:
                return [s for s in self._buf[:n]]
            i = n % cap
            return self._buf[i:] + self._buf[:i]

    def aggregate(self):
        """{name: {total_s, count, mean_s}} over the retained spans."""
        agg = {}
        for name, _, dur, *_ in self.spans():
            slot = agg.setdefault(name, [0.0, 0])
            slot[0] += dur
            slot[1] += 1
        return {name: {"total_s": t, "count": c, "mean_s": t / c}
                for name, (t, c) in sorted(agg.items())}

    # -- Chrome-trace export ----------------------------------------------
    def chrome_trace(self, jax_trace_dir=None, pid=1 << 20):
        """Trace-event JSON (``{"traceEvents": [...]}``) of the retained
        spans — complete ``X`` events in microseconds relative to the
        tracer epoch, on one process lane named ``hetu host spans``,
        one thread lane per recording thread, with ``parent``,
        ``key`` and the fields of ``extra`` in ``args``.

        ``jax_trace_dir``: a ``jax.profiler.trace`` output directory
        whose newest capture's events are merged in ahead of ours, so
        one chrome://tracing load shows XLA device lanes next to the
        host phases.  The ring's events keep the tracer's clock base
        (jax's capture epoch is not recoverable host-side); spans that
        ran while the capture was being taken are ALSO in it, as
        ``hetu:<name>`` events on the device's clock."""
        captured_events = []
        if jax_trace_dir is not None:
            import gzip
            from ..timeline import _latest_trace_json
            captured = json.loads(
                gzip.open(_latest_trace_json(jax_trace_dir)).read())
            captured_events = list(captured.get("traceEvents", []))
        events = [
            {"ph": "M", "pid": pid, "name": "process_name",
             "args": {"name": "hetu host spans"}},
        ]
        tids = {}
        for name, t0, dur, parent, key, thread, extra in self.spans():
            tid = tids.get(thread)
            if tid is None:
                tid = tids[thread] = len(tids)
                events.append(
                    {"ph": "M", "pid": pid, "tid": tid,
                     "name": "thread_name",
                     "args": {"name": "step phases" if tid == 0
                              else f"step phases (thread {tid})"}})
            events.append({"ph": "X", "pid": pid, "tid": tid,
                           "name": name, "ts": (t0 - self._epoch) * 1e6,
                           "dur": dur * 1e6,
                           "args": {"parent": parent, "key": key,
                                    **(extra or {})}})
        return {"traceEvents": captured_events + events,
                "displayTimeUnit": "ms"}

    def export_chrome(self, path, jax_trace_dir=None):
        """Write :meth:`chrome_trace` to ``path``; returns the path."""
        doc = self.chrome_trace(jax_trace_dir=jax_trace_dir)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path
