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
  ring: ``(name, start_s, dur_s, parent, key, thread)``.  ``parent`` is
  the name of the span open on the same thread when this one was opened
  (None at a root), ``key`` the identifier the root was given (children
  inherit it), ``thread`` is ``threading.get_ident()``.  Steady-state
  tracing never allocates unboundedly and never syncs the device.

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
import threading
import time

from jax.profiler import StepTraceAnnotation, TraceAnnotation

__all__ = ["SpanTracer", "NULL_SPAN", "ANNOTATION_PREFIX"]

#: what a program span's name carries in a ``jax.profiler`` capture
ANNOTATION_PREFIX = "hetu:"


class _NullSpan:
    """Shared do-nothing span (disabled tracer / allocation-free)."""

    __slots__ = ()
    dur = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tracer", "name", "key", "dur", "_step", "_parent",
                 "_ann", "_t0")

    def __init__(self, tracer, name, key, step):
        self._tracer = tracer
        self.name = name
        self.key = key
        self._step = step

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
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dur = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        self._tracer._stack().pop()
        self._tracer._record(self.name, self._t0, self.dur,
                             self._parent, self.key,
                             threading.get_ident())
        return False


class SpanTracer:
    """Fixed-capacity ring of host spans ``(name, start_s, dur_s, parent,
    key, thread)``; each enabled span is also a ``hetu:<name>``
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

    def span(self, name, key=None, step=None):
        """Context manager timing one phase; no-op while disabled.

        ``key`` identifies the unit of work a root span covers (a step,
        a request); spans opened inside it on the same thread inherit
        it.  ``step`` marks the span as one step of the program: its
        annotation is a ``StepTraceAnnotation`` with that step number."""
        if not self.enabled:
            return NULL_SPAN
        return _Span(self, name, key, step)

    def _stack(self):
        try:
            return self._open.stack
        except AttributeError:
            stack = self._open.stack = []
            return stack

    def _record(self, name, t0, dur, parent=None, key=None, thread=None):
        with self._lock:
            self._buf[self._n % self.capacity] = (name, t0, dur, parent,
                                                  key, thread)
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

    def spans(self):
        """Retained spans, oldest first (by the time they closed):
        ``[(name, start_s, dur_s, parent, key, thread)]``."""
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
        one thread lane per recording thread, with ``parent`` and
        ``key`` in ``args``.

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
        for name, t0, dur, parent, key, thread in self.spans():
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
                           "args": {"parent": parent, "key": key}})
        return {"traceEvents": captured_events + events,
                "displayTimeUnit": "ms"}

    def export_chrome(self, path, jax_trace_dir=None):
        """Write :meth:`chrome_trace` to ``path``; returns the path."""
        doc = self.chrome_trace(jax_trace_dir=jax_trace_dir)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path
