"""Unified runtime telemetry: one registry + one tracer per process.

The executor, prefetcher, resilience guard/checkpointer, serving engine,
and PS transport all instrument themselves against the singletons here.
Everything is DISABLED by default — the no-op instrument path costs
~100 ns per call (pinned by ``tests/test_telemetry.py``), so the hot
paths carry their probes unconditionally and a training run pays
nothing until someone calls :func:`enable`.

Four singletons: the :class:`MetricsRegistry` (counters/gauges/
histograms), the :class:`SpanTracer` (host step-phase spans), the
:class:`RequestTrace` (per-rid lifecycle timelines, stitched across
fleet failover), and the :class:`FlightRecorder` (recent-event ring +
incident dumps on any trip).

Typical wiring::

    from hetu_tpu import telemetry
    telemetry.enable(http_port=9100)      # /metrics /healthz /requests
                                          # /incidents live
    ... train / serve ...
    print(telemetry.report())             # snapshot + phase breakdown
    telemetry.shutdown()

``tests/test_chaos_stages.py`` runs every fault stage with exactly this
on and reads the alert, goodput and request-trace planes back.
"""

from __future__ import annotations

from .alerts import (ALERT_STATES, AbsenceRule, AlertManager,
                     BurnRateRule, ThresholdRule, slo_rules)
from .flight import FlightRecorder, INCIDENT_KINDS
from .goodput import (GOODPUT_BUCKETS, LOST_CAUSES, USEFUL_BUCKETS,
                      GoodputLedger)
from .numerics import ANOMALY_KINDS, NumericsMonitor, numerics_report
from .profiling import HBM_POOLS, HbmLedger, ProgramProfiler
from .registry import (DEFAULT_BUCKETS, Counter, Gauge, Histogram,
                       JsonlWriter, MetricsRegistry, MetricsServer,
                       start_http_server)
from .request_trace import EVENT_TYPES, RequestTrace
from . import steps as _steps
from .timeseries import TimeSeriesStore
from .tracing import NULL_SPAN, XLA_EVENTS, SpanTracer

__all__ = ["MetricsRegistry", "Counter", "Gauge", "Histogram",
           "JsonlWriter", "MetricsServer", "SpanTracer", "NULL_SPAN",
           "RequestTrace", "FlightRecorder", "EVENT_TYPES",
           "INCIDENT_KINDS", "DEFAULT_BUCKETS", "start_http_server",
           "HbmLedger", "ProgramProfiler", "HBM_POOLS",
           "NumericsMonitor", "numerics_report", "ANOMALY_KINDS",
           "TimeSeriesStore", "AlertManager", "ThresholdRule",
           "AbsenceRule", "BurnRateRule", "slo_rules", "ALERT_STATES",
           "GoodputLedger", "GOODPUT_BUCKETS", "USEFUL_BUCKETS",
           "LOST_CAUSES", "goodput_report",
           "get_registry", "get_tracer", "get_request_trace",
           "get_flight", "get_hbm_ledger", "get_profiler",
           "get_timeseries", "get_alerts", "get_goodput",
           "enabled", "enable", "disable", "shutdown",
           "report", "step_phase_report", "step_report", "chrome_trace"]

_registry = MetricsRegistry(enabled=False)
_tracer = SpanTracer(capacity=65536, enabled=False)
_request_trace = RequestTrace(enabled=False)
_flight = FlightRecorder(registry=_registry, enabled=False)
_hbm = HbmLedger(registry=_registry)
_profiler = ProgramProfiler(registry=_registry, ledger=_hbm)
# the time-series plane (ISSUE 19): metric history ring, alert rules
# over it, and the goodput ledger — all disabled-by-default, all driven
# by whoever owns a cadence (no collector threads)
_timeseries = TimeSeriesStore(registry=_registry, enabled=False)
_alerts = AlertManager(_timeseries, registry=_registry, flight=_flight,
                       enabled=False)
_goodput = GoodputLedger(registry=_registry, tracer=_tracer,
                         name="process", enabled=False)
# every request event also lands in the flight ring (bounded; the
# recorder gates on its own enabled flag)
_request_trace._sink = _flight.record
# incident dumps carry the HBM ledger snapshot (memory forensics for
# OOM-adjacent trips)
_flight.configure(request_trace=_request_trace, hbm=_hbm.snapshot)
_server = None


def get_registry():
    """The process-wide :class:`MetricsRegistry`."""
    return _registry


def get_tracer():
    """The process-wide :class:`SpanTracer`."""
    return _tracer


def get_request_trace():
    """The process-wide :class:`RequestTrace`."""
    return _request_trace


def get_flight():
    """The process-wide :class:`FlightRecorder`."""
    return _flight


def get_hbm_ledger():
    """The process-wide :class:`HbmLedger` (live-buffer HBM accounting)."""
    return _hbm


def get_profiler():
    """The process-wide :class:`ProgramProfiler`."""
    return _profiler


def get_timeseries():
    """The process-wide :class:`TimeSeriesStore`."""
    return _timeseries


def get_alerts():
    """The process-wide :class:`AlertManager` (rules added by the
    operator / bench; nothing fires out of the box)."""
    return _alerts


def get_goodput():
    """The process-wide :class:`GoodputLedger` (window pinned at
    :func:`enable`)."""
    return _goodput


def goodput_report(**kw):
    """Attribute the process ledger's current window (see
    :meth:`GoodputLedger.account`); ``{"enabled": False}`` while
    telemetry is off."""
    return _goodput.account(**kw)


def enabled():
    return _registry.enabled


def _slo_block():
    """The /slo debug payload: every live FleetController's report.
    Lazy import — serving imports telemetry, never the reverse at
    module load."""
    from ..serving import control
    return control.slo_report()


def _on_xla(event, value=1, **_):
    """The one ``jax.monitoring`` listener (durations and plain events):
    XLA's phases go under the root span open on the calling thread
    (``SpanTracer.xla_event``) and into two counters for scrapes."""
    heard = _tracer.xla_event(event, value)
    if heard is None:
        return
    field, grew = heard
    if field.endswith("_s"):
        _registry.counter(
            "hetu_xla_seconds_total",
            "Seconds of XLA's own phases as jax.monitoring reports them, "
            "inner events inside an outer one counted once: trace "
            "(jaxpr), lower (to MLIR), compile (backend compile, or the "
            "read from the persistent cache, with the executable's "
            "load), cache_load (the cache's retrieval alone, inside "
            "compile)", labels=("phase",)).labels(
                phase=field[len("xla_"):-len("_s")]).inc(max(grew, 0.0))
    else:
        _registry.counter(
            "hetu_xla_cache_total",
            "Programs asked of jax's persistent compilation cache, by "
            "result (hits, misses)", labels=("result",)).labels(
                result=field[len("xla_cache_"):]).inc(grew)


_listening = False


def _listen(on):
    """Register (or take away) the two ``jax.monitoring`` listeners;
    enabling twice registers once, and nothing listens while disabled."""
    global _listening
    if on == _listening:
        return
    from jax import monitoring
    if on:
        monitoring.register_event_duration_secs_listener(_on_xla)
        monitoring.register_event_listener(_on_xla)
    else:
        monitoring.unregister_event_duration_listener(_on_xla)
        monitoring.unregister_event_listener(_on_xla)
    _listening = on


def enable(http_port=None, host="127.0.0.1", incident_dir=None):
    """Turn instruments live; optionally start the HTTP exporter
    (``http_port=0`` binds an ephemeral port) and point the flight
    recorder at an incident-dump directory.  Returns the
    :class:`MetricsServer` when one is (already) running, else None."""
    global _server
    _registry.enable()
    _tracer.enabled = True
    _listen(True)
    import hetu_tpu
    _registry.gauge(
        "hetu_import_seconds",
        "Seconds `import hetu_tpu` took in this process (jax's import "
        "included where the package was the first to ask for it)"
        ).set(getattr(hetu_tpu, "import_seconds", 0.0))
    _request_trace.enabled = True
    _flight.enabled = True
    _timeseries.enabled = True
    _alerts.enabled = True
    _goodput.enabled = True
    _goodput.begin()        # the process goodput window starts here
    if incident_dir is not None:
        _flight.configure(incident_dir=incident_dir)
    if http_port is not None and _server is None:
        _server = start_http_server(
            port=http_port, host=host, registry=_registry,
            debug_providers={
                "/requests": _request_trace.inflight,
                "/incidents": _flight.incidents,
                "/profile": _profiler.report_block,
                "/slo": _slo_block,
                "/numerics": numerics_report,
                "/timeseries": _timeseries.report_block,
                "/alerts": _alerts.report_block,
                "/goodput": _goodput.report_block,
            },
            health_extra=lambda: {"alerts": _alerts.summary()})
    return _server


def disable():
    """Freeze instruments (references stay valid; state is retained)."""
    _registry.disable()
    _tracer.enabled = False
    _listen(False)
    _request_trace.enabled = False
    _flight.enabled = False
    _timeseries.enabled = False
    _alerts.enabled = False
    _goodput.enabled = False


def shutdown():
    """Disable + stop the exporter (if any).  State is retained."""
    global _server
    disable()
    if _server is not None:
        _server.close()
        _server = None


def _sync_loss_gauges(reg=None, tr=None, rt=None, fl=None):
    """Mirror ring occupancy + drop counts into registry gauges so
    silent span/event loss shows up in every snapshot and scrape."""
    reg = reg if reg is not None else _registry
    tr = tr if tr is not None else _tracer
    rt = rt if rt is not None else _request_trace
    fl = fl if fl is not None else _flight
    reg.gauge("hetu_tracer_ring_spans",
              "Host spans retained in the SpanTracer ring").set(len(tr))
    reg.gauge("hetu_tracer_ring_capacity",
              "SpanTracer ring capacity").set(tr.capacity)
    reg.gauge("hetu_tracer_spans_dropped",
              "Host spans that fell off the SpanTracer ring"
              ).set(tr.dropped)
    reg.gauge("hetu_trace_rids_tracked",
              "Request timelines currently retained").set(len(rt))
    reg.gauge("hetu_trace_events_dropped",
              "Request-trace events refused by the per-rid cap"
              ).set(rt.dropped_events)
    reg.gauge("hetu_trace_rids_dropped",
              "Whole request timelines evicted by the rid cap"
              ).set(rt.dropped_rids)
    reg.gauge("hetu_flight_ring_events",
              "Events retained in the flight-recorder ring"
              ).set(len(fl))
    reg.gauge("hetu_flight_events_dropped",
              "Events that fell off the flight-recorder ring"
              ).set(fl.dropped)


# children of SubExecutor.run()'s root span ``run``, whose duration is
# the step histogram's wall time; everything else host-side (data_wait,
# prefetch_h2d) happens between run() calls
_RUN_PHASES = ("h2d", "dispatch", "numerics", "guard_check", "fetch")
_LOOP_PHASES = ("data_wait", "prefetch_h2d")


def step_phase_report(registry=None, tracer=None):
    """Per-step host_gap decomposition from the executor step histogram
    + the tracer's phase spans.

    Returns ``{"steps", "wall_s_per_step", "phases": {...}}`` where the
    phases are ``data_wait`` / ``prefetch_h2d`` (between run() calls),
    ``h2d`` / ``dispatch`` / ``guard_check`` / ``fetch`` (inside run();
    ``fetch`` is the wait for the device and the copy of the results
    when the caller asked for numpy values), and ``device_and_wait`` —
    the remainder of the run() wall time no phase span covers (state
    rebinding, monitor polls, PS pushes).  The phases sum to
    ``wall_s_per_step`` by construction, so the breakdown IS the
    decomposition of the wall step time (host_gap's numerator).
    ``{"steps": 0}`` when no instrumented step has run."""
    reg = registry if registry is not None else _registry
    tr = tracer if tracer is not None else _tracer
    snap = reg.snapshot()
    hist = snap.get("hetu_executor_step_seconds")
    steps = 0
    wall_total = 0.0
    for sample in (hist or {}).get("samples", ()):
        steps += sample["count"]
        wall_total += sample["sum"]
    if steps == 0:
        return {"steps": 0}
    agg = tr.aggregate()
    phases = {}
    run_host = 0.0
    for name in _RUN_PHASES:
        t = agg.get(name, {}).get("total_s", 0.0) / steps
        phases[name] = t
        run_host += t
    run_wall = wall_total / steps
    phases["device_and_wait"] = max(0.0, run_wall - run_host)
    loop_extra = 0.0
    for name in _LOOP_PHASES:
        t = agg.get(name, {}).get("total_s", 0.0) / steps
        phases[name] = t
        loop_extra += t
    wall = max(run_wall, run_host) + loop_extra
    return {"steps": int(steps),
            "wall_s_per_step": round(wall, 9),
            "phases": {k: round(v, 9) for k, v in phases.items()},
            "spans_dropped": tr.dropped}


def step_report(subgraph=None, since=None, until=None, tracer=None,
                say=None):
    """What lay beneath the ``run`` roots of the process ring: steps and
    their wall quantiles, the stalled steps with phase, cause and OS
    account, the run second by second, the XLA phases of the first steps,
    of ``executor_init`` and outside any span, the host.  See
    :func:`hetu_tpu.telemetry.steps.step_report`; None where the ring
    dropped spans."""
    return _steps.step_report(tracer if tracer is not None else _tracer,
                              subgraph=subgraph, since=since, until=until,
                              say=say)


def report(registry=None, tracer=None):
    """Everything ``--telemetry`` appends to a bench detail JSON: the
    registry snapshot (with ring-occupancy/drop gauges synced first),
    the step-phase breakdown, the steps' report (:func:`step_report`),
    the raw per-span aggregates (serving phases etc. that aren't
    executor steps), and the request-trace / incident summary."""
    reg = registry if registry is not None else _registry
    tr = tracer if tracer is not None else _tracer
    if reg is _registry:
        _sync_loss_gauges(reg, tr)
    return {"registry": reg.snapshot(),
            "phases": step_phase_report(reg, tr),
            "steps": step_report(tracer=tr),
            "spans": {k: {"total_s": round(v["total_s"], 6),
                          "count": v["count"],
                          "mean_s": round(v["mean_s"], 9)}
                      for k, v in tr.aggregate().items()},
            "requests": {"tracked": len(_request_trace),
                         "events_dropped": _request_trace.dropped_events,
                         "rids_dropped": _request_trace.dropped_rids},
            "incidents": {"total": _flight.incident_count(),
                          "by_kind": {
                              k: _flight.incident_count(k)
                              for k in INCIDENT_KINDS
                              if _flight.incident_count(k)}},
            "profile": _profiler.report_block(),
            "numerics": numerics_report(),
            "timeseries": _timeseries.report_block(),
            "alerts": _alerts.report_block(),
            "goodput": _goodput.report_block()}


def chrome_trace(jax_trace_dir=None):
    """The merged Chrome-trace view: the SpanTracer's host phase lanes
    (optionally merged with a ``jax.profiler.trace`` capture, see
    :meth:`SpanTracer.chrome_trace`) PLUS the per-rid request lifecycle
    lanes — one pid per engine, one tid per rid — on the tracer's clock
    base, so one Perfetto load shows device ops, host phases, and
    request lifecycles together."""
    doc = _tracer.chrome_trace(jax_trace_dir=jax_trace_dir)
    doc["traceEvents"].extend(
        _request_trace.chrome_rows(epoch=_tracer._epoch))
    return doc
