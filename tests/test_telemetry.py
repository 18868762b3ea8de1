"""Unified runtime telemetry (hetu_tpu/telemetry/): registry semantics,
Prometheus exposition, the stdlib HTTP exporter, the span tracer, the
instrumented executor/prefetch/guard hot paths, and — critically — the
disabled-mode cost contract: every instrument is a near-free no-op until
``telemetry.enable()``, so the step path can carry its probes
unconditionally."""

import json
import mmap
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.graph.executor import SubExecutor
from hetu_tpu.telemetry import (JsonlWriter, MetricsRegistry, SpanTracer,
                                start_http_server)
from hetu_tpu.telemetry import steps as steps_mod
from hetu_tpu.telemetry import tracing as tracing_mod


@pytest.fixture
def tel():
    """Fresh, ENABLED process-wide telemetry; restored to disabled."""
    telemetry.get_registry().reset()
    telemetry.get_tracer().clear()
    telemetry.enable()
    yield telemetry
    telemetry.disable()


# ---------------- registry semantics ----------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(2)
    g = reg.gauge("g", "a gauge")
    g.set(5)
    g.dec(2)
    h = reg.histogram("h_seconds", "a histogram", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(100.0)
    snap = reg.snapshot()
    assert snap["c_total"]["samples"][0]["value"] == 3
    assert snap["g"]["samples"][0]["value"] == 3.0
    hs = snap["h_seconds"]["samples"][0]
    assert hs["count"] == 3
    assert hs["sum"] == pytest.approx(100.55)
    assert hs["buckets"] == [[0.1, 1], [1.0, 1]]  # per-bucket, not cum


def test_labels_resolve_distinct_series():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("steps_total", "steps", labels=("subgraph",))
    c.labels(subgraph="train").inc(3)
    c.labels(subgraph="eval").inc()
    # same labels -> same child object (pre-resolved hot path)
    assert c.labels(subgraph="train") is c.labels(subgraph="train")
    snap = reg.snapshot()
    by = {s["labels"]["subgraph"]: s["value"]
          for s in snap["steps_total"]["samples"]}
    assert by == {"train": 3, "eval": 1}
    with pytest.raises(ValueError):
        c.labels(wrong="x")
    with pytest.raises(ValueError):
        c.inc()          # labeled metric needs .labels(...)


def test_registry_caches_by_name_and_rejects_kind_conflicts():
    reg = MetricsRegistry(enabled=True)
    a = reg.counter("x_total", "x")
    assert reg.counter("x_total") is a
    with pytest.raises(ValueError):
        reg.gauge("x_total")
    with pytest.raises(ValueError):
        reg.counter("x_total", labels=("l",))


def test_counter_rejects_negative_and_histogram_bad_buckets():
    reg = MetricsRegistry(enabled=True)
    with pytest.raises(ValueError):
        reg.counter("n_total").inc(-1)
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(1.0, 0.5))


def test_snapshot_isolation():
    """A snapshot is a deep copy: later updates don't mutate it, and
    mutating it doesn't corrupt the registry."""
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("c_total")
    h = reg.histogram("h_seconds", buckets=(1.0,))
    c.inc()
    h.observe(0.5)
    snap = reg.snapshot()
    c.inc(10)
    h.observe(0.1)
    assert snap["c_total"]["samples"][0]["value"] == 1
    assert snap["h_seconds"]["samples"][0]["count"] == 1
    snap["h_seconds"]["samples"][0]["buckets"][0][1] = 999
    assert reg.snapshot()["h_seconds"]["samples"][0]["buckets"][0][1] == 2
    json.dumps(snap)      # JSON-safe by construction


def test_disabled_registry_is_inert():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("c_total")
    g = reg.gauge("g")
    h = reg.histogram("h")
    c.inc(5)
    g.set(3)
    h.observe(1.0)
    snap = reg.snapshot()
    assert snap["c_total"]["samples"][0]["value"] == 0
    assert snap["h"]["samples"][0]["count"] == 0
    reg.enable()
    c.inc()               # same reference goes live after enable()
    assert reg.snapshot()["c_total"]["samples"][0]["value"] == 1


# ---------------- Prometheus exposition ----------------

def test_prometheus_text_golden():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("hetu_test_total", "help text", labels=("stage",))
    c.labels(stage="a").inc(3)
    g = reg.gauge("hetu_depth", "queue depth")
    g.set(3)
    h = reg.histogram("hetu_lat_seconds", "lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(5.0)
    assert reg.to_prometheus() == (
        "# HELP hetu_depth queue depth\n"
        "# TYPE hetu_depth gauge\n"
        "hetu_depth 3\n"
        "# HELP hetu_lat_seconds lat\n"
        "# TYPE hetu_lat_seconds histogram\n"
        'hetu_lat_seconds_bucket{le="0.1"} 1\n'
        'hetu_lat_seconds_bucket{le="1"} 1\n'
        'hetu_lat_seconds_bucket{le="+Inf"} 2\n'
        "hetu_lat_seconds_sum 5.05\n"
        "hetu_lat_seconds_count 2\n"
        "# HELP hetu_test_total help text\n"
        "# TYPE hetu_test_total counter\n"
        'hetu_test_total{stage="a"} 3\n')


def test_prometheus_escapes_label_values():
    reg = MetricsRegistry(enabled=True)
    c = reg.counter("esc_total", "e", labels=("p",))
    c.labels(p='a"b\nc').inc()
    text = reg.to_prometheus()
    assert 'esc_total{p="a\\"b\\nc"} 1' in text


# ---------------- HTTP exporter ----------------

def test_metrics_endpoint_http_round_trip():
    reg = MetricsRegistry(enabled=True)
    reg.counter("hetu_rt_total", "round trip").inc(7)
    with start_http_server(port=0, registry=reg) as srv:
        body = urllib.request.urlopen(
            f"{srv.url}/metrics", timeout=5).read().decode()
        assert "hetu_rt_total 7" in body
        assert "# TYPE hetu_rt_total counter" in body
        health = json.loads(urllib.request.urlopen(
            f"{srv.url}/healthz", timeout=5).read())
        assert health["status"] == "ok"
        assert health["telemetry_enabled"] is True
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{srv.url}/nope", timeout=5)


# ---------------- span tracer ----------------

def test_tracer_ring_buffer_wraps():
    tr = SpanTracer(capacity=4, enabled=True)
    for i in range(6):
        with tr.span(f"s{i}"):
            pass
    assert len(tr) == 4
    assert tr.dropped == 2
    names = [s[0] for s in tr.spans()]
    assert names == ["s2", "s3", "s4", "s5"]       # oldest first
    agg = tr.aggregate()
    assert set(agg) == {"s2", "s3", "s4", "s5"}
    assert all(v["count"] == 1 and v["total_s"] >= 0
               for v in agg.values())


def test_tracer_disabled_records_nothing():
    tr = SpanTracer(capacity=4, enabled=False)
    with tr.span("x"):
        pass
    assert len(tr) == 0


def test_chrome_trace_json_validity(tmp_path):
    tr = SpanTracer(capacity=8, enabled=True)
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    doc = json.loads(open(path).read())
    events = doc["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    assert {e["name"] for e in xs} == {"outer", "inner"}
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0
    # the host lane is named for the viewer
    meta = [e for e in events if e.get("ph") == "M"]
    assert any(e["args"]["name"] == "hetu host spans" for e in meta)


def test_chrome_trace_merges_jax_capture(tmp_path):
    """chrome_trace(jax_trace_dir=...) prepends the newest capture's
    events, so device lanes and host phases share one viewer doc."""
    import gzip
    cap = tmp_path / "plugins" / "profile" / "2026_08_04"
    cap.mkdir(parents=True)
    device_events = [{"ph": "X", "pid": 7, "tid": 1, "name": "fusion.1",
                     "ts": 10.0, "dur": 5.0}]
    with gzip.open(cap / "host.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": device_events}, f)
    tr = SpanTracer(capacity=8, enabled=True)
    with tr.span("dispatch"):
        pass
    doc = tr.chrome_trace(jax_trace_dir=str(tmp_path))
    names = [e.get("name") for e in doc["traceEvents"]]
    assert "fusion.1" in names and "dispatch" in names
    with pytest.raises(FileNotFoundError):
        tr.chrome_trace(jax_trace_dir=str(tmp_path / "nope"))


def test_span_records_parent_key_and_thread():
    """A record is (name, start, dur, parent, key, thread): the parent
    is the span open on the SAME thread, children inherit the root's
    key, and a second thread's span is no child of the main thread's."""
    import threading

    tr = SpanTracer(capacity=16, enabled=True)
    seen = {}

    def other():
        with tr.span("prefetch_h2d"):
            seen["tid"] = threading.get_ident()

    with tr.span("run", key="train:7"):
        with tr.span("h2d"):
            with tr.span("inner", key="own"):
                pass
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        with tr.span("fetch"):
            pass
    with tr.span("loose"):
        pass
    recs = {r[0]: r for r in tr.spans()}
    me = threading.get_ident()
    assert recs["run"][3:6] == (None, "train:7", me)
    assert recs["h2d"][3:6] == ("run", "train:7", me)
    assert recs["inner"][3:6] == ("h2d", "own", me)
    assert recs["fetch"][3:6] == ("run", "train:7", me)
    assert recs["prefetch_h2d"][3:6] == (None, None, seen["tid"])
    assert seen["tid"] != me
    # the stack unwound: a later span is a root again, with no key
    assert recs["loose"][3:6] == (None, None, me)
    # children lie inside their parent on the host clock
    run, h2d = recs["run"], recs["h2d"]
    assert run[1] <= h2d[1] and h2d[1] + h2d[2] <= run[1] + run[2]
    # self time can be computed: a parent less its children
    assert run[2] >= h2d[2] + recs["fetch"][2]


def test_span_stack_unwinds_through_an_exception():
    tr = SpanTracer(capacity=8, enabled=True)
    with pytest.raises(RuntimeError):
        with tr.span("run", key="k"):
            with tr.span("dispatch"):
                raise RuntimeError("boom")
    with tr.span("after"):
        pass
    recs = {r[0]: r for r in tr.spans()}
    assert recs["dispatch"][3:5] == ("run", "k")
    assert recs["after"][3:5] == (None, None)


def test_chrome_trace_lanes_by_thread_with_parent_and_key():
    import threading

    tr = SpanTracer(capacity=8, enabled=True)

    def other():
        with tr.span("prefetch_h2d"):
            pass

    with tr.span("run", key="train:0"):
        with tr.span("h2d"):
            pass
        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=10)
    xs = {e["name"]: e for e in tr.chrome_trace()["traceEvents"]
          if e.get("ph") == "X"}
    assert xs["h2d"]["args"] == {"parent": "run", "key": "train:0"}
    assert xs["run"]["tid"] == xs["h2d"]["tid"]
    assert xs["prefetch_h2d"]["tid"] != xs["run"]["tid"]


def test_no_program_span_is_named_like_the_benchmarks():
    """``chipbench/trace_reduce.load`` keeps host events by NAME: a
    program span called ``feed`` or ``executor_run`` would be taken for
    the benchmark's own annotation.  Every ``.span("<literal>")`` in the
    package is checked, and the ``hetu:`` prefix keeps the rest apart."""
    import pathlib
    import re

    from hetu_tpu.telemetry.tracing import ANNOTATION_PREFIX

    root = pathlib.Path(ht.__file__).parent
    names = set()
    for path in root.rglob("*.py"):
        names.update(re.findall(r"\.span\(\s*[\"']([^\"']+)[\"']",
                                path.read_text()))
    assert {"run", "h2d", "dispatch", "fetch", "serve_decode"} <= names
    assert not names & {"feed", "executor_run"}
    assert ANNOTATION_PREFIX == "hetu:"


def test_histogram_bucket_override_and_mismatch_guard():
    """buckets= at first registration wins; a later registration with a
    DIFFERENT ladder fails loudly instead of silently sharing (the
    per-deployment override contract InferenceEngine/EngineFleet thread
    through)."""
    reg = MetricsRegistry(enabled=True)
    h = reg.histogram("ttft_s", "ttft", buckets=(0.01, 0.1, 1.0))
    assert h.buckets == (0.01, 0.1, 1.0)
    # same ladder re-registers fine (instrument cache)
    assert reg.histogram("ttft_s", buckets=(0.01, 0.1, 1.0)) is h
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("ttft_s", buckets=(0.5, 5.0))


# ---------------- JSONL writer ----------------

def test_jsonl_writer_and_registry_emission(tmp_path):
    path = tmp_path / "t.jsonl"
    reg = MetricsRegistry(enabled=True)
    reg.counter("c_total").inc(2)
    with JsonlWriter(path) as w:
        w.write({"kind": "custom", "x": 1})
        reg.write_jsonl(w)
    recs = [json.loads(line) for line in open(path)]
    assert recs[0] == {"kind": "custom", "x": 1}
    assert recs[1]["kind"] == "metrics_snapshot"
    assert recs[1]["metrics"]["c_total"]["samples"][0]["value"] == 2
    with pytest.raises(ValueError):
        w.write({"after": "close"})
    w.close()             # idempotent


def test_hetu_logger_context_manager_closes(tmp_path):
    path = str(tmp_path / "log.jsonl")
    with ht.HetuLogger(path=path, print_interval=1, printer=None) as lg:
        lg.log(loss=2.0)
        assert lg._writer is not None
    assert lg._writer is None
    rec = json.loads(open(path).read().splitlines()[0])
    assert rec["loss"] == 2.0
    assert rec["time"] >= 0       # monotonic elapsed, not wall clock


# ---------------- instrumented hot paths ----------------

def _tiny_executor(tag, guard=None):
    with ht.name_scope():
        x = ht.placeholder_op(f"tel_x_{tag}", (8, 4))
        y = ht.placeholder_op(f"tel_y_{tag}", (8,), dtype=np.int32)
        from hetu_tpu.layers import Linear
        loss = ht.reduce_mean_op(ht.softmax_cross_entropy_sparse_op(
            Linear(4, 3)(x), y))
    kw = {"step_guard": guard} if guard is not None else {}
    ex = ht.Executor(
        {"train": [loss, ht.SGDOptimizer(0.1).minimize(loss)]}, **kw)
    rng = np.random.default_rng(0)
    feed = {x: rng.standard_normal((8, 4)).astype(np.float32),
            y: rng.integers(0, 3, (8,)).astype(np.int32)}
    return ex, x, y, feed


def test_executor_steps_and_phases_recorded(tel):
    ex, x, y, feed = _tiny_executor("rec")
    for _ in range(3):
        ex.run("train", feed_dict=feed)
    snap = tel.get_registry().snapshot()
    counts = {s["labels"]["subgraph"]: s["value"] for s in
              snap["hetu_executor_steps_total"]["samples"]}
    assert counts["train"] == 3
    hist = snap["hetu_executor_step_seconds"]["samples"][0]
    assert hist["count"] == 3 and hist["sum"] > 0
    assert snap["hetu_executor_retraces_total"]["samples"][0]["value"] \
        == 1
    agg = tel.get_tracer().aggregate()
    assert agg["h2d"]["count"] == 3
    assert agg["dispatch"]["count"] == 3
    report = tel.step_phase_report()
    assert report["steps"] == 3
    phases = report["phases"]
    assert set(phases) >= {"h2d", "dispatch", "device_and_wait",
                           "data_wait"}
    # the contract: phases sum to the wall step time exactly
    assert sum(phases.values()) == pytest.approx(
        report["wall_s_per_step"], rel=1e-6)


def test_fetch_spans_and_phases_sum_to_wall(tel):
    """convert_to_numpy_ret_vals=True is the step's synchronisation
    point: three steps record three ``fetch`` spans under three ``run``
    roots keyed by subgraph and global step, the step histogram holds
    the roots' durations, and the phase report still sums to the wall
    time exactly."""
    ex, x, y, feed = _tiny_executor("fetch")
    for _ in range(3):
        out = ex.run("train", feed_dict=feed,
                     convert_to_numpy_ret_vals=True)
        assert isinstance(out[0], np.ndarray)
    ex.run("train", feed_dict=feed)          # no fetch asked, none recorded
    recs = tel.get_tracer().spans()
    roots = [r for r in recs if r[0] == "run"]
    assert [r[4] for r in roots] == [f"train:{k}" for k in range(4)]
    assert all(r[3] is None for r in roots)
    fetches = [r for r in recs if r[0] == "fetch"]
    assert [(r[3], r[4]) for r in fetches] == [
        ("run", f"train:{k}") for k in range(3)]
    for name in ("h2d", "dispatch"):
        assert [r[3] for r in recs if r[0] == name] == ["run"] * 4
    hist = tel.get_registry().snapshot()[
        "hetu_executor_step_seconds"]["samples"][0]
    assert hist["count"] == 4
    assert hist["sum"] == pytest.approx(sum(r[2] for r in roots))
    report = tel.step_phase_report()
    phases = report["phases"]
    assert phases["fetch"] > 0
    assert sum(phases.values()) == pytest.approx(
        report["wall_s_per_step"], rel=1e-6)
    # the remainder is the root less its phase children (the first
    # step's ``compile`` is the goodput ledger's, not a step phase)
    inside = sum(r[2] for r in recs
                 if r[3] == "run" and r[0] != "compile")
    assert phases["device_and_wait"] == pytest.approx(
        (hist["sum"] - inside) / 4, rel=1e-6, abs=1e-9)


def test_run_steps_has_a_root_and_a_fetch(tel):
    import jax.numpy as jnp

    ex, x, y, feed = _tiny_executor("multiroot")
    dev = {x: jnp.asarray(feed[x]), y: jnp.asarray(feed[y])}
    ex.run_steps("train", dev, 3, convert_to_numpy_ret_vals=True)
    ex.run_steps("train", dev, 2, convert_to_numpy_ret_vals=True)
    recs = tel.get_tracer().spans()
    # the first step of each group keys its root
    assert [r[4] for r in recs if r[0] == "run"] == ["train:0", "train:3"]
    assert [(r[3], r[4]) for r in recs if r[0] == "fetch"] == [
        ("run", "train:0"), ("run", "train:3")]


def _h2d_bytes(tel):
    snap = tel.get_registry().snapshot()
    return {s["labels"]["subgraph"]: s["value"] for s in
            snap["hetu_executor_h2d_bytes_total"]["samples"]}


@pytest.mark.parametrize("path", ["slow", "fast"])
def test_h2d_bytes_counter(tel, path):
    """Host arrays are counted where they are uploaded, at their size
    AFTER the cast (a float64 feed to a float32 placeholder counts 4
    bytes a value); device arrays in the declared dtype arm the fast
    path and upload nothing."""
    import jax.numpy as jnp

    ex, x, y, feed = _tiny_executor(f"h2d_{path}")
    if path == "slow":
        feed = {x: feed[x].astype(np.float64), y: feed[y]}
        want = 8 * 4 * 4 + 8 * 4            # f32 after the cast, int32
    else:
        feed = {x: jnp.asarray(feed[x]), y: jnp.asarray(feed[y])}
        want = 0
    for _ in range(3):
        ex.run("train", feed_dict=feed)
    sub = ex.subexecutor["train"]
    assert (sub._fast_feed is not None) == (path == "fast")
    assert _h2d_bytes(tel)["train"] == 3 * want


def test_run_steps_inner_trip_accounting_is_exact(tel):
    """The ROADMAP gap: StepGuard under run_steps detected trips only at
    the call boundary.  The carried fori_loop counter makes per-inner-
    step trips exact — n NaN steps report n trips, not 1."""
    import jax.numpy as jnp
    from hetu_tpu.resilience import StepGuard

    guard = StepGuard(policy="skip")
    ex, x, y, feed = _tiny_executor("trip", guard)
    clean = {x: jnp.asarray(feed[x]), y: jnp.asarray(feed[y])}
    ex.run_steps("train", clean, 3)
    guard.flush()
    assert guard.stats["inner_trips"] == 0
    bad = {x: jnp.asarray(np.full((8, 4), np.nan, np.float32)),
           y: clean[y]}
    ex.run_steps("train", bad, 5)
    guard.flush()
    assert guard.stats["inner_trips"] == 5
    assert guard.stats["steps"] == 8
    snap = tel.get_registry().snapshot()
    assert snap["hetu_guard_inner_trips_total"]["samples"][0]["value"] \
        == 5
    # params survived every poisoned inner step (skip's in-graph select)
    assert all(np.isfinite(np.asarray(v)).all()
               for v in ex.params.values())


def test_guard_trip_counter_on_run(tel):
    from hetu_tpu.resilience import StepGuard

    guard = StepGuard(policy="skip", defer=False)
    ex, x, y, feed = _tiny_executor("gtrip", guard)
    bad = dict(feed)
    bad[x] = np.full((8, 4), np.nan, np.float32)
    ex.run("train", feed_dict=bad)
    guard.flush()
    snap = tel.get_registry().snapshot()
    trips = {s["labels"]["policy"]: s["value"] for s in
             snap["hetu_guard_trips_total"]["samples"]}
    assert trips["skip"] == 1
    agg = tel.get_tracer().aggregate()
    assert agg["guard_check"]["count"] >= 1


def test_prefetch_queue_metrics(tel):
    from hetu_tpu.datasets.prefetch import DevicePrefetcher

    batches = [{"a": np.ones((2, 2), np.float32)} for _ in range(5)]
    pf = DevicePrefetcher(iter(batches), depth=2, sync=False)
    got = list(pf)
    pf.close()
    assert len(got) == 5
    snap = tel.get_registry().snapshot()
    assert snap["hetu_prefetch_batches_total"]["samples"][0]["value"] \
        == 5
    assert "hetu_prefetch_queue_depth" in snap
    assert snap["hetu_prefetch_consumer_wait_seconds_total"][
        "samples"][0]["value"] >= 0
    agg = tel.get_tracer().aggregate()
    # one data_wait span per delivered batch + one for the stop sentinel
    assert agg["data_wait"]["count"] in (5, 6)


def test_checkpointer_duration_histograms(tel, tmp_path):
    from hetu_tpu.resilience import RollingCheckpointManager

    ex, x, y, feed = _tiny_executor("ckpt")
    ex.run("train", feed_dict=feed)
    mgr = RollingCheckpointManager(str(tmp_path), keep=2)
    mgr.save(ex)
    mgr.restore_latest(ex)
    snap = tel.get_registry().snapshot()
    assert snap["hetu_checkpoint_saves_total"]["samples"][0]["value"] \
        == 1
    assert snap["hetu_checkpoint_save_seconds"]["samples"][0]["count"] \
        == 1
    assert snap["hetu_checkpoint_restore_seconds"]["samples"][0][
        "count"] == 1


def test_live_scrape_during_training(tel):
    """The acceptance-criteria path: a /metrics scrape mid-run returns
    executor counters in valid exposition format."""
    reg = tel.get_registry()
    ex, x, y, feed = _tiny_executor("scrape")
    with start_http_server(port=0, registry=reg) as srv:
        ex.run("train", feed_dict=feed)
        body = urllib.request.urlopen(
            f"{srv.url}/metrics", timeout=5).read().decode()
    assert 'hetu_executor_steps_total{subgraph="train"} 1' in body


# ---------------- the disabled-mode cost contract ----------------

def test_disabled_noop_path_costs_nothing_measurable():
    """Telemetry off (the default): the per-step instrument cost —
    a handful of no-op counter incs and null spans — must be far below
    the cost of even a trivial jitted executor step."""
    telemetry.disable()
    ex, x, y, feed = _tiny_executor("noop")
    ex.run("train", feed_dict=feed)            # compile + warm
    n_steps = 30
    t0 = time.perf_counter()
    for _ in range(n_steps):
        ex.run("train", feed_dict=feed)
    step_s = (time.perf_counter() - t0) / n_steps

    reg = telemetry.get_registry()
    tr = telemetry.get_tracer()
    c = reg.counter("hetu_noop_bench_total")
    reps = 20000
    t0 = time.perf_counter()
    for _ in range(reps):
        c.inc()
        with tr.span("noop"):
            pass
    per_op = (time.perf_counter() - t0) / reps
    # one disabled inc+span pair stays under 10 us absolute, and ten of
    # them per step stay under 5% of even this tiny step's wall time
    assert per_op < 10e-6, f"no-op instrument pair cost {per_op:.2e}s"
    assert per_op * 10 < 0.05 * step_s, (
        f"disabled telemetry would cost {per_op * 10 / step_s:.1%} "
        f"of a {step_s * 1e6:.0f}us step")

    # the ENABLED path with no profile being taken: a root (keyed, a
    # step marker) and a child — two TraceMe flag checks, the parent
    # stack, two ring writes — stay under 20 us (loose on purpose: the
    # chip's budget is 0.05 ms a step for all of a step's spans)
    on = SpanTracer(capacity=1024, enabled=True)
    reps = 5000
    t0 = time.perf_counter()
    for i in range(reps):
        with on.span("run", key="train:0", step=i):
            with on.span("h2d"):
                pass
    per_pair = (time.perf_counter() - t0) / reps
    assert per_pair < 20e-6, f"enabled span pair cost {per_pair:.2e}s"


# ---------------- what lay beneath a root (PR 53) ----------------

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _recorded_ring(extra):
    """Three steps of one thread and a prefetcher's span, as the executor
    writes them; ``extra`` is the root's seventh field (or "absent": a
    six-field ring, as before PR 53)."""
    recs = []
    for k in range(3):
        t = 100.0 + k * 0.1
        for name, a, b, parent in (("h2d", 2, 6, "run"),
                                   ("dispatch", 7, 10, "run"),
                                   ("fetch", 11, 96, "run"),
                                   ("run", 1, 97, None)):
            rec = (name, t + a * 1e-3, (b - a) * 1e-3, parent,
                   f"train:{k}", 7)
            if extra != "absent":
                rec += (extra if name == "run" else None,)
            recs.append(rec)
        recs.append(("prefetch_h2d", t + 0.012, 0.05, None, None, 8)
                    + (() if extra == "absent" else (None,)))
    return recs


@pytest.mark.parametrize("reader", ["run_thread", "steps_of", "host_means"])
def test_phase_readers_read_a_ring_with_the_seventh_field(reader):
    """The benchmark's readers index fields 0-5: a ring whose roots carry
    the OS account gives them what a six-field ring gave."""
    from chipbench.metrics import _phases
    account = dict.fromkeys(tracing_mod.ACCOUNT_FIELDS, 0)
    old, new = _recorded_ring("absent"), _recorded_ring(account)
    assert all(len(r) == 7 for r in new) and new[3][6] == account
    call = {"run_thread": lambda ring: [r[:6] for r in
                                        _phases.run_thread(ring)],
            "steps_of": lambda ring: _phases.steps_of(
                _phases.run_thread(ring), 0.5, 0.0, 1e15),
            "host_means": lambda ring: _phases.host_means(
                _phases.run_thread(ring), 100.0, 100.25)}[reader]
    assert call(new) == call(old)
    assert call(new)


def _sleep():
    time.sleep(0.05)


def _spin():
    t0 = time.thread_time()
    while time.thread_time() - t0 < 0.05:
        pass


def _touch_fresh_pages():
    with mmap.mmap(-1, 8 << 20) as m:
        for i in range(0, 8 << 20, mmap.PAGESIZE):
            m[i] = 1


@pytest.mark.parametrize("work, holds", [
    (_sleep, lambda a: a["vol_switches"] >= 1
     and a["cpu_user_s"] + a["cpu_sys_s"] < 0.03),
    (_spin, lambda a: a["cpu_user_s"] + a["cpu_sys_s"] >= 0.04),
    (_touch_fresh_pages, lambda a: a["minor_faults"] >= 256),
], ids=["sleep", "spin", "fresh_pages"])
def test_account_deltas_of_a_thread(work, holds):
    """An accounted span stores the deltas of the thread's own counters:
    a sleep is a voluntary switch and no CPU, a spin is CPU seconds,
    fresh pages are minor faults; a child span carries none."""
    tr = SpanTracer(capacity=16, enabled=True)
    with tr.span("run", key="train:0", account=True) as root:
        with tr.span("h2d"):
            work()
    child, rec = tr.spans()
    assert child[6] is None and len(child) == 7
    acct = rec[6]
    assert acct is root.extra
    assert set(tracing_mod.ACCOUNT_FIELDS) | {"cpu", "cpu_changed"} \
        <= set(acct)
    assert all(acct[k] is not None and acct[k] >= 0
               for k in tracing_mod.ACCOUNT_FIELDS)
    # whether the cpu changed is told against the thread's previous
    # accounted close: None at a tracer's first on this thread
    assert isinstance(acct["cpu"], int) and acct["cpu_changed"] is None
    with tr.span("run", key="train:1", account=True):
        pass
    assert tr.spans()[-1][6]["cpu_changed"] in (True, False)
    assert acct["runq_wait_s"] < rec[2]
    assert holds(acct), acct
    assert root.kids == {"h2d": child[2]}


@pytest.mark.parametrize("source, none_fields", [
    ("rusage", ("cpu_user_s", "cpu_sys_s", "vol_switches",
                "invol_switches", "minor_faults", "major_faults")),
    ("schedstat", ("runq_wait_s",)),
    ("getcpu", ("cpu", "cpu_changed")),
])
def test_account_reads_none_where_the_platform_lacks_a_source(
        monkeypatch, source, none_fields):
    if source == "rusage":
        monkeypatch.setattr(tracing_mod, "_RUSAGE_THREAD", None)
    elif source == "schedstat":
        def refuse(*a, **k):
            raise FileNotFoundError("/proc/thread-self/schedstat")
        monkeypatch.setattr(tracing_mod.os, "open", refuse)
    else:
        monkeypatch.setattr(tracing_mod, "_getcpu", lambda: None)
    tr = SpanTracer(capacity=4, enabled=True)
    for _ in range(2):          # the second has a close to compare with
        with tr.span("run", account=True):
            pass
    acct = tr.spans()[1][6]
    others = set(tracing_mod.ACCOUNT_FIELDS) | {"cpu", "cpu_changed"}
    assert all(acct[k] is None for k in none_fields)
    assert all(acct[k] is not None for k in others - set(none_fields))


def test_xla_events_nest_into_a_union_under_the_root(tel):
    """A step's trace ends after the traces of its inner functions: the
    event whose interval holds earlier ones replaces them, disjoint ones
    add up, and all of it lands on the ROOT, none in the ring."""
    record = jax.monitoring.record_event_duration_secs
    tr = tel.get_tracer()
    with tr.span("run", key="train:0") as root:
        with tr.span("dispatch"):
            t0 = time.perf_counter()
            time.sleep(0.02)
            record(TRACE_EVENT, 0.01)            # an inner function's
            time.sleep(0.005)
            record(TRACE_EVENT, 0.004)           # another, disjoint
            record(TRACE_EVENT, time.perf_counter() - t0)    # the step's
            outer = time.perf_counter() - t0
            time.sleep(0.01)
            record(LOWER_EVENT, 0.008)
            jax.monitoring.record_event(HIT_EVENT)
    assert [r[0] for r in tr.spans()] == ["dispatch", "run"]
    extra = tr.spans()[1][6]
    assert extra is root.extra
    assert extra["xla_trace_s"] == pytest.approx(outer, abs=2e-3)
    assert extra["xla_lower_s"] == pytest.approx(0.008)
    assert extra["xla_s"] == pytest.approx(outer + 0.008, abs=2e-3)
    assert extra["xla_cache_hits"] == 1 and extra["xla_cache_misses"] == 0
    assert extra["xla_compile_s"] == 0.0
    assert tr.spans()[0][6] is None
    assert tr.xla_seconds() == {"run": extra["xla_s"]}
    snap = tel.get_registry().snapshot()
    by_phase = {s["labels"]["phase"]: s["value"]
                for s in snap["hetu_xla_seconds_total"]["samples"]}
    assert by_phase["trace"] == pytest.approx(outer, abs=2e-3)
    assert by_phase["lower"] == pytest.approx(0.008)
    assert snap["hetu_xla_cache_total"]["samples"][0]["value"] == 1


def test_xla_events_disjoint_add_up(tel):
    record = jax.monitoring.record_event_duration_secs
    tr = tel.get_tracer()
    with tr.span("serve_prefill"):
        for _ in range(3):
            time.sleep(0.006)
            record(TRACE_EVENT, 0.005)
    extra = tr.spans()[0][6]
    assert extra["xla_trace_s"] == pytest.approx(0.015)
    assert set(extra) == set(tracing_mod.XLA_FIELDS)   # no account asked


def test_xla_events_outside_any_span_accumulate_by_thread(tel):
    record = jax.monitoring.record_event_duration_secs
    tr = tel.get_tracer()
    time.sleep(0.003)
    record(LOWER_EVENT, 0.002)

    def other():
        time.sleep(0.003)
        record(LOWER_EVENT, 0.001)
        jax.monitoring.record_event(HIT_EVENT)

    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=10)
    outside = tr.outside()
    assert len(tr) == 0 and len(outside) == 2
    assert outside[threading.get_ident()]["xla_lower_s"] == \
        pytest.approx(0.002)
    assert tel.step_report()["outside"]["xla_lower_s"] == \
        pytest.approx(0.003)
    assert tel.step_report()["outside"]["xla_cache_hits"] == 1
    tr.clear()
    assert tr.outside() == {}


def test_xla_union_stays_bounded():
    """A long-lived thread that keeps compiling folds the intervals that
    ended more than an hour ago into their seconds; the thousands of
    inner traces of ONE step are kept until the step's trace, which holds
    them, has come (folding them early counted them twice: PR 53's
    Granite, Ling-3.0 and Laguna roots read more XLA seconds than wall)."""
    xla = tracing_mod._Xla()
    for i in range(5000):
        xla.add("xla_compile_s", 0.5, 10.0 * (i + 1))
    assert xla.fields()["xla_compile_s"] == pytest.approx(2500.0)
    assert len(xla.spans["xla_compile_s"]) <= xla.KEEP + 2
    step = tracing_mod._Xla()
    for i in range(5000):                   # inner functions, 1 ms each
        step.add("xla_trace_s", 0.001, 100.0 + 0.002 * (i + 1))
    assert step.add("xla_trace_s", 10.5, 110.2) == pytest.approx(5.5)
    assert step.fields()["xla_trace_s"] == pytest.approx(10.5)
    assert step.fields()["xla_s"] == pytest.approx(10.5)


def test_listeners_registered_once_and_gone_after_disable():
    from jax._src import monitoring as mon

    def ours():
        return (mon.get_event_duration_listeners().count(telemetry._on_xla),
                mon.get_event_listeners().count(telemetry._on_xla))

    telemetry.disable()
    assert ours() == (0, 0)
    telemetry.enable()
    telemetry.enable()
    try:
        assert ours() == (1, 1)
    finally:
        telemetry.disable()
    assert ours() == (0, 0)
    telemetry.disable()                     # twice is fine
    # heard nothing while disabled
    tr = telemetry.get_tracer()
    tr.clear()
    jax.monitoring.record_event_duration_secs(TRACE_EVENT, 0.5)
    assert tr.outside() == {} and tr.xla_event(TRACE_EVENT, 0.5) is None


def test_first_root_carries_xla_and_the_tenth_does_not(tel):
    ex, x, y, feed = _tiny_executor("xla_first")
    for _ in range(10):
        ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    roots = [r for r in tel.get_tracer().spans() if r[0] == "run"]
    first, tenth = roots[0][6], roots[9][6]
    assert first["xla_trace_s"] > 0 and first["xla_lower_s"] > 0
    assert first["xla_compile_s"] > 0
    assert first["xla_s"] <= roots[0][2]
    assert first["cpu_user_s"] is not None          # and its account
    assert not any(k.startswith("xla_") for k in tenth)
    assert set(tracing_mod.ACCOUNT_FIELDS) <= set(tenth)
    # the initialisers' programs went under executor_init
    [init] = [r for r in tel.get_tracer().spans()
              if r[0] == "executor_init"]
    assert init[3] is None and init[4] == "train"
    assert init[6]["cpu_user_s"] is not None
    rep = tel.step_report()
    assert [s["key"] for s in rep["first_steps"]] == ["train:0"]
    assert rep["first_steps"][0]["xla_s"] == first["xla_s"]
    assert rep["executor_init"][0]["wall_s"] == init[2]
    assert rep["stalls"] == [] or all(
        s["cause"] != "xla" for s in rep["stalls"])


def _stalls(tel):
    fam = tel.get_registry().snapshot().get(
        "hetu_executor_step_stalls_total", {"samples": []})
    return {tuple(s["labels"][k] for k in ("subgraph", "phase", "cause")):
            s["value"] for s in fam["samples"]}


def test_a_shape_change_at_step_k_is_one_xla_stall(tel):
    """A recompile inside a run: the root that carries XLA's events once
    eight steady roots stand behind it is a stall whatever its length,
    counted, in the flight recorder with its whole record, and in
    ``step_report`` from the ring alone."""
    ex, x, y, feed = _tiny_executor("xla_stall")
    incidents = tel.get_flight().incident_count()
    k = 12
    for _ in range(k):
        ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    rng = np.random.default_rng(1)
    wide = {x: rng.standard_normal((16, 4)).astype(np.float32),
            y: rng.integers(0, 3, (16,)).astype(np.int32)}
    ex.run("train", feed_dict=wide, convert_to_numpy_ret_vals=True)
    ex.run("train", feed_dict=wide, convert_to_numpy_ret_vals=True)
    xla = {key: n for key, n in _stalls(tel).items() if key[2] == "xla"}
    assert sum(xla.values()) == 1 and next(iter(xla))[0] == "train"
    events = [e for e in tel.get_flight().ring()
              if e.get("type") == "step_stall" and e["cause"] == "xla"]
    assert [e["key"] for e in events] == [f"train:{k}"]
    assert events[0]["account"]["xla_trace_s"] > 0
    assert tel.get_flight().incident_count() == incidents   # no dump
    rep = tel.step_report()
    assert [(s["key"], s["cause"]) for s in rep["stalls"]
            if s["cause"] == "xla"] == [(f"train:{k}", "xla")]
    assert [s["key"] for s in rep["first_steps"]] == [
        "train:0", f"train:{k}"]
    # roots with XLA's events never entered the median
    assert rep["steps"] == k + 2
    assert tel.step_report(subgraph="validate")["steps"] == 0


@pytest.mark.parametrize("delay, cause", [(_sleep, "blocked"),
                                          (_spin, "host_cpu")])
def test_a_slow_fetch_is_named_with_its_cause(tel, monkeypatch, delay,
                                              cause):
    """Fifty milliseconds inside ``fetch`` on a millisecond step: phase
    ``fetch``; the thread slept (``blocked``) or burned its own CPU
    seconds (``host_cpu``).  A loaded machine can make a spin wait for a
    core as long as it ran (``runq``), so the spin gets three tries."""
    ex, x, y, feed = _tiny_executor(f"slow_{cause}")
    for _ in range(10):
        ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    fetch = SubExecutor._fetch

    def slow(self, vals):
        with self._tr.span("fetch"):
            delay()
        return fetch(self, vals)

    seen = []
    for _ in range(3):
        before = len([e for e in tel.get_flight().ring()
                      if e.get("type") == "step_stall"])
        with monkeypatch.context() as m:
            m.setattr(SubExecutor, "_fetch", slow)
            ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
        ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
        events = [e for e in tel.get_flight().ring()
                  if e.get("type") == "step_stall"][before:]
        assert len(events) == 1 and events[0]["phase"] == "fetch"
        assert events[0]["excess_s"] >= 0.045
        assert events[0]["phases"]["fetch"] >= 0.05
        seen.append(events[0]["cause"])
        if seen[-1] == cause:
            break
    assert seen[-1] == cause, seen
    assert _stalls(tel)[("train", "fetch", cause)] >= 1
    excess = tel.get_registry().snapshot()[
        "hetu_executor_step_excess_seconds_total"]["samples"][0]["value"]
    assert excess >= 0.045 * len(seen)
    rep = tel.step_report()
    assert [(s["phase"], s["cause"]) for s in rep["stalls"]] == [
        ("fetch", c) for c in seen]
    assert rep["excess_share"] > 0


@pytest.mark.parametrize("median, wall, stalled", [
    (1.0, 1.2, False),          # 200 ms over, but under a quarter
    (0.010, 0.040, False),      # four times the median, but under 50 ms
    (0.100, 0.160, True),
    (0.100, 0.140, False),
])
def test_step_watch_needs_a_quarter_and_fifty_ms(median, wall, stalled):
    watch = steps_mod.StepWatch()
    kids = {"h2d": 0.1 * median, "dispatch": 0.2 * median,
            "fetch": 0.6 * median}
    for i in range(steps_mod.STEADY - 1):
        assert watch.close(median, kids, None) == (0.0, None)
        assert watch.median() is None
    watch.close(median, kids, None)
    assert watch.median() == median
    excess, stall = watch.close(wall, {**kids, "dispatch": 0.2 * median
                                       + wall - median}, None)
    assert excess == pytest.approx(wall - median)
    assert (stall is not None) == stalled
    if stalled:
        assert stall["phase"] == "dispatch" and stall["cause"] == "blocked"
        assert stall["median_s"] == median
    # under the median costs nothing
    assert watch.close(0.5 * median, kids, None) == (0.0, None)


def test_step_watch_median_runs_over_the_last_window():
    watch = steps_mod.StepWatch()
    for _ in range(steps_mod.WINDOW):
        watch.close(1.0, {}, None)
    for _ in range(steps_mod.WINDOW // 2 + 1):
        watch.close(2.0, {}, None)
    assert watch.median() == 2.0
    assert len(watch._walls) == len(watch._steady) == steps_mod.WINDOW
    # a root with XLA's events does not enter it
    watch.close(50.0, {}, {"xla_s": 49.0})
    assert len(watch._walls) == steps_mod.WINDOW and 50.0 not in watch._walls


@pytest.mark.parametrize("account, cause", [
    ({"xla_s": 0.5, "major_faults": 3}, "xla"),
    ({"major_faults": 1, "runq_wait_s": 1.0}, "paging"),
    ({"runq_wait_s": 0.05, "cpu_user_s": 1.0}, "runq"),
    ({"runq_wait_s": 0.049, "cpu_user_s": 0.03, "cpu_sys_s": 0.02},
     "host_cpu"),
    ({"runq_wait_s": 0.01, "cpu_user_s": 0.01, "cpu_sys_s": 0.0},
     "blocked"),
    ({"runq_wait_s": None, "cpu_user_s": None, "major_faults": None},
     "blocked"),
    (None, "blocked"),
])
def test_cause_is_the_first_rule_that_holds(account, cause):
    assert steps_mod.cause_of(account, 0.1) == cause


def _report_ring():
    """A ring as the executor writes it: an ``executor_init``, a cold
    first step, then 2.5 seconds of 100 ms steps with one 400 ms step
    whose thread stood on a run queue."""
    tr = SpanTracer(capacity=1024, enabled=False)
    acct = dict.fromkeys(tracing_mod.ACCOUNT_FIELDS, 0)
    acct.update(cpu_user_s=0.004, cpu=3, cpu_changed=False)
    xla = dict.fromkeys(tracing_mod.XLA_FIELDS, 0.0)
    tr._record("executor_init", 1.0, 2.0, None, "train", 7,
               {**acct, **xla, "xla_compile_s": 0.5, "xla_s": 0.5})
    tr._record("compile", 5.001, 0.002, "run", "train:0", 7, None)
    tr._record("dispatch", 5.01, 29.0, "run", "train:0", 7, None)
    tr._record("run", 5.0, 30.0, None, "train:0", 7, {
        **acct, **xla, "xla_trace_s": 8.0, "xla_lower_s": 1.0,
        "xla_compile_s": 20.0, "xla_cache_load_s": 5.0,
        "xla_cache_hits": 1, "xla_s": 29.0, "load1": 1.5})
    t = 40.0
    for k in range(1, 24):
        wall = 0.4 if k == 15 else 0.1
        tr._record("h2d", t + 0.001, 0.004, "run", f"train:{k}", 7, None)
        tr._record("dispatch", t + 0.006, 0.01, "run", f"train:{k}", 7,
                   None)
        tr._record("fetch", t + 0.02, wall - 0.021, "run", f"train:{k}",
                   7, None)
        tr._record("run", t, wall, None, f"train:{k}", 7, {
            **acct, "invol_switches": 2 if k == 15 else 0,
            "runq_wait_s": 0.25 if k == 15 else 0.0,
            "cpu": 5 if k == 15 else 3,
            **({"load1": 2.5} if k in (1, 11, 21) else {})})
        tr._record("data_wait", t + wall, 0.001, None, None, 9, None)
        t += wall + 0.001
    return tr


def test_step_report_on_a_recorded_ring():
    rep = steps_mod.step_report(_report_ring())
    assert rep["steps"] == 24
    assert rep["wall_s"]["p50"] == 0.1 and rep["wall_s"]["max"] == 0.4
    assert rep["excess_share"] == pytest.approx(
        0.3 / (30.0 + 22 * 0.1 + 0.4))
    assert steps_mod.step_report(_report_ring(), since=40.0)[
        "excess_share"] == pytest.approx(0.3 / (22 * 0.1 + 0.4))
    [stall] = rep["stalls"]
    assert (stall["key"], stall["phase"], stall["cause"]) == (
        "train:15", "fetch", "runq")
    assert stall["excess_s"] == pytest.approx(0.3)
    assert stall["account"]["runq_wait_s"] == 0.25
    [first] = rep["first_steps"]
    assert first["key"] == "train:0" and first["wall_s"] == 30.0
    assert {k: first[k] for k in tracing_mod.XLA_EVENTS.values()} == {
        "xla_trace_s": 8.0, "xla_lower_s": 1.0, "xla_compile_s": 20.0,
        "xla_cache_load_s": 5.0, "xla_cache_hits": 1,
        "xla_cache_misses": 0.0}
    [init] = rep["executor_init"]
    assert init["wall_s"] == 2.0 and init["xla_compile_s"] == 0.5
    assert rep["xla_by_root"] == {"executor_init": 0.5, "run": 29.0}
    assert rep["host"]["load1_first"] == 1.5
    assert rep["host"]["load1_last"] == 2.5
    assert rep["host"]["cpu_count"] >= 1


def test_step_report_by_second_and_window():
    ring = _report_ring()
    rep = steps_mod.step_report(ring, since=40.0, until=42.0)
    assert rep["first_steps"] == [] and rep["executor_init"] == []
    assert rep["xla_by_root"] == {}
    secs = rep["by_second"]
    assert [s["second"] for s in secs] == [0, 1]
    assert secs[0]["steps"] == 10 and secs[1]["steps"] == 7
    assert rep["steps"] == 17
    assert secs[0]["wall_s"] == pytest.approx(0.1)
    assert secs[0]["h2d_s"] == pytest.approx(0.004)
    assert secs[0]["dispatch_s"] == pytest.approx(0.01)
    assert secs[0]["fetch_s"] == pytest.approx(0.079)
    assert secs[0]["run_self_s"] == pytest.approx(0.007)
    assert secs[0]["cpu_s"] == pytest.approx(0.04)
    assert secs[0]["cpus"] == [3] and secs[1]["cpus"] == [3, 5]
    assert secs[1]["runq_wait_s"] == 0.25 and secs[1]["invol_switches"] == 2
    assert secs[1]["wall_s"] == pytest.approx((6 * 0.1 + 0.4) / 7)
    assert secs[0]["load1"] == 2.5
    # the stall is judged against the medians of the whole ring, and
    # listed where it began
    assert [s["key"] for s in rep["stalls"]] == ["train:15"]
    assert steps_mod.step_report(ring, until=40.0)["stalls"] == []
    assert steps_mod.step_report(ring, until=40.0)["steps"] == 1


def test_step_report_of_a_dropped_ring_is_none():
    tr = SpanTracer(capacity=4, enabled=True)
    for i in range(6):
        with tr.span("run", key=f"train:{i}", account=True):
            pass
    said = []
    assert tr.dropped == 2
    assert steps_mod.step_report(tr, say=said.append) is None
    assert "dropped 2 spans" in said[0]
    assert telemetry.report(tracer=tr)["steps"] is None


def test_report_carries_the_step_report(tel):
    ex, x, y, feed = _tiny_executor("in_report")
    for _ in range(3):
        ex.run("train", feed_dict=feed)
    steps = tel.report()["steps"]
    assert steps["steps"] == 3 and steps["host"]["cpu_count"] >= 1
    json.dumps(steps)                       # a bench detail JSON holds it


def test_chrome_trace_puts_extra_in_args():
    tr = SpanTracer(capacity=8, enabled=True)
    with tr.span("run", key="train:0", account=True):
        with tr.span("h2d"):
            pass
    ev = {e["name"]: e for e in tr.chrome_trace()["traceEvents"]
          if e["ph"] == "X"}
    assert ev["run"]["args"]["key"] == "train:0"
    assert "invol_switches" in ev["run"]["args"]
    assert set(ev["h2d"]["args"]) == {"parent", "key"}
    json.dumps(tr.chrome_trace())


def test_run_steps_closes_its_root_like_run(tel):
    import jax.numpy as jnp

    ex, x, y, feed = _tiny_executor("multiclose")
    dev = {x: jnp.asarray(feed[x]), y: jnp.asarray(feed[y])}
    ex.run_steps("train", dev, 3)
    ex.run_steps("train", dev, 3)
    roots = [r for r in tel.get_tracer().spans() if r[0] == "run"]
    hist = tel.get_registry().snapshot()[
        "hetu_executor_step_seconds"]["samples"][0]
    assert hist["count"] == 2
    assert hist["sum"] == pytest.approx(sum(r[2] for r in roots))
    assert roots[0][6]["xla_s"] > 0 and "cpu_user_s" in roots[1][6]
    assert len(ex.subexecutor["train"]._watch._walls) == 1


def test_goodput_compile_bucket_holds_a_cold_steps_xla_seconds(tel):
    """The first root of a subgraph is XLA's trace, lowering and compile:
    the ledger books them as ``compile``, not as ``useful_train``, and the
    buckets still fill the window."""
    ex, x, y, feed = _tiny_executor("goodput_xla")
    for _ in range(5):
        ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    recs = tel.get_tracer().spans()
    roots = [r for r in recs if r[0] == "run"]
    [init] = [r for r in recs if r[0] == "executor_init"]
    [built] = [r for r in recs if r[0] == "compile"]
    xla_run = roots[0][6]["xla_s"]
    assert xla_run > 0.5 * roots[0][2] > 100 * built[2]
    # (the initialisers' programs are XLA's only in the process's first
    # executor; later ones find them in jit's cache)
    xla_init = init[6].get("xla_s", 0.0)
    acct = tel.goodput_report()
    buckets = acct["buckets_s"]
    assert buckets["compile"] == pytest.approx(
        built[2] + xla_run + xla_init, abs=1e-5)
    assert buckets["useful_train"] == pytest.approx(
        sum(r[2] for r in roots) - built[2] - xla_run, abs=1e-5)
    assert buckets["useful_train"] < 0.5 * roots[0][2]
    assert sum(buckets.values()) == pytest.approx(acct["wall_chip_s"],
                                                  abs=1e-4)
    assert sum(acct["fractions"].values()) == pytest.approx(1.0)


def test_import_seconds_gauge(tel):
    assert ht.import_seconds > 0
    [sample] = tel.get_registry().snapshot()[
        "hetu_import_seconds"]["samples"]
    assert sample["value"] == ht.import_seconds


def test_nothing_is_read_while_disabled(monkeypatch):
    """Telemetry off: no ``getrusage``, no ``/proc`` read, no
    ``sched_getcpu``, no listener, no root, no median."""
    telemetry.disable()

    def refuse(*a, **k):
        raise AssertionError("read while telemetry is disabled")

    monkeypatch.setattr(tracing_mod.resource, "getrusage", refuse)
    monkeypatch.setattr(tracing_mod.os, "pread", refuse)
    monkeypatch.setattr(tracing_mod._SchedStat, "__init__", refuse)
    monkeypatch.setattr(telemetry.get_tracer(), "_getcpu", refuse)
    monkeypatch.setattr(steps_mod.StepWatch, "close", refuse)
    monkeypatch.setattr(telemetry, "_on_xla", refuse)
    tr = telemetry.get_tracer()
    n = len(tr)
    ex, x, y, feed = _tiny_executor("off")
    for _ in range(3):
        ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    import jax.numpy as jnp
    ex.run_steps("train", {x: jnp.asarray(feed[x]),
                           y: jnp.asarray(feed[y])}, 2)
    assert len(tr) == n and tr.outside() == {}
    assert tr.span("run", account=True) is telemetry.NULL_SPAN


def test_enabled_root_costs_under_fifteen_microseconds():
    """What PR 53 added to a ``run`` root: two ``getrusage``, two
    ``pread``, one ``sched_getcpu`` and one median update.  The median
    over many roots of an accounted root and its close, less that of a
    plain root, stays under 15 us (a step is 160-1,420 ms); medians,
    because under six xdist workers single roots are preempted."""
    tr = SpanTracer(capacity=1024, enabled=True)
    watch = steps_mod.StepWatch()
    now = time.perf_counter
    plain, accounted = [], []
    for i in range(4000):
        t0 = now()
        with tr.span("run", key="train:0", step=i):
            pass
        t1 = now()
        root = tr.span("run", key="train:0", step=i, account=True)
        with root:
            pass
        watch.close(root.dur, root.kids, root.extra)
        t2 = now()
        plain.append(t1 - t0)
        accounted.append(t2 - t1)
    plain.sort()
    accounted.sort()
    added = accounted[len(accounted) // 2] - plain[len(plain) // 2]
    assert added < 15e-6, f"the account and the median cost {added:.2e} s"
    assert accounted[len(accounted) // 2] < 25e-6
