"""Unified runtime telemetry (hetu_tpu/telemetry/): registry semantics,
Prometheus exposition, the stdlib HTTP exporter, the span tracer, the
instrumented executor/prefetch/guard hot paths, and — critically — the
disabled-mode cost contract: every instrument is a near-free no-op until
``telemetry.enable()``, so the step path can carry its probes
unconditionally."""

import json
import time
import urllib.request

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.telemetry import (JsonlWriter, MetricsRegistry, SpanTracer,
                                start_http_server)


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
    assert recs["run"][3:] == (None, "train:7", me)
    assert recs["h2d"][3:] == ("run", "train:7", me)
    assert recs["inner"][3:] == ("h2d", "own", me)
    assert recs["fetch"][3:] == ("run", "train:7", me)
    assert recs["prefetch_h2d"][3:] == (None, None, seen["tid"])
    assert seen["tid"] != me
    # the stack unwound: a later span is a root again, with no key
    assert recs["loose"][3:] == (None, None, me)
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
