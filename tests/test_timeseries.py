"""Time-series plane (hetu_tpu/telemetry/{timeseries,alerts,goodput}):
store ring semantics (downsampling, label-summed queries, delta/rate),
the alert state machine on a manual clock (threshold / absence /
multi-window burn rate, no flapping, incident emission), the goodput
ledger's sum-to-1 attribution contract, and — the PR 4 discipline —
the disabled-mode cost of all three modules."""

import json
import time
import urllib.request

import pytest

from hetu_tpu import telemetry
from hetu_tpu.telemetry import (ALERT_STATES, GOODPUT_BUCKETS,
                                LOST_CAUSES, USEFUL_BUCKETS, AbsenceRule,
                                AlertManager, BurnRateRule, FlightRecorder,
                                GoodputLedger, JsonlWriter,
                                MetricsRegistry, SpanTracer,
                                ThresholdRule, TimeSeriesStore, slo_rules,
                                start_http_server)


class ManualClock:
    def __init__(self, t=0.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt=1.0):
        self.t += float(dt)
        return self.t


@pytest.fixture
def reg():
    return MetricsRegistry(enabled=True)


def _store(reg, clock, **kw):
    kw.setdefault("capacity", 16)
    return TimeSeriesStore(registry=reg, clock=clock, enabled=True, **kw)


# ---------------- TimeSeriesStore ----------------

def test_tick_captures_counters_gauges_histograms(reg):
    clk = ManualClock()
    st = _store(reg, clk)
    c = reg.counter("c_total", "c", labels=("k",))
    g = reg.gauge("g", "g")
    h = reg.histogram("h_seconds", "h")
    c.labels(k="a").inc(2)
    g.set(5)
    h.observe(0.3)
    clk.advance()
    assert st.tick() == 1.0
    assert st.last("c_total", labels={"k": "a"}) == 2.0
    assert st.last("g") == 5.0
    assert st.last("h_seconds", field="count") == 1.0
    assert st.last("h_seconds", field="sum") == pytest.approx(0.3)
    with pytest.raises(ValueError):
        st.last("h_seconds", field="p99")


def test_labels_none_sums_series_and_dict_selects_one(reg):
    clk = ManualClock()
    st = _store(reg, clk)
    c = reg.counter("c_total", "c", labels=("k",))
    c.labels(k="a").inc(3)
    c.labels(k="b").inc(4)
    st.tick(clk.advance())
    assert st.last("c_total") == 7.0                    # fleet-wide sum
    assert st.last("c_total", labels={"k": "b"}) == 4.0
    assert st.last("c_total", labels={"k": "zz"}) is None


def test_delta_rate_and_window(reg):
    clk = ManualClock()
    st = _store(reg, clk)
    c = reg.counter("c_total", "c")
    for i in range(6):
        c.inc(10)
        st.tick(clk.advance())
    # whole ring: 6 points at t=1..6, values 10..60
    assert st.delta("c_total") == 50.0
    assert st.rate("c_total") == pytest.approx(10.0)
    # a 2s window holds the last 3 points (t >= 6 - 2)
    assert st.delta("c_total", window=2.0) == 20.0
    # <2 points is None, not 0 — absence of evidence is not zero
    assert st.delta("c_total", window=0.5) is None
    assert st.rate("c_total", window=0.5) is None
    assert st.mean("c_total", window=2.0) == pytest.approx(50.0)


def test_downsampling_keeps_recent_fine_and_past_coarse(reg):
    clk = ManualClock()
    st = _store(reg, clk, capacity=8)
    c = reg.counter("c_total", "c")
    for _ in range(20):
        c.inc()
        st.tick(clk.advance())
    assert st.tick_count == 20
    assert len(st) <= 8
    assert st.downsampled > 0 and st.compactions > 0
    pts = st.series("c_total")
    # the newest ticks survive compaction untouched
    assert pts[-1][0] == 20.0 and pts[-1][1] == 20.0
    # timestamps stay strictly increasing after compaction
    assert all(a[0] < b[0] for a, b in zip(pts, pts[1:]))
    # the self-metrics row the drift gate documents
    assert st.tick_count == reg.snapshot()[
        "hetu_timeseries_ticks_total"]["samples"][0]["value"]


def test_counter_birth_counts_as_movement_gauge_birth_does_not(reg):
    """A counter created mid-window at value N is N increments: pre-
    birth ticks contribute 0 so rate rules can fire on faults that
    CREATE their counter (an engine crash builds the fleet's crash
    counter in the same act that increments it).  Gauges keep skip
    semantics — absence is not zero."""
    clk = ManualClock()
    st = _store(reg, clk)
    for _ in range(3):
        st.tick(clk.advance())              # metric does not exist yet
    reg.counter("born_total", "b").inc(4)
    reg.gauge("born_g", "g").set(4)
    st.tick(clk.advance())
    assert st.series("born_total") == [(1.0, 0.0), (2.0, 0.0),
                                       (3.0, 0.0), (4.0, 4.0)]
    assert st.delta("born_total") == 4.0
    assert st.rate("born_total") == pytest.approx(4.0 / 3.0)
    assert st.series("born_g") == [(4.0, 4.0)]
    assert st.delta("born_g") is None       # one real point only
    # a never-born metric is still no-evidence, not a zero series
    assert st.series("never_total") == []
    assert st.last("never_total") is None


def test_min_interval_rate_limits_hot_tickers(reg):
    clk = ManualClock()
    st = _store(reg, clk, min_interval_s=1.0)
    reg.counter("c_total", "c").inc()
    assert st.tick(clk.advance(1.0)) == 1.0
    assert st.tick(clk.advance(0.2)) is None        # too soon
    assert st.tick(clk.advance(0.9)) == 2.1
    assert st.tick_count == 2


def test_jsonl_stream_and_dump(reg, tmp_path):
    clk = ManualClock()
    st = _store(reg, clk)
    stream = tmp_path / "ticks.jsonl"
    with JsonlWriter(str(stream)) as w:
        st.configure(writer=w)
        reg.counter("c_total", "c").inc(5)
        st.tick(clk.advance())
    rows = [json.loads(l) for l in stream.read_text().splitlines()]
    assert rows[0]["kind"] == "timeseries_tick"
    assert rows[0]["metrics"]["c_total"]["samples"][0]["value"] == 5.0
    dump = tmp_path / "ring.jsonl"
    with JsonlWriter(str(dump)) as w:
        st.write_jsonl(w)
    doc = json.loads(dump.read_text().splitlines()[0])
    assert doc["kind"] == "timeseries" and len(doc["ticks"]) == 1


def test_store_report_block(reg):
    clk = ManualClock()
    st = _store(reg, clk)
    reg.counter("c_total", "c").inc()
    st.tick(clk.advance())
    st.tick(clk.advance())
    blk = st.report_block()
    assert blk["enabled"] and blk["tick_count"] == 2
    assert blk["span_s"] == 1.0
    assert "c_total" in blk["series"]


def test_capacity_floor():
    with pytest.raises(ValueError):
        TimeSeriesStore(capacity=2)


# ---------------- alert rules + state machine ----------------

def _plane(reg, rules, flight=None):
    clk = ManualClock()
    st = _store(reg, clk, capacity=64)
    mgr = AlertManager(st, rules, registry=reg, flight=flight,
                      clock=clk, enabled=True)
    return clk, st, mgr


def test_threshold_rule_walks_the_full_state_machine(reg):
    fl = FlightRecorder(registry=reg, enabled=True)
    clk, st, mgr = _plane(
        reg, [ThresholdRule("trips", "c_total", reduce="rate",
                            op=">", threshold=0.0, window=4.0,
                            for_ticks=2)], flight=fl)
    c = reg.counter("c_total", "c")
    for _ in range(3):
        mgr.poll(clk.advance())
    assert mgr.state("trips") == "inactive"
    c.inc()                                     # the fault
    mgr.poll(clk.advance())
    assert mgr.state("trips") == "pending"      # one bad eval armed it
    fired = mgr.poll(clk.advance())
    assert fired == ("trips",)                  # for_ticks=2 reached
    # firing emitted exactly one alert incident with the series tail
    assert fl.incident_count("alert") == 1
    extra = fl.incidents()[-1]
    assert extra["kind"] == "alert"
    # the movement ages out of the 4s window -> resolved -> inactive
    for _ in range(8):
        mgr.poll(clk.advance())
    assert mgr.state("trips") == "inactive"
    firings = [t for s, t in mgr.transitions("trips") if s == "firing"]
    assert len(firings) == 1, "rule flapped"
    states = [s for s, _ in mgr.transitions("trips")]
    assert states == ["pending", "firing", "resolved", "inactive"]
    assert set(states) <= set(ALERT_STATES)
    # one more incident would mean re-firing: there is none
    assert fl.incident_count("alert") == 1


def test_alert_incident_carries_rule_and_tail(reg):
    fl = FlightRecorder(registry=reg, enabled=True)
    clk, st, mgr = _plane(
        reg, [ThresholdRule("g_high", "g", reduce="last", op=">",
                            threshold=10.0, for_ticks=1)], flight=fl)
    g = reg.gauge("g", "g")
    g.set(50)
    mgr.poll(clk.advance())
    assert mgr.firing() == ("g_high",)
    # the dump index entry exists; the in-memory dump extra carries the
    # rule name, observed value, threshold, and the offending series
    ring_entry = fl.incidents()[-1]
    assert ring_entry["kind"] == "alert"
    mgr_blk = mgr.report_block()
    assert mgr_blk["rules"]["g_high"]["observed"] == 50.0
    assert mgr_blk["firing"] == ["g_high"]


def test_pending_clears_without_firing_on_recovery(reg):
    clk, st, mgr = _plane(
        reg, [ThresholdRule("trips", "c_total", reduce="rate",
                            op=">", threshold=0.0, window=3.0,
                            for_ticks=4)])
    c = reg.counter("c_total", "c")
    mgr.poll(clk.advance())
    c.inc()
    mgr.poll(clk.advance())
    assert mgr.state("trips") == "pending"
    for _ in range(6):                      # movement ages out before
        mgr.poll(clk.advance())             # for_ticks accumulates
    assert mgr.state("trips") == "inactive"
    assert not [1 for s, _ in mgr.transitions("trips") if s == "firing"]


def test_absence_rule_fires_only_under_load(reg):
    clk, st, mgr = _plane(
        reg, [AbsenceRule("stuck", "tok_total", window=3.0, for_ticks=2,
                          while_metric="depth", while_op=">",
                          while_threshold=0.0)])
    tok = reg.counter("tok_total", "t")
    depth = reg.gauge("depth", "d")
    # never moved: no evidence, never pending
    mgr.poll(clk.advance())
    assert mgr.state("stuck") == "inactive"
    tok.inc(5)
    depth.set(0)
    for _ in range(5):
        mgr.poll(clk.advance())
    # counter flat but queue empty: idle, not stuck
    assert mgr.state("stuck") == "inactive"
    depth.set(3)                            # load with no progress
    fired = ()
    for _ in range(4):
        fired = mgr.poll(clk.advance())
    assert fired == ("stuck",)
    tok.inc(1)                              # progress resumes
    mgr.poll(clk.advance())
    assert mgr.state("stuck") == "resolved"


def test_burn_rate_needs_both_windows(reg):
    rule = BurnRateRule("burn", "bad_total", "good_total", 0.1,
                        window=8.0, fast_window=2.0, fast_factor=2.0,
                        slow_factor=1.0, for_ticks=1)
    clk, st, mgr = _plane(reg, [rule])
    bad = reg.counter("bad_total", "b")
    good = reg.counter("good_total", "g")
    # healthy burn: 1 bad per 100 good = 0.01 << budget 0.1
    for _ in range(8):
        good.inc(100)
        bad.inc(1)
        mgr.poll(clk.advance())
    assert mgr.state("burn") == "inactive"
    # a fast-window blip alone must not page: two hot ticks inside an
    # otherwise-healthy slow window
    bad.inc(60)
    good.inc(100)
    mgr.poll(clk.advance())
    st_blip = mgr.state("burn")
    # sustained burn: every tick now spends 50x budget
    for _ in range(8):
        bad.inc(50)
        good.inc(100)
        mgr.poll(clk.advance())
    assert mgr.state("burn") == "firing"
    assert st_blip in ("inactive", "pending")
    assert rule.describe()["kind"] == "burn_rate"


def test_burn_rate_budget_validation():
    with pytest.raises(ValueError):
        BurnRateRule("b", "bad", "good", 0.0)
    with pytest.raises(ValueError):
        BurnRateRule("b", "bad", "good", 1.5)


def test_rule_validation_and_dup_names(reg):
    with pytest.raises(ValueError):
        ThresholdRule("r", "m", op="!=")
    with pytest.raises(ValueError):
        ThresholdRule("r", "m", reduce="p99")
    clk, st, mgr = _plane(reg, [ThresholdRule("r", "m")])
    with pytest.raises(ValueError):
        mgr.add(ThresholdRule("r", "m2"))


def test_slo_rules_cover_the_fault_classes(reg):
    rules = slo_rules(window=8.0, hbm_headroom_floor_bytes=1 << 20)
    names = {r.name for r in rules}
    # the chaos contract: one rule per injected fault class
    assert {"guard_trips", "engine_crashes", "migration_failures",
            "overload_shed"} <= names
    assert {"slo_deadline_burn", "slo_attainment_low",
            "watchdog_trips", "numerics_anomaly_streak",
            "serving_tokens_stuck", "hbm_headroom_low"} <= names
    clk, st, mgr = _plane(reg, rules)
    # a full poll with none of the metrics present: every rule returns
    # no-evidence and nothing fires or pends
    mgr.poll(clk.advance())
    assert mgr.firing() == ()
    blk = mgr.report_block()
    assert all(r["state"] == "inactive" for r in blk["rules"].values())


def test_alert_metrics_and_summary(reg):
    clk, st, mgr = _plane(
        reg, [ThresholdRule("hot", "g", reduce="last", op=">",
                            threshold=1.0, for_ticks=1)])
    reg.gauge("g", "g").set(9)
    mgr.poll(clk.advance())
    snap = reg.snapshot()
    firing = {tuple(sorted(s["labels"].items())): s["value"]
              for s in snap["hetu_alerts_firing"]["samples"]}
    assert firing[(("rule", "hot"),)] == 1.0
    assert snap["hetu_alerts_evals_total"]["samples"][0]["value"] == 1.0
    trans = {(s["labels"]["rule"], s["labels"]["to"]): s["value"]
             for s in snap["hetu_alerts_transitions_total"]["samples"]}
    assert trans[("hot", "firing")] == 1.0
    s = mgr.summary()
    assert s["firing"] == 1 and s["summary"] == "firing: 1"
    assert s["rules"] == ["hot"]


# ---------------- goodput ledger ----------------

def _ledger(reg, tr, clock, **kw):
    kw.setdefault("name", "t")
    return GoodputLedger(registry=reg, tracer=tr, clock=clock,
                         enabled=True, **kw)


def test_goodput_buckets_are_exhaustive_and_disjoint():
    assert set(USEFUL_BUCKETS) | set(LOST_CAUSES) == set(GOODPUT_BUCKETS)
    assert not set(USEFUL_BUCKETS) & set(LOST_CAUSES)
    assert "idle" in LOST_CAUSES


def test_goodput_fractions_sum_to_one_exactly(reg):
    tr = SpanTracer(enabled=True)
    clk = ManualClock()
    led = _ledger(reg, tr, clk)
    h = reg.histogram("hetu_executor_step_seconds", "s",
                      labels=("subgraph",)).labels(subgraph="train")
    led.begin(now=clk.advance())
    for _ in range(10):
        h.observe(0.05)                    # 0.5s of step time
    with tr.span("compile"):
        time.sleep(0.002)
    acct = led.account(wall_s=1.0, now=clk.advance())
    fr = acct["fractions"]
    assert set(fr) == set(GOODPUT_BUCKETS)
    assert sum(fr.values()) == pytest.approx(1.0, abs=1e-12)
    assert acct["goodput_fraction"] == pytest.approx(
        sum(fr[k] for k in USEFUL_BUCKETS))
    assert fr["useful_train"] > 0.4
    assert fr["compile"] > 0.0
    assert fr["idle"] > 0.0 and not acct["scaled_to_wall"]


def test_goodput_rollback_attribution(reg):
    tr = SpanTracer(enabled=True)
    clk = ManualClock()
    led = _ledger(reg, tr, clk)
    h = reg.histogram("hetu_executor_step_seconds", "s",
                      labels=("subgraph",)).labels(subgraph="train")
    trips = reg.counter("hetu_guard_trips_total", "t",
                        labels=("policy",)).labels(policy="rollback")
    led.begin(now=clk.advance())
    for _ in range(10):
        h.observe(0.1)
    trips.inc(2)                            # 2 of 10 steps wasted
    with tr.span("rollback_restore"):
        time.sleep(0.001)
    acct = led.account(wall_s=2.0, now=clk.advance())
    b = acct["buckets_s"]
    # rollback = 2 tripped steps at the 0.1s mean + the restore span
    assert b["rollback"] == pytest.approx(0.2, abs=0.02)
    assert b["useful_train"] == pytest.approx(0.8, abs=0.02)
    assert sum(acct["fractions"].values()) == pytest.approx(1.0)


def test_goodput_restore_split_between_rollback_and_checkpoint(reg):
    tr = SpanTracer(enabled=True)
    clk = ManualClock()
    led = _ledger(reg, tr, clk)
    rh = reg.histogram("hetu_checkpoint_restore_seconds", "r")
    led.begin(now=clk.advance())
    # one PLAIN restore (resume) and one guard rollback restore; the
    # rollback's span is carved out of the restore histogram so the two
    # buckets never double-count
    rh.observe(0.3)
    with tr.span("rollback_restore"):
        pass
    agg_before = tr.aggregate()["rollback_restore"]["total_s"]
    rh.observe(max(agg_before, 1e-9))
    acct = led.account(wall_s=1.0, now=clk.advance())
    b = acct["buckets_s"]
    assert b["checkpoint_restore"] == pytest.approx(0.3, abs=0.01)
    assert b["rollback"] == pytest.approx(agg_before, abs=0.01)


def test_goodput_failover_replay_carved_from_decode(reg):
    tr = SpanTracer(enabled=True)
    clk = ManualClock()
    led = _ledger(reg, tr, clk)
    tok = reg.counter("hetu_serving_tokens_total", "t",
                      labels=("scheduler",)).labels(scheduler="continuous")
    rep = reg.counter("hetu_serving_replayed_tokens_total", "r",
                      labels=("scheduler",)).labels(scheduler="continuous")
    led.begin(now=clk.advance())
    with tr.span("serve_decode"):
        time.sleep(0.002)
    decode_s = tr.aggregate()["serve_decode"]["total_s"]
    tok.inc(100)                            # 100 tokens emitted
    rep.inc(25)                             # 25 of them re-derived
    acct = led.account(wall_s=1.0, now=clk.advance())
    b = acct["buckets_s"]
    assert b["failover_replay"] == pytest.approx(decode_s * 0.25,
                                                 rel=0.05)
    assert b["useful_decode"] == pytest.approx(decode_s * 0.75,
                                               rel=0.05)


def test_goodput_brownout_shed_bounded_by_idle(reg):
    tr = SpanTracer(enabled=True)
    clk = ManualClock()
    led = _ledger(reg, tr, clk)
    tok = reg.counter("hetu_serving_tokens_total", "t",
                      labels=("scheduler",)).labels(scheduler="continuous")
    fin = reg.counter("hetu_serving_requests_total", "f",
                      labels=("scheduler",)).labels(scheduler="continuous")
    rej = reg.counter("hetu_serving_rejections_total", "r",
                      labels=("scheduler",)).labels(scheduler="continuous")
    led.begin(now=clk.advance())
    with tr.span("serve_decode"):
        time.sleep(0.002)
    tok.inc(10)
    fin.inc(2)                              # mean request cost: decode/2
    rej.inc(1000)                           # absurd shed count...
    # (a 1 s window: the sleep's 2 ms may take 10 on a loaded machine, and
    # 1,000 sheds at half the decode span still ask for more than is idle)
    acct = led.account(wall_s=1.0, now=clk.advance())
    fr = acct["fractions"]
    # ...must stay bounded by the idle residual, never oversubscribe
    assert fr["brownout_shed"] > 0.0
    assert sum(fr.values()) == pytest.approx(1.0)
    assert fr["idle"] >= 0.0


def test_goodput_oversubscribed_wall_scales_not_breaks(reg):
    tr = SpanTracer(enabled=True)
    clk = ManualClock()
    led = _ledger(reg, tr, clk)
    h = reg.histogram("hetu_executor_step_seconds", "s",
                      labels=("subgraph",)).labels(subgraph="train")
    led.begin(now=clk.advance())
    h.observe(5.0)                          # 5s of steps in a 1s wall
    acct = led.account(wall_s=1.0, now=clk.advance())
    assert acct["scaled_to_wall"]
    assert sum(acct["fractions"].values()) == pytest.approx(1.0)
    assert acct["buckets_s"]["useful_train"] == pytest.approx(1.0)


def test_goodput_replica_split_rides_label_shares(reg):
    tr = SpanTracer(enabled=True)
    clk = ManualClock()
    led = _ledger(reg, tr, clk)
    h = reg.histogram("hetu_executor_step_seconds", "s",
                      labels=("subgraph",))
    led.begin(now=clk.advance())
    for _ in range(3):
        h.labels(subgraph="a").observe(0.1)
    h.labels(subgraph="b").observe(0.1)
    acct = led.account(wall_s=1.0, now=clk.advance())
    split = acct["replicas"]["useful_train"]
    assert split["subgraph=a"] == pytest.approx(
        3 * split["subgraph=b"], rel=0.01)
    assert sum(split.values()) == pytest.approx(
        acct["fractions"]["useful_train"])


def test_goodput_chips_validation_and_empty_window(reg):
    with pytest.raises(ValueError):
        GoodputLedger(chips=0)
    clk = ManualClock()
    led = _ledger(reg, SpanTracer(enabled=True), clk)
    led.begin(now=clk.advance())
    acct = led.account(wall_s=0.0, now=clk.advance())
    # zero capacity: everything idle by definition, identity intact
    assert acct["fractions"]["idle"] == 1.0
    assert sum(acct["fractions"].values()) == pytest.approx(1.0)


def test_goodput_gauges_exported(reg):
    tr = SpanTracer(enabled=True)
    clk = ManualClock()
    led = _ledger(reg, tr, clk, name="probe")
    led.begin(now=clk.advance())
    led.account(wall_s=1.0, now=clk.advance())
    snap = reg.snapshot()
    good = snap["hetu_goodput_fraction"]["samples"]
    assert good[0]["labels"] == {"ledger": "probe"}
    causes = {s["labels"]["cause"]
              for s in snap["hetu_goodput_lost_fraction"]["samples"]}
    assert causes == set(LOST_CAUSES)


# ---------------- process wiring ----------------

def test_process_singletons_follow_enable_disable():
    st = telemetry.get_timeseries()
    mgr = telemetry.get_alerts()
    led = telemetry.get_goodput()
    assert not (st.enabled or mgr.enabled or led.enabled)
    assert st.tick() is None
    assert mgr.poll() == ()
    assert led.account() == {"enabled": False}
    telemetry.enable()
    try:
        assert st.enabled and mgr.enabled and led.enabled
        rep = telemetry.report()
        assert rep["timeseries"]["enabled"]
        assert rep["alerts"]["enabled"]
        assert rep["goodput"]["enabled"]
        assert telemetry.goodput_report()["ledger"] == "process"
    finally:
        telemetry.disable()
    assert not (st.enabled or mgr.enabled or led.enabled)


def test_healthz_carries_alert_summary_over_http():
    """The /healthz round-trip: the one-line firing summary (and the
    /timeseries /alerts /goodput debug endpoints) ride the exporter."""
    telemetry.get_registry().reset()
    srv = telemetry.enable(http_port=0)
    try:
        mgr = telemetry.get_alerts()
        added = None
        if not any(r.name == "tz_probe" for r in mgr.rules()):
            added = mgr.add(ThresholdRule(
                "tz_probe", "tz_g", reduce="last", op=">",
                threshold=1.0, for_ticks=1))
        telemetry.get_registry().gauge("tz_g", "g").set(5)
        mgr.poll(time.perf_counter())

        def get(path):
            return urllib.request.urlopen(
                f"{srv.url}{path}", timeout=5).read().decode()

        doc = json.loads(get("/healthz"))
        assert doc["alerts"]["firing"] == 1
        assert doc["alerts"]["summary"] == "firing: 1"
        assert doc["alerts"]["rules"] == ["tz_probe"]
        ts = json.loads(get("/timeseries"))
        assert ts["enabled"] and ts["tick_count"] >= 1
        al = json.loads(get("/alerts"))
        assert "tz_probe" in al["rules"]
        gp = json.loads(get("/goodput"))
        assert gp["enabled"] and "fractions" in gp
        body = get("/metrics")
        assert 'hetu_alerts_firing{rule="tz_probe"} 1' in body
    finally:
        telemetry.shutdown()


def test_healthz_alert_provider_failure_degrades_not_500():
    reg = MetricsRegistry(enabled=True)

    def boom():
        raise RuntimeError("summary exploded")

    srv = start_http_server(port=0, registry=reg, health_extra=boom)
    try:
        doc = json.loads(urllib.request.urlopen(
            f"{srv.url}/healthz", timeout=5).read().decode())
        assert doc["status"] == "degraded"
        assert "summary exploded" in doc["error"]
    finally:
        srv.close()


# ---------------- the disabled-mode cost contract ----------------

def _per_op(fn, reps=3000):
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps


def test_disabled_plane_is_one_flag_check():
    """tick/poll/evaluate/begin/account while disabled each stay under
    20us/op — control loops carry their plane hooks unconditionally."""
    reg = MetricsRegistry(enabled=True)
    tr = SpanTracer(enabled=True)
    st = TimeSeriesStore(registry=reg, enabled=False)
    mgr = AlertManager(st, slo_rules(), enabled=False)
    led = GoodputLedger(registry=reg, tracer=tr, enabled=False)
    assert _per_op(st.tick) < 20e-6
    assert _per_op(mgr.poll) < 20e-6
    assert _per_op(mgr.evaluate) < 20e-6
    assert _per_op(led.begin) < 20e-6
    assert _per_op(led.account) < 20e-6
