"""Training numerics plane (hetu_tpu/telemetry/numerics.py): the fused
per-layer grad/update/param stats vector riding the jitted step, the
deferred host-read cadence, run_steps' exact inner-step attribution,
sampled-mode program twins, anomaly escalation into every StepGuard
policy, culprit attribution on trips, and the disabled-mode cost
contract."""

import json
import urllib.request

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.telemetry import NumericsMonitor, start_http_server
from hetu_tpu.resilience import (GuardTripped, RollingCheckpointManager,
                                 StepGuard)


@pytest.fixture
def tel():
    """Fresh, ENABLED process-wide telemetry; restored to disabled."""
    telemetry.get_registry().reset()
    telemetry.get_tracer().clear()
    telemetry.enable()
    yield telemetry
    telemetry.disable()


def _tiny_executor(tag, guard=None, numerics=None):
    with ht.name_scope():
        x = ht.placeholder_op(f"num_x_{tag}", (8, 4))
        y = ht.placeholder_op(f"num_y_{tag}", (8,), dtype=np.int32)
        from hetu_tpu.layers import Linear
        loss = ht.reduce_mean_op(ht.softmax_cross_entropy_sparse_op(
            Linear(4, 3, name=f"dense_{tag}")(x), y))
    kw = {}
    if guard is not None:
        kw["step_guard"] = guard
    if numerics is not None:
        kw["numerics"] = numerics
    ex = ht.Executor(
        {"train": [loss, ht.SGDOptimizer(0.1).minimize(loss)]}, **kw)
    rng = np.random.default_rng(0)
    feed = {x: rng.standard_normal((8, 4)).astype(np.float32),
            y: rng.integers(0, 3, (8,)).astype(np.int32)}
    return ex, x, y, feed


# ---------------- determinism ----------------

def test_per_layer_stats_bitwise_deterministic():
    """Two fresh executors over the same graph/seed/feeds must produce
    bit-identical numerics history: the stats are pure functions of the
    step, so any wobble would mean nondeterministic capture."""
    def run_once():
        mon = NumericsMonitor(name="det", check_interval=1, defer=False)
        ex, x, y, feed = _tiny_executor("det", numerics=mon)
        for _ in range(6):
            ex.run("train", feed_dict=feed)
        mon.flush()
        return list(mon.history)

    h1, h2 = run_once(), run_once()
    assert len(h1) == 6
    assert h1 == h2          # dict equality is exact float equality


# ---------------- deferred-read cadence ----------------

def test_deferred_cadence_no_host_sync_between_intervals():
    """Below the check interval nothing is materialized: rows queue as
    DEVICE arrays and ``processed`` stays 0 — the step path never paid
    a host sync for the stats."""
    mon = NumericsMonitor(name="cad", check_interval=4, defer=True)
    ex, x, y, feed = _tiny_executor("cad", numerics=mon)
    for i in range(4):
        ex.run("train", feed_dict=feed)
        assert mon.stats["processed"] == 0
        assert mon.pending_count == i + 1
    # queued entries are still device arrays, not numpy: no read yet
    assert all(not isinstance(p[2], np.ndarray) for p in mon._pending)
    # the 5th step crosses check_interval + defer and drains to keep=1
    ex.run("train", feed_dict=feed)
    assert mon.stats["processed"] == 4
    assert mon.pending_count == 1
    mon.flush()
    assert mon.stats["processed"] == 5
    assert mon.pending_count == 0


# ---------------- run_steps inner-step attribution ----------------

def test_run_steps_inner_nonfinite_attribution_exact(tel):
    """k poisoned inner steps inside one run_steps dispatch report
    exactly k non-finite steps per layer (the carried [n_layers] int32
    counter), not 1 per call boundary."""
    import jax.numpy as jnp

    guard = StepGuard(policy="skip")
    mon = NumericsMonitor(name="inner", check_interval=1)
    ex, x, y, feed = _tiny_executor("inner", guard=guard, numerics=mon)
    clean = {x: jnp.asarray(feed[x]), y: jnp.asarray(feed[y])}
    ex.run_steps("train", clean, 3)
    guard.flush()
    mon.flush()
    assert all(st["nonfinite_steps"] == 0 for st in mon.layers.values())

    bad = {x: jnp.asarray(np.full((8, 4), np.nan, np.float32)),
           y: clean[y]}
    ex.run_steps("train", bad, 5)
    guard.flush()
    mon.flush()
    assert mon.layers, "monitor saw no layers"
    for st in mon.layers.values():
        assert st["nonfinite_steps"] == 5
    assert mon.stats["steps"] == 8
    snap = tel.get_registry().snapshot()
    nf = {s["labels"]["layer"]: s["value"] for s in
          snap["hetu_numerics_nonfinite_total"]["samples"]
          if s["labels"]["monitor"] == "inner"}
    assert set(nf.values()) == {5}


# ---------------- sampled mode (two-program switching) ----------------

def test_sample_every_processes_only_cadence_steps():
    """sample_every=4: only steps 0, 4, 8 of a 10-step run carry a
    stats row — off-cadence steps run the plain program and never even
    reach on_step."""
    mon = NumericsMonitor(name="samp", check_interval=1, defer=False,
                          sample_every=4)
    ex, x, y, feed = _tiny_executor("samp", numerics=mon)
    for _ in range(10):
        ex.run("train", feed_dict=feed)
    mon.flush()
    assert mon.stats["processed"] == 3
    assert mon.stats["steps"] == 3
    steps = [e["step"] for e in mon.history]
    assert [s - steps[0] for s in steps] == [0, 4, 8]


def test_run_steps_sampled_window_delivery():
    """A run_steps window delivers its latest sampled row; a window
    containing no sampled step delivers nothing (the zeros filler must
    never surface as a fake row)."""
    import jax.numpy as jnp

    mon = NumericsMonitor(name="sampw", check_interval=1, defer=False,
                          sample_every=4)
    ex, x, y, feed = _tiny_executor("sampw", numerics=mon)
    clean = {x: jnp.asarray(feed[x]), y: jnp.asarray(feed[y])}
    ex.run_steps("train", clean, 10)      # steps 0..9: sampled 0,4,8
    assert mon.stats["processed"] == 1
    ex.run_steps("train", clean, 2)       # steps 10,11: no sample
    assert mon.stats["processed"] == 1
    ex.run_steps("train", clean, 2)       # steps 12,13: sample at 12
    assert mon.stats["processed"] == 2


# ---------------- anomaly escalation through each policy ----------------

_BAD_ROW = np.array([[np.nan, 1.0, 1.0]], np.float32)


def test_escalation_skip_policy_counts_one_per_streak():
    guard = StepGuard(policy="skip")
    mon = NumericsMonitor(name="esc_skip", check_interval=1, defer=False,
                          escalate_after=2, guard=guard)
    mon.on_step(None, ("lyr",), 0, _BAD_ROW)
    assert mon.stats["escalations"] == 0
    mon.on_step(None, ("lyr",), 1, _BAD_ROW)
    assert mon.stats["escalations"] == 1
    assert guard.stats["skipped"] == 1
    assert guard.stats["trip_steps"] == [1]
    # streak resets on escalation: the next trip needs a fresh streak
    mon.on_step(None, ("lyr",), 2, _BAD_ROW)
    assert mon.stats["escalations"] == 1
    mon.on_step(None, ("lyr",), 3, _BAD_ROW)
    assert mon.stats["escalations"] == 2


def test_escalation_abort_policy_raises():
    guard = StepGuard(policy="abort")
    mon = NumericsMonitor(name="esc_abort", check_interval=1,
                          defer=False, escalate_after=2, guard=guard)
    mon.on_step(None, ("lyr",), 0, _BAD_ROW)
    with pytest.raises(GuardTripped, match="numerics escalation"):
        mon.on_step(None, ("lyr",), 1, _BAD_ROW)


def test_escalation_rollback_policy_restores(tmp_path):
    """A sustained anomaly under policy='rollback' restores the last
    good checkpoint before any NaN ever reaches the parameters."""
    mgr = RollingCheckpointManager(str(tmp_path), keep=2)
    guard = StepGuard(policy="rollback", manager=mgr)
    mon = NumericsMonitor(name="esc_rb", check_interval=1, defer=False,
                          escalate_after=2, guard=guard)
    ex, x, y, feed = _tiny_executor("escrb", guard=guard, numerics=mon)
    ex.run("train", feed_dict=feed)
    guard.flush()
    mon.flush()
    mgr.save(ex)
    with pytest.warns(UserWarning, match="rolled back"):
        mon.on_step(ex, ("lyr",), 10, _BAD_ROW)
        mon.on_step(ex, ("lyr",), 11, _BAD_ROW)
    assert mon.stats["escalations"] == 1
    assert guard.stats["rollbacks"] == 1
    assert guard.stats["restored_steps"] == [1]
    assert all(np.isfinite(np.asarray(v)).all()
               for v in ex.params.values())


# ---------------- culprit attribution ----------------

def test_culprit_in_guardtripped_and_incident_dump(tmp_path, tel):
    """An abort trip names the layer that went non-finite — in the
    GuardTripped exception AND in the guard_trip incident dump."""
    fl = tel.get_flight()
    fl.configure(incident_dir=str(tmp_path))
    guard = StepGuard(policy="abort", defer=False)
    mon = NumericsMonitor(name="culprit", check_interval=1, defer=False)
    ex, x, y, feed = _tiny_executor("culprit", guard=guard, numerics=mon)
    ex.run("train", feed_dict=feed)
    bad = dict(feed)
    bad[x] = np.full((8, 4), np.nan, np.float32)
    with pytest.raises(GuardTripped) as ei:
        ex.run("train", feed_dict=bad)
    layers = set(mon.layers)
    assert ei.value.culprit is not None
    assert ei.value.culprit["first_nonfinite"] in layers
    assert "[culprit layer:" in str(ei.value)
    trips = [e for e in fl.incidents() if e["kind"] == "guard_trip"]
    assert trips, "no guard_trip incident recorded"
    dump = fl.load_dump(trips[-1]["path"])
    culprit = (dump.get("extra") or {}).get("culprit") or {}
    assert culprit.get("first_nonfinite") in layers


# ---------------- /numerics endpoint + report round-trip ----------------

def test_numerics_endpoint_round_trip(tel):
    mon = NumericsMonitor(name="endpoint_mon", check_interval=1,
                          defer=False)
    mon.on_step(None, ("lyr",), 0,
                np.array([[1.0, 0.25, 4.0]], np.float32))
    with start_http_server(
            port=0, registry=tel.get_registry(),
            debug_providers={"/numerics": telemetry.numerics_report}
    ) as srv:
        doc = json.loads(urllib.request.urlopen(
            f"{srv.url}/numerics", timeout=5).read().decode())
    assert "endpoint_mon" in doc
    lyr = doc["endpoint_mon"]["layers"]["lyr"]
    assert lyr["grad_norm"] == pytest.approx(1.0)
    assert lyr["update_norm"] == pytest.approx(0.5)
    assert lyr["param_norm"] == pytest.approx(2.0)
    assert lyr["update_ratio"] == pytest.approx(0.25)
    # the same block rides telemetry.report()["numerics"]
    rep = telemetry.report()["numerics"]
    assert rep["endpoint_mon"]["steps"] == 1


# ---------------- detach removes the stats from the step ----------------

def test_detach_stops_capture():
    mon = NumericsMonitor(name="det2", check_interval=1, defer=False)
    ex, x, y, feed = _tiny_executor("det2", numerics=mon)
    ex.run("train", feed_dict=feed)
    ex.run("train", feed_dict=feed)
    assert mon.stats["steps"] == 2
    mon.detach(ex)
    ex.run("train", feed_dict=feed)
    ex.run("train", feed_dict=feed)
    mon.flush()
    assert mon.stats["steps"] == 2


# ---------------- the disabled-mode cost contract ----------------

class _CountedRow:
    """A step's stats array that counts how often the host reads it."""

    def __init__(self, reads):
        self.reads = reads

    def __array__(self, dtype=None, copy=None):
        self.reads.append(1)
        return np.zeros((4, 3), dtype or np.float32)


def test_disabled_mode_on_step_cost_under_20us():
    """Telemetry off (the default), ``check_interval=1``, deferred: what
    "disabled" means, in counts (a CPU run shared with five other workers
    proves nothing about 20 us).  A step writes nothing to the registry and
    opens no span; the step's device array is read once, a step late, so
    ``on_step`` never waits for the device; and nothing a step allocates
    outlives the bounded history."""
    import tracemalloc
    telemetry.disable()
    reg, tracer = telemetry.get_registry(), telemetry.get_tracer()
    mon = NumericsMonitor(name="bench", check_interval=1, defer=True,
                          history_cap=8)
    layers = ("a", "b", "c", "d")
    reads = []
    for i in range(50):                     # fill the history, cache labels
        mon.on_step(None, layers, i, _CountedRow(reads))
    assert len(reads) == 49 and mon.pending_count == 1
    before, spans = reg.snapshot(), len(tracer)
    tracemalloc.start()
    try:
        held = tracemalloc.get_traced_memory()[0]
        for i in range(50, 450):
            mon.on_step(None, layers, i, _CountedRow(reads))
            assert mon.pending_count == 1       # read a step late, never now
        held = tracemalloc.get_traced_memory()[0] - held
    finally:
        tracemalloc.stop()
    assert len(reads) == 449                   # once a step, no second read
    assert mon.stats["steps"] == 449 and len(mon.history) == 8
    assert reg.snapshot() == before            # no registry write
    assert len(tracer) == spans                # no span
    assert held < 16 * 1024, f"{held} bytes kept over 400 steps"


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
