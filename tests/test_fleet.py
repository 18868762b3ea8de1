"""Fleet-serving contracts (hetu_tpu/serving/fleet.py + health.py).

The cluster-level robustness layer pinned here:
* latency-aware dispatch over replica telemetry (queue depth + TPOT
  EWMAs) and CLUSTER-level request ids ("e0-3": engine-instance prefix,
  deterministic per run, stable across failover);
* FAILOVER DETERMINISM — the headline: a greedy request failed over
  mid-decode (engine crash, wedge, or slot quarantine) yields a
  token stream BITWISE identical to an uninterrupted run, because the
  sibling re-prefills through the same shared executable and
  teacher-forces the already-delivered tokens;
* health state machine + circuit breaker (unit-level, hand clock);
* supervised restart over the shared compile-once program cache
  (retrace counters flat across restart);
* graceful drain / rolling restart with zero accepted-rid loss;
* typed FleetUnavailable with per-engine states + retry-after hint;
* hedged dispatch (duplicate + first-success-wins + loser cancelled);
* per-deployment latency histogram bucket overrides threaded through
  InferenceEngine/EngineFleet.
"""

import threading
import time
import warnings

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.models import LlamaConfig, LlamaForCausalLM
from hetu_tpu.resilience import faults
from hetu_tpu.serving import (EngineFleet, FleetUnavailable,
                              InferenceEngine)
from hetu_tpu.serving.health import (CircuitBreaker, DEGRADED, HEALTHY,
                                     QUARANTINED, ReplicaHealth, STOPPED)

V = 64
EKW = dict(n_slots=2, max_len=32, max_prompt_len=8, name="flt")


class ManualClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)


@pytest.fixture(scope="module")
def served():
    c = LlamaConfig(vocab_size=V, hidden_size=32, num_layers=2,
                    num_heads=4, num_kv_heads=2, intermediate_size=56,
                    seq_len=16)
    model = LlamaForCausalLM(c, name="flt")
    ids = ht.placeholder_op("flt_ids", (1, 4), dtype=np.int32)
    ex = ht.Executor([model(ids)])
    return ex, model


@pytest.fixture(scope="module")
def oracle(served):
    """Uninterrupted single-engine greedy streams for the fixed prompt
    set — the parity reference (shared compile-once programs make the
    comparison bitwise)."""
    ex, model = served
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, V, (int(L),))
               for L in rng.integers(3, 9, 6)]
    eng = InferenceEngine(ex, model, **EKW)
    return prompts, eng.generate_many(prompts, 10)


def _fleet(served, n=3, threaded=False, **kw):
    ex, model = served
    kw.setdefault("engine_kwargs", EKW)
    return EngineFleet(ex, model, n_engines=n, threaded=threaded, **kw)


import contextlib


@contextlib.contextmanager
def _quiet():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        yield


# -- health + breaker units --------------------------------------------------

def test_circuit_breaker_exponential_backoff():
    clk = ManualClock()
    b = CircuitBreaker(base=1.0, cap=8.0, clock=clk)
    assert b.allow()
    assert b.open_() == 1.0         # 1st failure: base
    assert not b.allow()
    assert b.retry_after() == pytest.approx(1.0)
    clk.advance(1.0)
    assert b.allow()                # backoff elapsed: half-open
    assert b.open_() == 2.0         # 2nd failure doubles
    assert b.open_() == 4.0
    assert b.open_() == 8.0         # capped
    assert b.open_() == 8.0
    b.close()
    assert b.failures == 0 and b.allow()
    assert b.open_() == 1.0         # streak reset
    assert b.opens == 6             # lifetime count survives close


def test_replica_health_state_machine():
    clk = ManualClock()
    h = ReplicaHealth("e0", degraded_after=1, quarantine_after=3,
                      recover_after=2, clock=clk)
    assert h.state == HEALTHY and h.dispatchable
    assert h.observe(1) == DEGRADED
    assert h.dispatchable               # degraded still serves
    assert h.observe(0) == DEGRADED     # one clean tick: not yet
    assert h.observe(0) == HEALTHY      # recover_after reached
    assert h.observe(2) == DEGRADED
    assert h.observe(1) == QUARANTINED  # 3 consecutive faults
    assert not h.dispatchable
    assert h.observe(0) == QUARANTINED  # external control from here
    h.to(HEALTHY, "restarted")
    assert h.consecutive_faults == 0
    # heartbeats age on the injected clock
    h.heartbeat()
    clk.advance(4.0)
    assert h.heartbeat_age() == pytest.approx(4.0)


# -- dispatch + rids ---------------------------------------------------------

def test_dispatch_balances_and_cluster_rids_deterministic(served,
                                                          oracle):
    prompts, base = oracle
    def run_once():
        fleet = _fleet(served)
        reqs = [fleet.submit(p, 10) for p in prompts]
        rids = [r.rid for r in reqs]
        fleet.wait(reqs)
        outs = [r.result() for r in reqs]
        fleet.stop()
        return rids, outs

    rids1, outs1 = run_once()
    rids2, outs2 = run_once()
    # engine-instance prefix + per-engine sequence, same every run
    assert rids1 == rids2
    assert all("-" in r and r.split("-")[0].startswith("e")
               for r in rids1)
    assert len(set(rids1)) == len(rids1)
    # depth-aware routing spreads an idle-fleet burst evenly
    assert sorted(r.split("-")[0] for r in rids1) == \
        ["e0", "e0", "e1", "e1", "e2", "e2"]
    for o, b in zip(outs1, base):
        np.testing.assert_array_equal(o, b)


def test_fleet_streams_match_single_engine(served, oracle):
    prompts, base = oracle
    fleet = _fleet(served, n=2)
    outs = fleet.generate_many(prompts, 10)
    fleet.stop()
    for o, b in zip(outs, base):
        np.testing.assert_array_equal(o, b)


# -- failover determinism (the headline) -------------------------------------

def test_crash_failover_token_parity_bitwise(served, oracle):
    """Kill a replica mid-decode: its in-flight greedy streams continue
    on siblings BITWISE identical to the uninterrupted run, keep their
    rids, and reach healthy terminal reasons."""
    prompts, base = oracle
    fleet = _fleet(served, breaker_base=1e-4)
    with _quiet():
        reqs = [fleet.submit(p, 10) for p in prompts]
        rids_before = [r.rid for r in reqs]
        fleet.pump(3)
        victim = max(fleet._replicas, key=lambda r: len(r.inflight))
        assert victim.inflight
        faults.crash_engine(victim.engine)
        fleet.wait(reqs)
    assert [r.rid for r in reqs] == rids_before
    assert all(r.finish_reason in ("eos", "max_new") for r in reqs)
    assert fleet.stats()["failovers"] >= 1
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(r.result(), b)
    # every live replica's pool balances
    for a in fleet.audit().values():
        assert a["allocs"] == a["frees"] and a["in_use"] == 0
    fleet.stop()


def test_slot_quarantine_fails_over_to_sibling_bitwise(served, oracle):
    """A slot-level watchdog quarantine ("error" at the engine) is
    retried on a sibling by the fleet — the single-engine terminal
    state becomes a cluster-level recovery, bitwise."""
    prompts, base = oracle
    fleet = _fleet(served, n=2)
    with _quiet():
        req = fleet.submit(prompts[0], 10)
        fleet.pump(2)
        rep = fleet._by_name(req.engine)
        attempt = req.attempt
        assert attempt.slot is not None
        faults.poison_slot_kv(rep.engine, attempt.slot)
        fleet.wait([req])
    assert req.finish_reason in ("eos", "max_new")
    assert req.failovers == 1
    assert req.engine != rep.name
    np.testing.assert_array_equal(req.result(), base[0])
    fleet.stop()


def test_failover_replay_never_redelivers_tokens(served, oracle):
    """Stream consumers see each token exactly once across a failover:
    replayed tokens are absorbed, not re-emitted."""
    prompts, base = oracle
    fleet = _fleet(served, breaker_base=1e-4)
    got = {}
    def cb(tok, freq):
        got.setdefault(freq.rid, []).append(tok)
    with _quiet():
        reqs = [fleet.submit(p, 10, stream=cb) for p in prompts[:4]]
        fleet.pump(3)
        victim = max(fleet._replicas, key=lambda r: len(r.inflight))
        faults.crash_engine(victim.engine)
        fleet.wait(reqs)
    assert fleet.stats()["failovers"] >= 1
    for r, b in zip(reqs, base):
        assert got[r.rid] == list(b)        # once each, in order
    fleet.stop()


def test_fleet_churn_soak_audits_balanced_everywhere(served):
    """Fleet-wide churn: a burst of mixed-length requests, a crash, a
    cancellation, a deadline — every accepted rid reaches a terminal
    finish_reason and allocs==frees on every live replica."""
    ex, model = served
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, V, (int(L),))
               for L in rng.integers(3, 9, 24)]
    fleet = _fleet(served, breaker_base=1e-4,
                   engine_kwargs=dict(EKW, max_queue=16))
    with _quiet():
        reqs = []
        for i, p in enumerate(prompts):
            reqs.append(fleet.submit(p, int(rng.integers(2, 9))))
            if i % 3 == 2:
                fleet.pump()
            if i == 12:
                victim = max(fleet._replicas,
                             key=lambda r: len(r.inflight))
                faults.crash_engine(victim.engine)
            if i == 15:
                fleet.cancel(reqs[14].rid)
        fleet.wait(reqs)
    assert all(r.finished for r in reqs)
    reasons = {r.finish_reason for r in reqs}
    assert reasons <= {"eos", "max_new", "cancelled"}
    for a in fleet.audit().values():
        assert a["allocs"] == a["frees"] and a["in_use"] == 0
    # records on every replica carry cluster-prefixed ids
    fleet.stop()


# -- supervised restart + compile-once ---------------------------------------

def test_restart_reuses_shared_program_cache(served, oracle):
    prompts, base = oracle
    fleet = _fleet(served, breaker_base=1e-4)
    with _quiet():
        before = fleet.trace_counts()
        reqs = [fleet.submit(p, 8) for p in prompts[:3]]
        fleet.pump(2)
        victim = max(fleet._replicas, key=lambda r: len(r.inflight))
        faults.crash_engine(victim.engine)
        fleet.wait(reqs)
    s = fleet.stats()
    assert s["engines"][victim.name]["incarnation"] >= 1  # restarted
    assert s["engines"][victim.name]["state"] == HEALTHY
    # the restarted replica decodes clean work immediately…
    out = fleet.generate_many([prompts[0]], 8)
    np.testing.assert_array_equal(out[0], base[0][:8])
    # …and never retraced: same executables as before the crash
    assert fleet.trace_counts() == before == \
        {"prefill": 1, "step": 1}
    fleet.stop()


def test_operator_restart_of_live_replica_fails_work_over(served,
                                                          oracle):
    """restart() on a replica still holding work must not drop it: the
    restart imposes a quarantine first, so the streams fail over
    (bitwise) instead of vanishing with the bookkeeping."""
    prompts, base = oracle
    fleet = _fleet(served)
    with _quiet():
        reqs = [fleet.submit(p, 10) for p in prompts[:3]]
        fleet.pump(2)
        victim = max(fleet._replicas, key=lambda r: len(r.inflight))
        assert victim.inflight
        fleet.restart(victim.name)
        fleet.wait(reqs)
    assert all(r.finish_reason in ("eos", "max_new") for r in reqs)
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(r.result(), b)
    fleet.stop()


def test_drain_and_rolling_restart_zero_loss(served, oracle):
    prompts, base = oracle
    fleet = _fleet(served)
    with _quiet():
        reqs = [fleet.submit(p, 10) for p in prompts[:4]]
        fleet.pump(2)
        fleet.rolling_restart()
        reqs += [fleet.submit(p, 10) for p in prompts[4:]]
        fleet.wait(reqs)
    assert all(r.finish_reason in ("eos", "max_new") for r in reqs)
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(r.result(), b)
    s = fleet.stats()
    assert all(v["incarnation"] >= 1 for v in s["engines"].values())
    assert s["trace_counts"] == {"prefill": 1, "step": 1}
    fleet.stop()


# -- availability ------------------------------------------------------------

def test_fleet_unavailable_typed_with_states_and_retry_hint(served):
    clk = ManualClock()
    fleet = _fleet(served, n=2, clock=clk, auto_restart=False,
                   breaker_base=2.0, quarantine_after=1)
    with _quiet():
        r = fleet.submit(np.array([1, 2, 3]), 4)
        fleet.pump()
        for rep in fleet._replicas:
            faults.crash_engine(rep.engine, at=0)
        # both replicas crash on their next tick -> quarantined
        fleet.pump(2)
    with pytest.raises(FleetUnavailable) as ei:
        fleet.submit(np.array([1, 2, 3]), 4)
    assert ei.value.states == {"e0": QUARANTINED, "e1": QUARANTINED}
    assert ei.value.retry_after is not None
    assert 0.0 < ei.value.retry_after <= 2.0   # min breaker backoff
    # the harvested request is parked, not lost: restart re-homes it
    fleet.restart("e0")
    with _quiet():
        fleet.wait([r])
    assert r.finish_reason in ("eos", "max_new")
    fleet.stop()


def test_drained_fleet_raises_unavailable_without_retry_hint(served):
    fleet = _fleet(served, n=2)
    fleet.drain(wait=True)
    assert all(r.health.state == STOPPED for r in fleet._replicas)
    with pytest.raises(FleetUnavailable) as ei:
        fleet.submit(np.array([1, 2, 3]), 4)
    assert ei.value.retry_after is None     # nothing counting down
    fleet.stop()


# -- hedged dispatch ---------------------------------------------------------

def test_hedged_dispatch_first_success_wins_loser_cancelled(served,
                                                            oracle):
    prompts, base = oracle
    fleet = _fleet(served, n=2)
    with _quiet():
        req = fleet.submit(prompts[0], 10, hedge=True)
        assert fleet.hedged == 1
        fleet.wait([req])
        fleet.pump(3)       # let the loser's cancel land
    np.testing.assert_array_equal(req.result(), base[0])
    assert req.finish_reason in ("eos", "max_new")
    for a in fleet.audit().values():
        assert a["allocs"] == a["frees"] and a["in_use"] == 0
    snap = telemetry.get_registry().snapshot()
    assert "hetu_fleet_hedged_dispatches_total" in snap
    fleet.stop()


# -- wedge detection (threaded) ----------------------------------------------

@pytest.mark.timeout(120)
def test_wedged_replica_quarantined_by_supervisor_threaded(served,
                                                           oracle):
    """A replica stuck inside step() can't run its own bookkeeping —
    the SUPERVISOR must see the stale heartbeat, quarantine from
    outside, fail the streams over (bitwise), and restart."""
    prompts, base = oracle
    with _quiet():
        fleet = _fleet(served, n=2, threaded=True, wedge_timeout=0.25,
                       breaker_base=0.01)
        fleet.generate_many(prompts[:2], 4, timeout=60)
        victim = fleet._replicas[0]
        faults.wedge_engine(victim.engine, 1.5)
        reqs = [fleet.submit(p, 10) for p in prompts[:4]]
        fleet.wait(reqs, timeout=60)
        fleet._wait_for(lambda: victim.incarnation >= 1, 60, "restart")
    assert all(r.finish_reason in ("eos", "max_new") for r in reqs)
    for r, b in zip(reqs, base):
        np.testing.assert_array_equal(r.result(), b)
    assert fleet.stats()["failovers"] >= 1
    fleet.stop()


def test_first_call_of_a_program_is_not_a_wedge_under_the_derived_bound(
        served, oracle):
    """The first call of each program variant may compile, for tens of
    seconds at published widths on a chip.  The derived wedge bound (no
    ``wedge_timeout=``) waits for it; the same stall on a warm program
    is still a wedge."""
    prompts, base = oracle
    fl = telemetry.get_flight()
    was = fl.enabled
    fl.enabled = True
    try:
        with _quiet():
            # wedge_safety=1: the derived bound stays at the floor even
            # after the stalled first request has inflated the TPOT EWMA
            fleet = _fleet(served, n=1, wedge_floor=0.2, wedge_safety=1.0,
                           breaker_base=0.01)
            rep = fleet._replicas[0]
            n0 = fl.incident_count("engine_wedge")
            # stall the engine's FIRST decode call, as a compilation would
            faults.wedge_engine(rep.engine, 0.6)
            req = fleet.submit(prompts[0], 10)
            for _ in range(40):
                if req.finished:
                    break
                fleet.pump()
            assert req.finished and req.engines == ["e0"]
            assert fl.incident_count("engine_wedge") == n0
            np.testing.assert_array_equal(req.result(), base[0])
            # the decode program is warm now: the same stall is a wedge
            time.sleep(0.25)          # past the grace after a cold call
            faults.wedge_engine(rep.engine, 0.6)
            req2 = fleet.submit(prompts[1], 10)
            fleet.pump(2)
            assert fl.incident_count("engine_wedge") == n0 + 1
            fleet.wait([req2])
    finally:
        fl.enabled = was
    fleet.stop()


# -- latency bucket overrides ------------------------------------------------

def test_latency_buckets_threaded_through_engine_and_fleet(served):
    reg = telemetry.get_registry()
    reg.reset()
    try:
        custom = (0.001, 0.1, 1.0)
        eng = InferenceEngine(*served, latency_buckets=custom, **EKW)
        for name in ("hetu_serving_ttft_seconds",
                     "hetu_serving_tpot_seconds",
                     "hetu_serving_queue_wait_seconds"):
            assert reg.histogram(name, labels=("scheduler",),
                                 buckets=custom).buckets == custom
        # a later engine demanding a DIFFERENT ladder fails loudly
        # (instruments are cached by name — silent sharing would lie)
        with pytest.raises(ValueError, match="buckets"):
            InferenceEngine(*served, latency_buckets=(0.5, 5.0), **EKW)
        eng.generate_many([np.array([1, 2, 3])], 2)
        reg.reset()
        fleet = _fleet(served, n=2, latency_buckets=custom)
        assert reg.histogram("hetu_serving_ttft_seconds",
                             labels=("scheduler",),
                             buckets=custom).buckets == custom
        fleet.generate_many([np.array([1, 2, 3])], 2)
        fleet.stop()
    finally:
        reg.reset()


# -- telemetry surface -------------------------------------------------------

def test_fleet_instruments_on_registry(served):
    reg = telemetry.get_registry()
    reg.reset()
    reg.enable()
    try:
        fleet = _fleet(served, n=2, breaker_base=1e-4)
        with _quiet():
            reqs = [fleet.submit(np.array([1, 2, 3, 4]), 6)
                    for _ in range(4)]
            fleet.pump(2)
            victim = max(fleet._replicas,
                         key=lambda r: len(r.inflight))
            faults.crash_engine(victim.engine)
            fleet.wait(reqs)
            fleet.drain("e1" if victim.name == "e0" else "e0",
                        wait=True)
        snap = reg.snapshot()
        assert "hetu_fleet_engine_health_state" in snap
        states = {s["labels"]["engine"]: s["value"]
                  for s in snap["hetu_fleet_engine_health_state"]
                  ["samples"]}
        assert set(states) == {"e0", "e1"}
        failovers = snap["hetu_fleet_failovers_total"]["samples"][0]
        assert failovers["value"] >= 1
        assert snap["hetu_fleet_breaker_opens_total"]["samples"]
        assert snap["hetu_fleet_restarts_total"]["samples"]
        assert snap["hetu_fleet_drains_total"]["samples"]
        assert snap["hetu_serving_replayed_tokens_total"]["samples"]
        fleet.stop()
    finally:
        reg.disable()
        reg.reset()


def test_fleet_stats_surface(served):
    fleet = _fleet(served, n=2)
    out = fleet.generate_many([np.array([1, 2, 3])], 4)
    assert len(out[0]) == 4
    s = fleet.stats()
    assert s["n_engines"] == 2
    assert s["submitted"] == s["completed"] == 1
    assert s["finish_reasons"] == {"max_new": 1}
    assert set(s["engines"]) == {"e0", "e1"}
    for e in s["engines"].values():
        assert {"state", "dispatches", "tpot_ewma",
                "breaker_opens"} <= set(e)
    fleet.stop()


def test_no_nondaemon_threads_survive_fleet(served):
    """Fleet drivers/supervisors are daemons and are joined at stop —
    nothing non-daemon may outlive the fleet (the conftest fixture
    enforces the same at module scope)."""
    before = set(threading.enumerate())
    with _quiet():
        fleet = _fleet(served, n=2, threaded=True)
        fleet.generate_many([np.array([1, 2, 3])], 4, timeout=60)
        fleet.stop()
    time.sleep(0.05)
    new = [t for t in threading.enumerate()
           if t not in before and t.is_alive() and not t.daemon]
    assert new == []
