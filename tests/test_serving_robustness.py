"""Serving-under-failure contracts (hetu_tpu/serving/ + resilience).

The request-lifecycle robustness layer pinned here:
* admission control — bounded queue, typed EngineOverloaded with a
  queue-depth hint, watermark hysteresis, documented shed policies;
* deadlines — expiry at admission (zero tokens, no slot ever held) and
  mid-flight (partial tokens, slot freed immediately), finish_reason
  "deadline" both ways;
* cancellation — queued and running, slot reclaimed on the spot, no
  leak across churn;
* decode watchdog — a poisoned slot is quarantined alone: the OTHER
  requests' token streams stay bitwise identical to a clean run, the
  engine loop survives, and the reused slot decodes clean;
* slot-leak reconcile + stream-consumer detach;
* request ids scoped per scheduler (no process-global leakage).

The fault stages proper (poisoned decode, raising step, slot leak,
stalled consumer, overload burst, deadline/cancel churn, each against its
unprotected twin) are tests/test_chaos_stages.py.
"""

import warnings

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.models import LlamaConfig, LlamaForCausalLM
from hetu_tpu.resilience import InjectedFault, faults
from hetu_tpu.serving import EngineOverloaded, InferenceEngine

V = 64


class ManualClock:
    """Deterministic engine clock: deadline tests advance time by hand
    instead of racing the wall clock."""

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
    model = LlamaForCausalLM(c, name="srv_rob")
    ids = ht.placeholder_op("srv_rob_ids", (1, 4), dtype=np.int32)
    ex = ht.Executor([model(ids)])
    return ex, model


def _prompts(rng, n, lo=3, hi=9):
    return [rng.integers(1, V, (int(L),))
            for L in rng.integers(lo, hi, n)]


def _engine(served, **kw):
    ex, model = served
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 32)
    kw.setdefault("max_prompt_len", 8)
    return InferenceEngine(ex, model, name="srv_rob", **kw)


# -- admission control -------------------------------------------------------

def test_overload_raises_typed_with_queue_depth_hint(served, rng):
    eng = _engine(served, max_queue=2)
    eng.submit(_prompts(rng, 1)[0], 4)
    eng.submit(_prompts(rng, 1)[0], 4)
    with pytest.raises(EngineOverloaded) as ei:
        eng.submit(_prompts(rng, 1)[0], 4)
    assert ei.value.queue_depth == 2
    assert ei.value.max_queue == 2
    assert eng.scheduler.rejected == 1
    assert eng.scheduler.queue_depth_peak == 2
    eng.run(max_iterations=500)


def test_watermark_hysteresis_reopens_after_drain(served, rng):
    """Once the high watermark trips, admission stays closed until the
    queue drains to the LOW watermark — no accept/reject flapping at
    the edge."""
    eng = _engine(served, n_slots=1, max_queue=4, low_watermark=1,
                  prefill_budget=1)
    reqs = [eng.submit(p, 2) for p in _prompts(rng, 4)]
    with pytest.raises(EngineOverloaded):
        eng.submit(_prompts(rng, 1)[0], 2)
    # one admission (queue 4 -> 3): still above low watermark -> closed
    eng.step()
    assert len(eng.scheduler.queue) == 3
    with pytest.raises(EngineOverloaded):
        eng.submit(_prompts(rng, 1)[0], 2)
    # drain to <= low watermark: admission reopens
    while len(eng.scheduler.queue) > 1:
        eng.step()
    late = eng.submit(_prompts(rng, 1)[0], 2)
    eng.run(max_iterations=500)
    assert late.finished and all(r.finished for r in reqs)
    assert eng.scheduler.rejected == 2


def test_drop_expired_first_sheds_dead_seats(served, rng):
    """Under drop_expired_first a full queue of expired requests is shed
    to seat live work; the shed requests finish with reason "deadline"
    and land in records."""
    clk = ManualClock()
    eng = _engine(served, n_slots=1, max_queue=2,
                  shed_policy="drop_expired_first", clock=clk)
    dead = [eng.submit(p, 4, ttl=1.0) for p in _prompts(rng, 2)]
    clk.advance(5.0)
    live = eng.submit(_prompts(rng, 1)[0], 4)
    assert all(r.finish_reason == "deadline" for r in dead)
    assert all(len(r.tokens) == 0 for r in dead)
    recorded = {r["id"]: r["finish_reason"] for r in eng.records}
    assert {d.rid for d in dead} <= set(recorded)
    eng.run(max_iterations=500)
    assert live.finish_reason == "max_new"
    # reject_newest (the default) refuses the newcomer instead
    eng2 = _engine(served, n_slots=1, max_queue=2, clock=clk)
    for p in _prompts(rng, 2):
        eng2.submit(p, 4, ttl=1.0)
    clk.advance(5.0)
    with pytest.raises(EngineOverloaded):
        eng2.submit(_prompts(rng, 1)[0], 4)
    eng2.run(max_iterations=500)


# -- deadlines ---------------------------------------------------------------

def test_queued_deadline_expires_without_taking_a_slot(served, rng):
    clk = ManualClock()
    eng = _engine(served, n_slots=1, clock=clk)
    hog = eng.submit(_prompts(rng, 1)[0], 10)
    doomed = eng.submit(_prompts(rng, 1)[0], 10, ttl=5.0)
    eng.step()
    clk.advance(10.0)
    eng.run(max_iterations=500)
    assert hog.finish_reason == "max_new" and len(hog.tokens) == 10
    assert doomed.finish_reason == "deadline"
    assert doomed.tokens == []
    # never admitted: exactly ONE slot alloc (the hog's)
    assert eng.cache.alloc_count == eng.cache.free_count == 1
    rec = next(r for r in eng.records if r["id"] == doomed.rid)
    assert rec["finish_reason"] == "deadline"
    assert rec["ttft"] is None      # no first token ever
    assert eng.expirations == 1


def test_midflight_deadline_returns_partial_and_frees_slot(served, rng):
    clk = ManualClock()
    eng = _engine(served, n_slots=1, clock=clk)
    req = eng.submit(_prompts(rng, 1)[0], 12, ttl=3.0)
    eng.step()
    eng.step()
    produced = len(req.tokens)
    assert 0 < produced < 12
    clk.advance(5.0)
    eng.step()          # expiry sweep retires it mid-flight
    assert req.finished and req.finish_reason == "deadline"
    assert len(req.tokens) == produced          # partial result kept
    assert eng.cache.n_free == eng.cache.n_slots
    assert eng.cache.alloc_count == eng.cache.free_count == 1


def test_ttl_and_deadline_are_exclusive_and_validated(served, rng):
    clk = ManualClock()
    eng = _engine(served, clock=clk)
    with pytest.raises(ValueError, match="not both"):
        eng.submit(_prompts(rng, 1)[0], 4, ttl=1.0, deadline=2.0)
    with pytest.raises(ValueError, match="ttl"):
        eng.submit(_prompts(rng, 1)[0], 4, ttl=0.0)


# -- cancellation ------------------------------------------------------------

def test_cancel_running_frees_slot_immediately(served, rng):
    eng = _engine(served, n_slots=1)
    req = eng.submit(_prompts(rng, 1)[0], 12)
    eng.step()
    eng.step()
    produced = len(req.tokens)
    assert produced > 0 and req.slot is not None
    assert eng.cancel(req.rid) is True
    assert req.finished and req.finish_reason == "cancelled"
    assert req.slot is None
    assert eng.cache.n_free == eng.cache.n_slots   # freed on the spot
    assert len(req.tokens) == produced             # partial result kept
    assert eng.cancel(req.rid) is False            # already finished
    assert eng.cancel(10 ** 9) is False            # unknown rid


def test_cancel_queued_never_takes_a_slot(served, rng):
    eng = _engine(served, n_slots=1)
    hog = eng.submit(_prompts(rng, 1)[0], 6)
    queued = eng.submit(_prompts(rng, 1)[0], 6)
    eng.step()
    assert eng.cancel(queued.rid) is True
    assert queued.finish_reason == "cancelled"
    assert queued.tokens == []
    eng.run(max_iterations=500)
    assert hog.finish_reason == "max_new"
    assert eng.cache.alloc_count == eng.cache.free_count == 1


def test_cancel_churn_no_slot_leak(served, rng):
    """Cancel every third request (queued or mid-flight) while the rest
    churn through a small pool: alloc/free balance, everything reaches a
    terminal state, records carry every request."""
    eng = _engine(served, n_slots=2, prefill_budget=1)
    n = 18
    reqs = [eng.submit(p, int(m)) for p, m in
            zip(_prompts(rng, n), rng.integers(2, 9, n))]
    it = 0
    while not eng.scheduler.idle:
        eng.step()
        it += 1
        if it % 2 == 0:
            victims = [r for r in reqs
                       if r.rid % 3 == 0 and not r.finished]
            if victims:
                eng.cancel(victims[0].rid)
        assert it < 2000
    assert all(r.finished for r in reqs)
    assert eng.cache.alloc_count == eng.cache.free_count
    assert eng.cache.n_free == eng.cache.n_slots
    assert len(eng.records) == n
    cancelled = [r for r in reqs if r.finish_reason == "cancelled"]
    assert cancelled and eng.cancellations == len(cancelled)


# -- decode watchdog ---------------------------------------------------------

def test_watchdog_quarantines_only_poisoned_slot_bitwise(served, rng):
    """Poison one slot's KV mid-flight: that request retires with
    "error"; the OTHER requests' token streams are bitwise identical to
    a clean run, and the engine survives."""
    prompts = _prompts(rng, 3)
    clean = _engine(served, n_slots=3, prefill_budget=3)
    baseline = clean.generate_many(prompts, 8)

    eng = _engine(served, n_slots=3, prefill_budget=3)
    reqs = [eng.submit(p, 8) for p in prompts]
    eng.step()
    faults.poison_slot_kv(eng, reqs[1].slot)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng.run(max_iterations=500)
    assert reqs[1].finish_reason == "error"
    assert eng.watchdog_trips >= 1
    np.testing.assert_array_equal(reqs[0].result(), baseline[0])
    np.testing.assert_array_equal(reqs[2].result(), baseline[2])
    assert eng.cache.alloc_count == eng.cache.free_count
    # the quarantined slot is REUSABLE: stale NaN rows are never
    # attended (col <= position masks them until overwritten)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        fresh = eng.generate_many([prompts[0]], 8)[0]
    np.testing.assert_array_equal(fresh, baseline[0])


def test_raising_step_retires_in_flight_and_engine_survives(served, rng):
    prompts = _prompts(rng, 2)
    eng = _engine(served)
    reqs = [eng.submit(p, 8) for p in prompts]
    undo = faults.raising_engine_step(eng, at=1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng.run(max_iterations=500)
    assert all(r.finish_reason == "error" for r in reqs)
    assert eng.cache.n_free == eng.cache.n_slots
    # the engine keeps serving NEW work after the fault
    out = eng.generate_many([prompts[0]], 6)
    assert len(out[0]) == 6
    undo()


def test_unprotected_twin_propagates_the_same_fault(served, rng):
    eng = _engine(served, watchdog=False)
    eng.submit(_prompts(rng, 1)[0], 8)
    faults.raising_engine_step(eng, at=0)
    with pytest.raises(InjectedFault):
        eng.run(max_iterations=500)


def test_slot_leak_reconciled_within_one_iteration(served, rng):
    eng = _engine(served)
    leaked = faults.leak_slot(eng)
    assert leaked is not None
    reqs = [eng.submit(p, 4) for p in _prompts(rng, 3)]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng.run(max_iterations=500)
    assert all(r.finished for r in reqs)
    assert eng.slot_leaks_reclaimed >= 1
    assert eng.cache.alloc_count == eng.cache.free_count
    assert eng.cache.n_free == eng.cache.n_slots


def test_stream_consumer_raise_and_stall_are_detached(served, rng):
    clk = ManualClock()
    eng = _engine(served, stream_stall_timeout=1.0, clock=clk)
    # a consumer that raises on its second delivery
    got = []
    fail_cb = faults.stalling_consumer(0, collect=got, fail_after=1)

    # a consumer that "stalls" (advances the engine clock past the
    # bound) on every delivery
    stalls = []

    def stall_cb(tok, req):
        stalls.append(tok)
        clk.advance(5.0)

    r1 = eng.submit(_prompts(rng, 1)[0], 6, stream=fail_cb)
    r2 = eng.submit(_prompts(rng, 1)[0], 6, stream=stall_cb)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng.run(max_iterations=500)
    # both detached; decode still completed the full budget
    assert eng.streams_detached == 2
    assert len(r1.tokens) == 6 and len(r2.tokens) == 6
    assert len(got) == 2        # delivered once, raised on the second
    assert len(stalls) == 1     # stalled once, never called again
    assert r1.finish_reason == r2.finish_reason == "max_new"


# -- request-id scoping ------------------------------------------------------

def test_request_ids_scoped_per_scheduler(served, rng):
    """Two engines each number their requests from 0 — ids no longer
    leak across engines (or test ordering) through a process-global
    counter."""
    a = _engine(served)
    b = _engine(served)
    ra = [a.submit(p, 2) for p in _prompts(rng, 3)]
    rb = [b.submit(p, 2) for p in _prompts(rng, 3)]
    assert [r.rid for r in ra] == [0, 1, 2]
    assert [r.rid for r in rb] == [0, 1, 2]
    a.run(max_iterations=500)
    b.run(max_iterations=500)


# -- stats surface -----------------------------------------------------------

def test_stats_carries_robustness_counters(served, rng):
    clk = ManualClock()
    eng = _engine(served, max_queue=2, clock=clk)
    eng.submit(_prompts(rng, 1)[0], 4)
    eng.submit(_prompts(rng, 1)[0], 4, ttl=1.0)
    with pytest.raises(EngineOverloaded):
        eng.submit(_prompts(rng, 1)[0], 4)
    clk.advance(2.0)
    eng.step()
    s = eng.stats()
    assert s["rejections"] == 1
    assert s["expirations"] == 1
    assert s["queue_depth_peak"] == 2
    for k in ("cancellations", "watchdog_trips",
              "slot_leaks_reclaimed", "streams_detached"):
        assert k in s
    eng.run(max_iterations=500)
