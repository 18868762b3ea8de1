"""Fault-recovery stages, one tier-1 case a stage, all in-process.

Each case injects one fault class through ``hetu_tpu.resilience.faults``
into the product's public entry points — a guarded W&D train step, the
continuous-batching ``InferenceEngine``, the ``EngineFleet`` — and holds
the product to what recovery means for that fault: every injected fault
recovered, no accepted request lost, slot and page audits balanced, and
where the stage has an unprotected twin, the twin demonstrably dying,
wedging or leaking on the same seed.

Telemetry is live for the whole module (as an operator runs it), so four
stages also run under a plane probe: the injected fault must fire exactly
its named SLO alert rule and the lost capacity must land in the matching
goodput bucket.  Every serve and fleet case ends with the per-rid audit:
each accepted rid of the stage shows a complete admit-to-terminal
timeline, stitched across however many failovers it survived.

Nothing here compares a duration: sleeps and stall timeouts are the
faults themselves (a wedged step, a straggler, a stalled consumer).
"""

import contextlib
import os
import time
import warnings

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.datasets.prefetch import DevicePrefetcher
from hetu_tpu.models import WDL, LlamaConfig, LlamaForCausalLM
from hetu_tpu.resilience import (FaultInjector, InjectedFault,
                                 RollingCheckpointManager, StepGuard,
                                 faults)
from hetu_tpu.serving import (SLO, EngineFleet, EngineOverloaded,
                              FleetController, InferenceEngine, SLOReject)
from hetu_tpu.telemetry import (AlertManager, GoodputLedger,
                                NumericsMonitor, TimeSeriesStore, slo_rules)

SEED = 0
STEPS = 12          # train steps per stage: enough room to place faults


@contextlib.contextmanager
def _quiet():
    """Recovery paths warn by design (quarantine, rollback, shed)."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        yield


# -- the plane probe ---------------------------------------------------------

#: stage -> (alert rule that MUST fire, goodput bucket the lost capacity
#: MUST land in, fault rules that must stay quiet).  The quiet set is the
#: four fault rules minus legitimate co-trips: transfer_drop crashes its
#: donor on purpose, so engine_crashes may fire beside it.
PROBES = {
    "nan_rollback": ("guard_trips", "rollback",
                     ("engine_crashes", "migration_failures",
                      "overload_shed")),
    "overload_burst": ("overload_shed", "brownout_shed",
                       ("guard_trips", "engine_crashes",
                        "migration_failures")),
    "engine_crash": ("engine_crashes", "failover_replay",
                     ("guard_trips", "migration_failures",
                      "overload_shed")),
    "transfer_drop": ("migration_failures", "kv_migration",
                      ("guard_trips", "overload_shed")),
}


def _under_plane_probe(rule, cause, quiet, stage_fn):
    """Run ``stage_fn`` inside a dedicated time-series plane — its own
    ring, the standard ``slo_rules`` AlertManager and a scoped
    GoodputLedger, all on one manual clock (1.0 per poll).  The ring's
    first frames baseline the registry as it stands now, so counter
    movement from earlier cases cannot pass for this stage's burst.
    window=8 ticks, for_ticks=2: the fault fires on the second post-fault
    poll and ages out after eight, so one pass walks the whole state
    machine (pending -> firing -> resolved, no flapping)."""
    reg = telemetry.get_registry()
    now = [0.0]
    clock = lambda: now[0]      # noqa: E731
    ledger = GoodputLedger(registry=reg, tracer=telemetry.get_tracer(),
                           name=f"probe_{rule}", clock=clock, enabled=True)
    store = TimeSeriesStore(registry=reg, capacity=256, clock=clock,
                            enabled=True)
    alerts = AlertManager(store, slo_rules(window=8.0, for_ticks=2),
                          registry=reg, flight=telemetry.get_flight(),
                          clock=clock, enabled=True)

    def poll(n):
        fired = set()
        for _ in range(n):
            now[0] += 1.0
            fired.update(alerts.poll(now[0]))
        return fired

    poll(3)                             # pre-fault baseline
    ledger.begin(now=now[0])
    # the ledger prices buckets in measured seconds, so the window it
    # divides by is the stage's own span; nothing is asserted of it
    t0 = time.perf_counter()
    stage_fn()
    window_s = time.perf_counter() - t0
    fired = poll(4)                     # detection window
    acct = ledger.account(wall_s=window_s, now=now[0])
    poll(12)                            # the fault ages out: resolve

    assert rule in fired, \
        f"injected fault did not fire {rule!r} (fired: {sorted(fired)})"
    firings = [t for s, t in alerts.transitions(rule) if s == "firing"]
    assert len(firings) == 1, f"{rule!r} flapped: firing at {firings}"
    assert alerts.state(rule) in ("resolved", "inactive"), \
        f"{rule!r} never resolved (state {alerts.state(rule)!r})"
    for q in quiet:
        q_fired = [t for s, t in alerts.transitions(q) if s == "firing"]
        assert not q_fired, \
            f"unrelated rule {q!r} fired at {q_fired} during {rule!r}"
    fractions = acct["fractions"]
    assert abs(sum(fractions.values()) - 1.0) <= 1e-6, fractions
    assert fractions[cause] > 0.0, \
        f"no lost capacity attributed to {cause!r} (lost: {acct['lost']})"


def _run_stage(name, stage_fn):
    if name in PROBES:
        _under_plane_probe(*PROBES[name], stage_fn)
    else:
        stage_fn()


def _assert_rids_complete(prefix):
    """Every accepted rid minted under ``prefix`` (a stage names its
    engines and replicas after itself) reached a terminal finish on one
    stitched timeline.  Unprotected twins are "twin.<stage>": they die
    by design and fall outside every stage's prefix."""
    rt = telemetry.get_request_trace()
    mine = [r for r in rt.rids() if str(r).startswith(prefix)]
    assert mine, f"no rid traced under {prefix!r}: the audit is blind"
    bad = [r for r in mine if not rt.complete(r)]
    assert not bad, f"incomplete rid timelines: {bad[:8]}"


def _assert_balanced(audit):
    assert audit["allocs"] == audit["frees"] and audit["in_use"] == 0, audit
    assert audit.get("page_allocs", 0) == audit.get("page_frees", 0), audit


# -- set-ups, one a family ---------------------------------------------------

@pytest.fixture(scope="module")
def plane(tmp_path_factory):
    """Telemetry on for the module, incident dumps under pytest's tmp."""
    fl = telemetry.get_flight()
    was_on, was_dir = telemetry.enabled(), fl.incident_dir
    telemetry.enable(
        incident_dir=str(tmp_path_factory.mktemp("chaos_incidents")))
    yield
    fl.configure(incident_dir=was_dir)
    if not was_on:
        telemetry.disable()


@pytest.fixture(scope="module")
def trainer(plane):
    """``build(tag, guard, numerics) -> (executor, batch)``: a small W&D
    train step (cheap, with a NaN-prone float path through dense and
    labels) and a deterministic per-step batch maker.  Params are
    name-stable, so a rebuilt executor restores 1:1."""
    B, rows = 32, 2000

    def build(tag, guard=None, numerics=None):
        with ht.name_scope():
            dense = ht.placeholder_op(f"cz_dense_{tag}", (B, 13))
            sparse = ht.placeholder_op(f"cz_sparse_{tag}", (B, 26),
                                       dtype=np.int32)
            labels = ht.placeholder_op(f"cz_labels_{tag}", (B,))
            loss = WDL(rows, embedding_dim=8).loss(dense, sparse, labels)
        ex = ht.Executor(
            {"train": [loss, ht.AdamOptimizer(0.01).minimize(loss)]},
            step_guard=guard, numerics=numerics)

        def batch(i, bad=False):
            r = np.random.default_rng(1000 + i)
            d = r.standard_normal((B, 13)).astype(np.float32)
            if bad:
                d[0, 0] = np.nan
            return {dense: d,
                    sparse: r.integers(0, rows, (B, 26)).astype(np.int32),
                    labels: r.integers(0, 2, (B,)).astype(np.float32)}

        return ex, batch

    return build


class _Served:
    """A tiny decode model behind its executor: the faults, not the
    shapes, are the subject.  Name-seeded init, so every engine over it
    serves the same weights."""

    def __init__(self, name):
        c = LlamaConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=4, num_kv_heads=2, intermediate_size=56,
                        seq_len=16)
        self.name, self.vocab = name, c.vocab_size
        self.model = LlamaForCausalLM(c, name=name)
        ids = ht.placeholder_op(f"{name}_ids", (1, 4), dtype=np.int32)
        self.ex = ht.Executor([self.model(ids)])

    def engine(self, instance, **kw):
        return InferenceEngine(self.ex, self.model, name=self.name,
                               seed=SEED, instance=instance, **kw)

    def prompts(self, n, seed):
        rng = np.random.default_rng(seed)
        return [rng.integers(1, self.vocab, (int(L),))
                for L in rng.integers(3, 9, n)]


@pytest.fixture(scope="module")
def served(plane):
    return _Served("czs")


@pytest.fixture(scope="module")
def fleet_served(plane):
    return _Served("czf")


# -- training family ---------------------------------------------------------

def _nan_skip(build, tmp_path):
    """NaN batches absorbed by the skip policy: the fused select keeps
    params clean and the run finishes finite.  The NumericsMonitor riding
    along must attribute every trip to a model layer, and the guard_trip
    incident dump must NAME that layer."""
    guard = StepGuard(policy="skip")
    mon = NumericsMonitor(name="chaos_nan", check_interval=1)
    ex, batch = build("skip", guard, numerics=mon)
    fault_at = set(FaultInjector(SEED).pick_steps(STEPS, n_faults=2))
    for i in range(STEPS):
        ex.run("train", feed_dict=batch(i, bad=i in fault_at))
    guard.flush()
    mon.flush()
    final = ex.run("train", feed_dict=batch(STEPS),
                   convert_to_numpy_ret_vals=True)
    assert guard.stats["skipped"] >= len(fault_at)
    assert np.isfinite(final[0])
    layers = set(mon.layers or ())
    assert mon.culprit().get("first_nonfinite") in layers
    fl = telemetry.get_flight()
    trips = [e for e in fl.incidents() if e["kind"] == "guard_trip"]
    assert trips, "no guard_trip incident despite injected NaNs"
    dump = fl.load_dump(trips[-1]["path"])
    named = ((dump.get("extra") or {}).get("culprit")
             or {}).get("first_nonfinite")
    assert named in layers, \
        f"guard_trip incident dump culprit {named!r} is not a layer"
    mon.close()


def _nan_rollback(build, tmp_path):
    """A NaN that DOES corrupt params (no in-graph select under the
    rollback policy) restores the last rolling checkpoint."""
    mgr = RollingCheckpointManager(str(tmp_path), keep=2)
    guard = StepGuard(policy="rollback", manager=mgr, defer=False)
    ex, batch = build("rb", guard)
    (fault_at,) = FaultInjector(SEED).pick_steps(
        STEPS, n_faults=1, low=max(2, STEPS // 3))
    with _quiet():
        for i in range(STEPS):
            if i % 5 == 0:
                mgr.save(ex)
            ex.run("train", feed_dict=batch(i, bad=i == fault_at))
        guard.flush()
    assert guard.stats["rollbacks"] >= 1
    assert all(np.isfinite(np.asarray(v)).all()
               for v in ex.params.values()
               if np.issubdtype(np.asarray(v).dtype, np.floating))


def _prefetch_kill(build, tmp_path):
    """Silent producer death mid-stream surfaces within one step; a fresh
    prefetcher resumes the run."""
    ex, batch = build("pk", StepGuard(policy="skip"))
    (kill_at,) = FaultInjector(SEED).pick_steps(
        STEPS, n_faults=1, low=max(2, STEPS // 3))
    src = (batch(i) for i in range(10 ** 9))
    pf = DevicePrefetcher(faults.killer_stream(src, at=kill_at), depth=2,
                          sync=False)
    n_ok = 0
    with pytest.raises(RuntimeError, match="producer"):
        for _ in range(STEPS):
            ex.run("train", feed_dict=next(pf))
            n_ok += 1
    pf.close()
    assert n_ok == kill_at          # detected within one step
    pf2 = DevicePrefetcher((batch(i) for i in range(8)), depth=2,
                           sync=False)
    for _ in range(3):
        ex.run("train", feed_dict=next(pf2))
    pf2.close()


def _torn_ckpt(build, tmp_path):
    """Tear the NEWEST checkpoint; restore_latest falls back to the
    previous good one."""
    mgr = RollingCheckpointManager(str(tmp_path), keep=3)
    ex, batch = build("tc")
    for i in range(6):
        ex.run("train", feed_dict=batch(i))
        mgr.save(ex)
    newest, second = mgr.entries()[:2]
    faults.tear_file(os.path.join(str(tmp_path), newest["file"]), frac=0.5)
    with _quiet():
        assert mgr.restore_latest(ex) == second["step"]


def _preempt(build, tmp_path):
    """Simulated SIGTERM preemption: the hook flushes a checkpoint and
    the run resumes bitwise from it."""
    mgr = RollingCheckpointManager(str(tmp_path), keep=2)
    ex, batch = build("pre")
    mgr.install_preemption_hook(ex, exit_on_save=False)
    try:
        for i in range(5):
            ex.run("train", feed_dict=batch(i))
        faults.simulate_preemption()
        assert mgr.preempted, "the hook flushed no checkpoint"
        saved = {k: np.asarray(v).copy() for k, v in ex.params.items()}
        for i in range(5, 8):   # post-preemption work that will be lost
            ex.run("train", feed_dict=batch(i))
        mgr.restore_latest(ex)
        for k, v in saved.items():      # bitwise resume
            np.testing.assert_array_equal(v, np.asarray(ex.params[k]))
    finally:
        mgr.uninstall_preemption_hook()


TRAIN_STAGES = {"nan_skip": _nan_skip, "nan_rollback": _nan_rollback,
                "prefetch_kill": _prefetch_kill, "torn_ckpt": _torn_ckpt,
                "preempt": _preempt}


# prefetch_kill's producer thread dies by SystemExit: that is the fault
@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
@pytest.mark.timeout(240)
@pytest.mark.parametrize("stage", list(TRAIN_STAGES))
def test_train_stage_recovers(stage, trainer, tmp_path):
    _run_stage(stage, lambda: TRAIN_STAGES[stage](trainer, tmp_path))


# -- single-engine family ----------------------------------------------------

def _nan_decode(sv, tag):
    """Poison one running slot's KV mid-flight: the protected engine
    quarantines exactly that request (finish_reason="error") and the
    other streams stay bitwise identical to a clean run; the unprotected
    twin serves NaN-derived tokens as if healthy."""
    prompts = sv.prompts(3, SEED)
    kw = dict(n_slots=3, max_len=32, max_prompt_len=8, prefill_budget=3)
    baseline = sv.engine(f"{tag}.clean", **kw).generate_many(prompts, 8)

    def poisoned_run(watchdog):
        eng = sv.engine(f"{tag}.prot" if watchdog else f"twin.{tag}",
                        watchdog=watchdog, **kw)
        reqs = [eng.submit(p, 8) for p in prompts]
        eng.step()
        faults.poison_slot_kv(eng, reqs[1].slot)
        with _quiet():
            eng.run(max_iterations=500)
        return eng, reqs

    eng, reqs = poisoned_run(watchdog=True)
    assert reqs[1].finish_reason == "error"
    np.testing.assert_array_equal(reqs[0].result(), baseline[0])
    np.testing.assert_array_equal(reqs[2].result(), baseline[2])
    assert eng.watchdog_trips >= 1
    _assert_balanced(eng.cache.audit())
    _, ureqs = poisoned_run(watchdog=False)
    assert ureqs[1].finish_reason in ("eos", "max_new")


def _raising_step(sv, tag):
    """A decode step that RAISES: the protected engine retires the
    in-flight batch with "error" and keeps serving new requests; the
    unprotected twin dies on the spot."""
    prompts = sv.prompts(2, SEED + 1)
    kw = dict(n_slots=2, max_len=32, max_prompt_len=8)
    eng = sv.engine(f"{tag}.prot", **kw)
    reqs = [eng.submit(p, 8) for p in prompts]
    faults.raising_engine_step(eng, at=2)
    with _quiet():
        eng.run(max_iterations=500)
        after = eng.generate_many([prompts[0]], 6)
    assert all(r.finish_reason == "error" for r in reqs)
    assert len(after[0]) == 6           # still serving after the fault
    _assert_balanced(eng.cache.audit())
    ueng = sv.engine(f"twin.{tag}", watchdog=False, **kw)
    for p in prompts:
        ueng.submit(p, 8)
    faults.raising_engine_step(ueng, at=2)
    with pytest.raises(InjectedFault):
        ueng.run(max_iterations=500)


def _slot_leak(sv, tag):
    """Leak EVERY free slot: the protected engine's reconcile sweep
    reclaims them within one iteration and the queue drains; the
    unprotected twin starves — queued requests are never admitted."""
    prompts = sv.prompts(3, SEED + 2)
    kw = dict(n_slots=2, max_len=32, max_prompt_len=8)
    eng = sv.engine(f"{tag}.prot", **kw)
    leaked = 0
    while faults.leak_slot(eng) is not None:
        leaked += 1
    assert leaked == 2
    reqs = [eng.submit(p, 6) for p in prompts]
    with _quiet():
        eng.run(max_iterations=500)
    assert all(r.finished for r in reqs)
    assert eng.slot_leaks_reclaimed >= leaked
    _assert_balanced(eng.cache.audit())
    ueng = sv.engine(f"twin.{tag}", watchdog=False, **kw)
    while faults.leak_slot(ueng) is not None:
        pass
    for p in prompts:
        ueng.submit(p, 6)
    with pytest.raises(RuntimeError):   # never drains: no slot is left
        ueng.run(max_iterations=50)


def _stalled_consumer(sv, tag):
    """One stream consumer stalls, another raises: the protected engine
    detaches each after one bounded delivery and finishes the requests;
    their tokens still land in result()."""
    prompts = sv.prompts(2, SEED + 3)
    stall = 0.05
    eng = sv.engine(f"{tag}.prot", n_slots=2, max_len=32, max_prompt_len=8,
                    stream_stall_timeout=stall / 4)
    r1 = eng.submit(prompts[0], 6, stream=faults.stalling_consumer(stall))
    r2 = eng.submit(prompts[1], 6,
                    stream=faults.stalling_consumer(0, fail_after=1))
    with _quiet():
        eng.run(max_iterations=500)
    assert eng.streams_detached >= 2
    assert len(r1.tokens) == 6 and len(r2.tokens) == 6
    _assert_balanced(eng.cache.audit())


def _overload_burst(sv, tag):
    """Arrival burst 4x the queue bound: the protected engine sheds with
    typed EngineOverloaded rejections at a bounded depth and finishes
    everything it admitted; the unprotected twin queues the whole burst
    (unbounded growth — the OOM path in production)."""
    n_burst, max_queue = 24, 6
    prompts = sv.prompts(n_burst, SEED + 4)
    kw = dict(n_slots=2, max_len=32, max_prompt_len=8)
    eng = sv.engine(f"{tag}.prot", max_queue=max_queue, **kw)
    accepted, rejected = [], 0
    with _quiet():
        for i, p in enumerate(prompts):
            try:
                accepted.append(eng.submit(p, 4))
            except EngineOverloaded:
                rejected += 1
            if i % 4 == 3:
                # the burst outruns decode 4:1 — admission must stay
                # closed until the queue drains to the low watermark,
                # then reopen (the hysteresis cycle, not one hard edge)
                eng.step()
        eng.run(max_iterations=2000)
    assert rejected > 0
    assert eng.scheduler.queue_depth_peak <= max_queue
    assert all(r.finished for r in accepted)    # zero accepted loss
    _assert_balanced(eng.cache.audit())
    ueng = sv.engine(f"twin.{tag}", watchdog=False, **kw)
    for p in prompts:
        ueng.submit(p, 4)
    assert ueng.scheduler.queue_depth_peak > eng.scheduler.queue_depth_peak
    with _quiet():
        ueng.run(max_iterations=5000)


def _deadline_cancel(sv, tag):
    """Deadline expiry (queued AND mid-flight) and mid-flight cancel: all
    three return partial results with the right finish_reason and free
    their slots at once."""
    prompts = sv.prompts(4, SEED + 5)
    eng = sv.engine(f"{tag}.prot", n_slots=1, max_len=32, max_prompt_len=8)
    with _quiet():
        ra = eng.submit(prompts[0], 20)              # hogs the one slot
        rb = eng.submit(prompts[1], 8, ttl=1e-6)     # expires queued
        eng.step()
        eng.step()
        rc = eng.submit(prompts[2], 20)
        rd = eng.submit(prompts[3], 20)
        # drive ra out, let rc get the slot and produce a few tokens
        eng.cancel(ra.rid)
        for _ in range(3):
            eng.step()
        # mid-flight expiry: force rc's deadline into the past
        rc.deadline = eng._now() - 1.0
        eng.step()
        eng.cancel(rd.rid)
        eng.run(max_iterations=500)
    assert rb.finish_reason == "deadline" and len(rb.tokens) == 0
    assert rc.finish_reason == "deadline" and 0 < len(rc.tokens) < 20
    assert ra.finish_reason == "cancelled" and 0 < len(ra.tokens) < 20
    assert rd.finish_reason == "cancelled"
    _assert_balanced(eng.cache.audit())


SERVE_STAGES = {"nan_decode": _nan_decode, "raising_step": _raising_step,
                "slot_leak": _slot_leak,
                "stalled_consumer": _stalled_consumer,
                "overload_burst": _overload_burst,
                "deadline_cancel": _deadline_cancel}


@pytest.mark.timeout(240)
@pytest.mark.parametrize("stage", list(SERVE_STAGES))
def test_serve_stage_recovers(stage, served):
    _run_stage(stage, lambda: SERVE_STAGES[stage](served, stage))
    _assert_rids_complete(f"{stage}.")


# -- fleet family ------------------------------------------------------------

_FLEET_EKW = dict(n_slots=2, max_len=32, max_prompt_len=8)
#: paged replicas for the KV-migration stages: page migration is a
#: block-table splice, so dense slots cannot carry it; n_slots=4 leaves
#: receivers FREE slots to adopt into
_MIG_EKW = dict(_FLEET_EKW, n_slots=4, paged=True, page_len=4)
_SLO_DT = 0.05      # virtual seconds per pump iteration


def _oracle(sv, prompts, max_new, tag, ekw=_FLEET_EKW):
    """Uninterrupted single-engine greedy streams: the parity oracle of
    every failover stage (shared compile-once programs make the
    comparison bitwise)."""
    return sv.engine(f"{tag}.base", **ekw).generate_many(prompts, max_new)


def _fleet(sv, tag, n, ekw=_FLEET_EKW, **kw):
    """A fleet whose replicas (and so its rids) carry the stage's tag."""
    return EngineFleet(sv.ex, sv.model, n_engines=n,
                       engine_kwargs=dict(ekw, name=sv.name),
                       replica_prefix=f"{tag}.e", **kw)


def _assert_zero_loss(fleet, reqs, baseline=None):
    """The zero-loss contract: every accepted rid terminal with a healthy
    reason, every replica's audit balanced, and greedy parity with the
    oracle when one is given."""
    assert all(r.finished for r in reqs)
    assert {r.finish_reason for r in reqs} <= {"eos", "max_new"}
    for a in fleet.audit().values():
        _assert_balanced(a)
    if baseline is not None:
        for r, b in zip(reqs, baseline):
            np.testing.assert_array_equal(r.result(), b)


def _busiest(fleet):
    return max(fleet._replicas, key=lambda r: len(r.inflight))


def _engine_crash(sv, tag):
    """Kill one replica mid-decode: its in-flight requests fail over
    (replayed bitwise) and the supervisor restarts it from the shared
    program cache; the SINGLE-ENGINE twin loses every in-flight stream on
    the same seed."""
    prompts = sv.prompts(6, SEED)
    baseline = _oracle(sv, prompts, 10, tag)
    fleet = _fleet(sv, tag, 3, threaded=False,
                   breaker_base=1e-4)
    with _quiet():
        reqs = [fleet.submit(p, 10) for p in prompts]
        fleet.pump(3)
        victim = _busiest(fleet)
        in_flight = len(victim.inflight)
        assert in_flight
        faults.crash_engine(victim.engine)
        fleet.wait(reqs, timeout=120)
    s = fleet.stats()
    _assert_zero_loss(fleet, reqs, baseline)
    assert s["failovers"] >= in_flight
    assert s["engines"][victim.name]["incarnation"] >= 1
    assert fleet.trace_counts() == {"prefill": 1, "step": 1}
    fleet.stop()
    # the same crash with no fleet above it: the process survives (it is
    # an exception) but every in-flight stream is LOST — no terminal
    # finish_reason, no more tokens, ever
    twin = sv.engine(f"twin.{tag}", **_FLEET_EKW)
    treqs = [twin.submit(p, 10) for p in prompts]
    for _ in range(3):
        twin.step()
    faults.crash_engine(twin)
    with pytest.raises(InjectedFault):
        twin.run(max_iterations=500)
    assert sum(1 for r in treqs if not r.finished) > 0


def _engine_wedge(sv, tag):
    """Wedge one replica's decode step (a hung device call): its driver
    thread is stuck, the heartbeat goes stale, and the SUPERVISOR must
    quarantine from outside, fail the streams over, and restart."""
    prompts = sv.prompts(4, SEED + 11)
    baseline = _oracle(sv, prompts, 10, tag)
    with _quiet():
        fleet = _fleet(sv, tag, 2, threaded=True,
                       wedge_timeout=0.25, breaker_base=0.01)
        # route one warm request everywhere so EWMAs exist
        fleet.generate_many(prompts[:2], 4, timeout=60)
        victim = fleet._replicas[0]
        faults.wedge_engine(victim.engine, 1.0)
        reqs = [fleet.submit(p, 10) for p in prompts]
        fleet.wait(reqs, timeout=120)
        # the breaker-gated restart puts the replica back in service
        fleet._wait_for(lambda: victim.incarnation >= 1, 60, "restart")
        s = fleet.stats()
        _assert_zero_loss(fleet, reqs, baseline)
        fleet.stop()
    assert s["failovers"] >= 1


def _slow_engine(sv, tag):
    """One straggler replica (every step sleeps): not a fault — the
    latency-aware router must LEARN to route around it from the TPOT
    EWMAs, while the straggler still finishes what it holds."""
    prompts = sv.prompts(15, SEED + 22)
    # threaded: under manual pumping every replica shares the caller's
    # clock, a straggler's sleeps inflate EVERYONE's TPOT and the EWMAs
    # never separate; with a driver thread each, its latency is its own
    fleet = _fleet(sv, tag, 3, threaded=True, wedge_timeout=30.0)
    slow = fleet._replicas[0]
    # many healthy steps per straggler step, so one seed round separates
    # the EWMAs decisively
    faults.slow_engine(slow.engine, 0.05)
    with _quiet():
        # seed round: one request per replica so every EWMA is measured
        fleet.generate_many(prompts[:3], 4, timeout=120)
        reqs = []
        for p in prompts[3:]:
            reqs.append(fleet.submit(p, 6))
            time.sleep(0.02)            # arrivals spaced, not a burst
        fleet.wait(reqs, timeout=120)
    disp = {r.name: r.dispatches for r in fleet._replicas}
    _assert_zero_loss(fleet, reqs)
    fleet.stop()
    # routed around: the straggler draws no more work than any fast
    # replica AND under a fair share (a fast sibling absorbing nearly
    # everything is the router working, not failing)
    assert disp[slow.name] <= min(v for k, v in disp.items()
                                  if k != slow.name), disp
    assert disp[slow.name] < sum(disp.values()) / len(disp), disp


def _rolling_restart(sv, tag):
    """Drain and restart every replica in turn while requests keep
    arriving: zero accepted-rid loss, retrace counters flat (restarts
    reuse the shared compile-once program cache)."""
    prompts = sv.prompts(9, SEED + 33)
    baseline = _oracle(sv, prompts, 8, tag)
    fleet = _fleet(sv, tag, 3, threaded=False)
    with _quiet():
        reqs = [fleet.submit(p, 8) for p in prompts[:5]]
        fleet.pump(2)
        fleet.rolling_restart()
        reqs += [fleet.submit(p, 8) for p in prompts[5:]]
        fleet.wait(reqs, timeout=120)
    s = fleet.stats()
    _assert_zero_loss(fleet, reqs, baseline)
    assert all(e["incarnation"] >= 1 for e in s["engines"].values())
    assert fleet.trace_counts() == {"prefill": 1, "step": 1}
    fleet.stop()


def _burst_failover(sv, tag):
    """Arrival burst against bounded per-replica queues, then kill the
    replica with the deepest backlog: queued AND running requests all
    fail over; rejected requests were never accepted (honest shed, not
    loss)."""
    prompts = sv.prompts(18, SEED + 44)
    fleet = _fleet(sv, tag, 3, dict(_FLEET_EKW, max_queue=4),
                   threaded=False, breaker_base=1e-4, max_failovers=5)
    accepted = []
    with _quiet():
        for p in prompts:
            with contextlib.suppress(EngineOverloaded):
                accepted.append(fleet.submit(p, 6))
        fleet.pump(2)
        victim = max(fleet._replicas,
                     key=lambda r: len(r.engine.scheduler.queue)
                     + len(r.inflight))
        faults.crash_engine(victim.engine)
        fleet.wait(accepted, timeout=240)
    s = fleet.stats()
    _assert_zero_loss(fleet, accepted)
    assert s["failovers"] >= 1
    fleet.stop()


def _slo_controller(sv, tag):
    """Replica crash under the SLO controller, mid-burst: predictive
    admission sheds provably-infeasible work with a typed SLOReject
    BEFORE it takes a slot, the controller scales up through the same
    supervised machinery the crash exercises, and every ACCEPTED rid
    still reaches a terminal finish — the control plane never costs
    correctness."""
    # a virtual clock the loop advances one quantum an iteration:
    # deadlines, EWMAs, breaker backoff and controller cooldowns all see
    # the same seeded timeline on every run
    now = [0.0]
    fleet = _fleet(sv, tag, 1, threaded=False, clock=lambda: now[0],
                   breaker_base=1e-4, name="chaos_slo")
    ctl = FleetController(fleet, SLO(deadline_miss_target=0.05),
                          min_engines=1, max_engines=3,
                          scale_up_queue=2.0, cooldown_s=0.5)
    prompts = sv.prompts(16, SEED)
    reqs, doomed, sheds = [], [], 0
    crashed = False
    with _quiet():
        for it in range(1200):
            if it < len(prompts):
                # one arrival per iteration: a burst one replica cannot
                # absorb, plus two DOOMED deadlines once the cost model
                # has a finished request to learn from
                is_doomed = it in (11, 13)
                try:
                    freq = ctl.submit(prompts[it], 8,
                                      ttl=0.01 if is_doomed else 30.0)
                    (doomed if is_doomed else reqs).append(freq)
                except SLOReject:
                    sheds += 1
            fleet.pump()
            ctl.tick()
            now[0] += _SLO_DT
            if not crashed and ctl.scale_ups >= 1 and it >= len(prompts):
                victim = _busiest(fleet)
                if victim.engine is not None:
                    faults.crash_engine(victim.engine)
                    crashed = True
            if crashed and it > len(prompts) + 10 and fleet.idle:
                break
    _assert_zero_loss(fleet, reqs)
    # a doomed request that slipped past admission still reaches a
    # TERMINAL state (deadline): shed-or-expire changes efficiency,
    # never bookkeeping
    assert all(r.finished for r in doomed)
    assert crashed and ctl.scale_ups >= 1 and sheds >= 1
    fleet.stop()
    ctl.stop()


def _transfer_fault(sv, tag, seed, inject):
    """Crash the busiest paged replica with every migration blob faulted
    in flight: page migration fails LOUDLY — counted, never silently
    adopted — and teacher-forced replay takes over with zero accepted-rid
    loss and the same bitwise streams."""
    prompts = sv.prompts(4, seed)
    baseline = _oracle(sv, prompts, 10, tag, _MIG_EKW)
    fleet = _fleet(sv, tag, 3, _MIG_EKW, threaded=False,
                   breaker_base=1e-4)
    with _quiet():
        reqs = [fleet.submit(p, 10) for p in prompts]
        fleet.pump(3)
        for i in range(8):
            inject(fleet, i)
        victim = _busiest(fleet)
        in_flight = len(victim.inflight)
        faults.crash_engine(victim.engine)
        fleet.wait(reqs, timeout=240)
    s = fleet.stats()
    _assert_zero_loss(fleet, reqs, baseline)
    assert s["migrations"] == 0 and s["migration_failures"] >= 1
    assert s["failovers"] >= in_flight
    fleet.stop()


def _transfer_drop(sv, tag):
    """Dropped frames: each injector drops the FIRST transfer it sees, so
    a stack of them swallows every blob the stage can produce."""
    _transfer_fault(sv, tag, SEED + 55,
                    lambda fleet, i: faults.drop_transfer(fleet, at=0))


def _transfer_corrupt(sv, tag):
    """A flipped byte mid-wire, rejected by the CRC32 frame.  Corrupted
    bytes flow through the whole filter chain, so each injector targets a
    DISTINCT transfer index: an even stack of same-byte XOR flips on one
    blob would cancel out."""
    _transfer_fault(sv, tag, SEED + 66,
                    lambda fleet, i: faults.corrupt_transfer(fleet, at=i))


def _donor_crash_mid_migration(sv, tag):
    """The donor dies MID-MIGRATION (scale-down drain): the first blob
    never lands (the wire died with the donor) and the stream it carried
    re-homes by replay off the corpse's quarantine; later streams still
    escape by page migration — the donor's host-side state outlives its
    wedged device step."""
    prompts = sv.prompts(4, SEED + 77)
    baseline = _oracle(sv, prompts, 10, tag, _MIG_EKW)
    fleet = _fleet(sv, tag, 3, _MIG_EKW, threaded=False,
                   breaker_base=1e-4)
    fired = []
    with _quiet():
        reqs = [fleet.submit(p, 10) for p in prompts]
        fleet.pump(3)
        victim = _busiest(fleet)

        def die_mid_transfer(blob):
            if fired:
                return blob
            fired.append(True)
            faults.crash_engine(victim.engine)
            return None             # the wire died with the donor

        fleet.transfer_filter = die_mid_transfer
        fleet.drain(victim.name, wait=False, migrate=True)
        fleet.wait(reqs, timeout=240)
    s = fleet.stats()
    _assert_zero_loss(fleet, reqs, baseline)
    assert fired and s["migration_failures"] >= 1
    fleet.stop()


FLEET_STAGES = {"engine_crash": _engine_crash,
                "engine_wedge": _engine_wedge,
                "slow_engine": _slow_engine,
                "rolling_restart": _rolling_restart,
                "burst_failover": _burst_failover,
                "slo_controller": _slo_controller,
                "transfer_drop": _transfer_drop,
                "transfer_corrupt": _transfer_corrupt,
                "donor_crash_mid_migration": _donor_crash_mid_migration}


@pytest.mark.timeout(420)
@pytest.mark.parametrize("stage", list(FLEET_STAGES))
def test_fleet_stage_recovers(stage, fleet_served):
    _run_stage(stage, lambda: FLEET_STAGES[stage](fleet_served, stage))
    _assert_rids_complete(f"{stage}.")
