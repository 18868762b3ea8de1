"""Live KV page migration (serving/kv_transfer.py) + the four fleet
robustness paths that ride it (serving/fleet.py, control.py).

The contracts pinned here:

* WIRE ROUND-TRIP — ``export_pages``/``import_pages`` move raw pool
  rows (f32, int8, fp8 where supported) bit-exactly, refcounts land
  caller-owned on the receiver, and page audits balance on both pools;
  torn frames and CRC mismatches are rejected LOUDLY with both pools
  untouched.
* BITWISE CONTINUATION — a stream migrated mid-decode (snapshot →
  splice → donor ack) is bitwise identical to an uninterrupted run,
  for greedy AND sampled requests, on f32 AND quantized pools, across
  all four fleet paths: crash failover, SLO rebalance, migrate-then-
  drain, and prefill→decode role handoff.
* REPLAY IS THE ORACLE — every injected transfer fault (drop, corrupt,
  tear) falls back to teacher-forced replay with zero accepted-rid
  loss and the same bitwise streams.
* DONOR ACK ORDER — the donor frees its side only after the receiver
  adopted the stream; a failed adopt rolls the receiver back.
* DISPATCH WEDGE (satellite) — a manual ``pump()`` fleet arms a
  watcher deadline BEFORE each tick, so a step that wedges INSIDE the
  dispatch is quarantined + failed over while the pumping caller is
  still stuck (incident mode="dispatch").
"""

import os

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.models import LlamaConfig, LlamaForCausalLM
from hetu_tpu.ops import quant
from hetu_tpu.resilience import faults
from hetu_tpu.serving import (EngineFleet, InferenceEngine,
                              PagedKVCache, TransferError, blob_info,
                              can_migrate, resume_request,
                              snapshot_request)
from hetu_tpu.serving import kv_transfer as kvt
from hetu_tpu.serving.health import QUARANTINED

import contextlib
import warnings

V = 64
EKW = dict(n_slots=4, max_len=32, max_prompt_len=8, name="mig",
           paged=True, page_len=4)

FP8 = "fp8"


@contextlib.contextmanager
def _quiet():
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        yield


@pytest.fixture(scope="module")
def served():
    c = LlamaConfig(vocab_size=V, hidden_size=32, num_layers=2,
                    num_heads=4, num_kv_heads=2, intermediate_size=56,
                    seq_len=16)
    model = LlamaForCausalLM(c, name="mig")
    ids = ht.placeholder_op("mig_ids", (1, 4), dtype=np.int32)
    ex = ht.Executor([model(ids)])
    return ex, model


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(7)
    return [rng.integers(1, V, (int(L),))
            for L in rng.integers(3, 9, 4)]


SAMPLING = {"greedy": {},
            "sampled": dict(temperature=0.8, top_k=4, seed=123)}

_oracles = {}


def _oracle(served, prompts, kv, mode):
    """Uninterrupted single-engine streams, per (pool dtype, sampling)
    variant — quantized KV changes the logits, so each pool dtype has
    its own bitwise reference."""
    key = (kv, mode)
    if key not in _oracles:
        ex, model = served
        kw = dict(EKW)  # same geometry as the fleet replicas, so the
        # oracle shares their decode executable (per-row streams are
        # batch-size independent — the parity below proves it)
        if kv is not None:
            kw["kv_dtype"] = kv
        eng = InferenceEngine(ex, model, **kw)
        reqs = [eng.submit(p, 10, **SAMPLING[mode]) for p in prompts]
        eng.run(max_iterations=500)
        _oracles[key] = [list(map(int, r.result())) for r in reqs]
        eng.close()
    return _oracles[key]


def _fleet(served, n=3, kv=None, **kw):
    ex, model = served
    ekw = dict(EKW)
    if kv is not None:
        ekw["kv_dtype"] = kv
    kw.setdefault("engine_kwargs", ekw)
    return EngineFleet(ex, model, n_engines=n, threaded=False, **kw)


def _close_balanced(fleet):
    """Stop the fleet and assert every live pool's page audit balances
    (allocs == frees — migration leaked nothing on either side).  The
    audit runs after close() so prefix-cache-retained pages, released
    on close, are settled too."""
    fleet.stop()
    for rep in fleet._replicas:
        if rep.engine is not None:
            rep.engine.close()
            a = rep.engine.cache.audit()
            assert a["page_allocs"] == a["page_frees"], (rep.name, a)
            assert a["pages_in_use"] == 0, (rep.name, a)


# -- pool-level wire round-trip ----------------------------------------------

def _pool(kv, n_pages=9, page_len=4):
    kw = {} if kv is None else {"kv_dtype": kv}
    return PagedKVCache(2, layers=2, kv_heads=2, page_len=page_len,
                        head_dim=4, max_len=16, n_pages=n_pages, **kw)


def _fill(pool, pages, rng):
    """Write recognizable data straight into the pool arrays."""
    idx = np.asarray(pages)
    if pool.kv_dtype is None:
        rows = rng.normal(size=(len(pages),) + pool.k.shape[1:])
        pool.k = pool.k.at[idx].set(rows.astype(pool.k.dtype))
        pool.v = pool.v.at[idx].set((2 * rows).astype(pool.v.dtype))
    else:
        import jax.numpy as jnp
        codes = rng.integers(-127, 128,
                             size=(len(pages),) + pool.k.codes.shape[1:])
        scales = rng.uniform(0.01, 1.0,
                             size=(len(pages),) + pool.k.scales.shape[1:])
        pool.k = type(pool.k)(
            pool.k.codes.at[idx].set(
                jnp.asarray(codes, pool.k.codes.dtype)),
            pool.k.scales.at[idx].set(
                jnp.asarray(scales, pool.k.scales.dtype)),
            pool.k.qdtype)
        pool.v = type(pool.v)(
            pool.v.codes.at[idx].set(
                jnp.asarray(-codes, pool.v.codes.dtype)),
            pool.v.scales.at[idx].set(
                jnp.asarray(scales, pool.v.scales.dtype)),
            pool.v.qdtype)


@pytest.mark.parametrize("kv", [None, "int8", FP8])
def test_export_import_roundtrip_bitwise(kv):
    rng = np.random.default_rng(3)
    donor, recv = _pool(kv), _pool(kv)
    slot = donor.alloc(owner="d0", n_tokens=8)
    pages = donor.slot_pages(slot)
    _fill(donor, pages, rng)
    payload = donor.export_pages(pages)
    got = recv.import_pages(payload)
    assert got is not None and len(got) == len(pages)
    # re-export from the receiver: the raw bytes must be identical
    back = recv.export_pages(got)
    for name in payload:
        if name == "kv_dtype":
            assert back[name] == payload[name]
            continue
        a, b = np.asarray(payload[name]), np.asarray(back[name])
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes(), name
    # imported pages are ref-1 caller-owned: releasing balances
    recv.release_pages(got)
    donor.free(slot)
    for pool in (donor, recv):
        a = pool.audit()
        assert a["page_allocs"] == a["page_frees"], a
        assert a["pages_in_use"] == 0, a


def test_import_refcounts_compose_with_shared_alloc():
    """The engine-adopt splice: import (ref 1) → alloc(shared=) (ref 2,
    mapped) → release (ref 1, private again, writes legal)."""
    donor, recv = _pool(None), _pool(None)
    slot = donor.alloc(owner="d0", n_tokens=8)
    pages = donor.slot_pages(slot)
    # donor side SHARED (prefix-cache style, CoW territory): export is
    # a pure read — refcounts don't travel, ownership does
    donor.retain_pages(pages)
    payload = donor.export_pages(pages)
    got = recv.import_pages(payload)
    new = recv.alloc(owner="r0", n_tokens=16, shared=got)
    assert list(recv.slot_pages(new))[:len(got)] == list(got)
    recv.release_pages(got)       # slot now sole owner: private pages
    assert all(recv._ref[p] == 1 for p in got)
    recv.free(new)
    donor.release_pages(pages)
    donor.free(slot)
    for pool in (donor, recv):
        a = pool.audit()
        assert a["page_allocs"] == a["page_frees"], a


def test_import_refuses_dtype_and_shape_drift():
    donor = _pool("int8")
    slot = donor.alloc(owner="d0", n_tokens=8)
    payload = donor.export_pages(donor.slot_pages(slot))
    with pytest.raises(ValueError, match="kv_dtype"):
        _pool(None).import_pages(payload)
    bad = dict(payload)
    bad["k_codes"] = np.asarray(payload["k_codes"])[..., :2]
    with pytest.raises(ValueError, match="shape"):
        _pool("int8").import_pages(bad)


def test_import_pool_exhaustion_returns_none_without_leak():
    donor, tiny = _pool(None), _pool(None, n_pages=2)  # 1 usable page
    slot = donor.alloc(owner="d0", n_tokens=8)         # 2 pages
    payload = donor.export_pages(donor.slot_pages(slot))
    before = tiny.audit()
    assert tiny.import_pages(payload) is None
    after = tiny.audit()
    assert after["page_allocs"] == before["page_allocs"]
    assert after["pages_in_use"] == before["pages_in_use"]


# -- blob framing ------------------------------------------------------------

def _live_blob(served, prompts, kv=None, steps=4, **sampling):
    """One real mid-decode snapshot + its (engine, req) for reuse."""
    ex, model = served
    kw = dict(EKW)
    if kv is not None:
        kw["kv_dtype"] = kv
    eng = InferenceEngine(ex, model, **kw)
    req = eng.submit(prompts[0], 10, **sampling)
    for _ in range(steps + 2):
        eng.step()
    assert can_migrate(eng, req)
    return eng, req, snapshot_request(eng, req)


def test_corrupt_and_torn_blobs_rejected_loudly(served, prompts):
    eng, req, blob = _live_blob(served, prompts)
    try:
        flipped = bytearray(blob)
        flipped[len(flipped) // 2] ^= 0xFF
        with pytest.raises(TransferError, match="CRC32 mismatch"):
            kvt._unpack(bytes(flipped))
        with pytest.raises(TransferError, match="torn frame"):
            kvt._unpack(blob[:len(blob) // 2])
        with pytest.raises(TransferError, match="bad magic"):
            kvt._unpack(b"NOPE" + blob)
        # header survives a full CRC walk on the intact blob
        hdr = blob_info(blob)
        assert hdr["rid"] == req.rid and hdr["kind"] == "request"
        assert hdr["position"] == int(req.prompt.size) + \
            len(req.tokens) - 1
    finally:
        eng.cancel(req.rid)
        eng.run(max_iterations=50)
        eng.close()


def test_snapshot_carries_effective_sampling_operands(served, prompts):
    eng, req, blob = _live_blob(served, prompts, temperature=0.8,
                                top_k=4, seed=123)
    try:
        hdr = blob_info(blob)
        assert hdr["temperature"] == pytest.approx(0.8)
        assert hdr["top_k"] == 4 and hdr["seed"] == 123
    finally:
        eng.cancel(req.rid)
        eng.run(max_iterations=50)
        eng.close()


def test_receiver_verify_hook_refuses(served, prompts):
    eng, req, blob = _live_blob(served, prompts)
    ex, model = served
    recv = InferenceEngine(ex, model, **EKW)
    try:
        before = recv.cache.audit()["page_allocs"]
        with pytest.raises(TransferError, match="verify hook"):
            resume_request(recv, blob, verify=lambda h, a: False)

        def explode(h, a):
            raise RuntimeError("stale shard")
        with pytest.raises(TransferError, match="stale shard"):
            resume_request(recv, blob, verify=explode)
        # both refusals left the receiver pool untouched
        assert recv.cache.audit()["page_allocs"] == before
    finally:
        eng.cancel(req.rid)
        eng.run(max_iterations=50)
        eng.close()
        recv.close()


def test_donor_frees_only_after_receiver_ack(served, prompts):
    """Snapshot → splice → ONLY THEN donor ack: the donor's pages stay
    live (replay still possible) until the receiver owns the stream."""
    ex, model = served
    base = _oracle(served, prompts, None, "greedy")
    donor = InferenceEngine(ex, model, **EKW)
    recv = InferenceEngine(ex, model, **EKW)
    try:
        req = donor.submit(prompts[0], 10)
        for _ in range(6):
            donor.step()
        blob = snapshot_request(donor, req)
        adopted = resume_request(recv, blob)
        # receiver owns a live copy; the donor side is still intact
        assert adopted.rid == req.rid
        assert donor.cache.audit()["pages_in_use"] > 0
        assert not req.finished
        # ack: donor retires its attempt without touching the stream
        assert donor.release_migrated(req.rid) is True
        assert donor.cache.audit()["pages_in_use"] == 0
        recv.run(max_iterations=200)
        assert list(map(int, adopted.result())) == base[0]
    finally:
        donor.close()
        recv.close()


# -- fleet paths × sampling × pool dtype: bitwise continuation ---------------

def _run_path(fleet, prompts, mode, path):
    sampling = SAMPLING[mode]
    reqs = [fleet.submit(p, 10, **sampling) for p in prompts]
    if path == "handoff":
        fleet.wait(reqs)
        return reqs
    fleet.pump(4)
    if path == "crash":
        victim = fleet._by_name(reqs[0].engine)
        faults.crash_engine(victim.engine)
    elif path == "rebalance":
        src = max(fleet._replicas, key=lambda r: len(r.inflight))
        assert fleet.rebalance(src.name, max_requests=2) >= 1
    elif path == "drain":
        busy = max(fleet._replicas, key=lambda r: len(r.inflight))
        fleet.drain(busy.name, wait=False, migrate=True)
    fleet.wait(reqs)
    return reqs


@pytest.mark.parametrize("kv", [None, "int8"])
@pytest.mark.parametrize("mode", ["greedy", "sampled"])
@pytest.mark.parametrize("path", ["crash", "rebalance", "drain",
                                  "handoff"])
def test_migrated_streams_bitwise_identical(served, prompts, kv, mode,
                                            path):
    base = _oracle(served, prompts, kv, mode)
    roles = ("prefill", "decode", "decode") if path == "handoff" \
        else None
    with _quiet():
        fleet = _fleet(served, kv=kv, roles=roles)
        try:
            reqs = _run_path(fleet, prompts, mode, path)
            got = [list(map(int, r.result())) for r in reqs]
            assert got == base
            st = fleet.stats()
            assert st["migrations"] >= 1, (path, st)
            if path == "handoff":
                assert all(r.engines[0] == "e0" for r in reqs)
                assert all(r.engine in ("e1", "e2") for r in reqs)
        finally:
            _close_balanced(fleet)


@pytest.mark.parametrize("fault", ["drop", "corrupt", "tear"])
def test_transfer_faults_fall_back_to_replay_bitwise(served, prompts,
                                                     fault):
    """Every injected wire fault is survived by the replay oracle:
    same accepted rids, same bitwise streams, balanced audits, and a
    ``migrate_failed`` incident on the books."""
    base = _oracle(served, prompts, None, "greedy")
    inject = {"drop": faults.drop_transfer,
              "corrupt": faults.corrupt_transfer,
              "tear": faults.tear_transfer}[fault]
    with _quiet():
        fleet = _fleet(served)
        try:
            # fault EVERY transfer this fleet attempts.  Chaining
            # semantics differ: a drop short-circuits the outer
            # counters (stack all at=0 so each transfer meets the next
            # still-armed wrapper); corrupt/tear pass bytes through the
            # whole chain (distinct at= — and an even number of same-
            # byte XOR flips would cancel out)
            for i in range(len(prompts)):
                inject(fleet, at=0 if fault == "drop" else i)
            reqs = [fleet.submit(p, 10) for p in prompts]
            fleet.pump(4)
            victim = fleet._by_name(reqs[0].engine)
            faults.crash_engine(victim.engine)
            fleet.wait(reqs)
            got = [list(map(int, r.result())) for r in reqs]
            assert got == base
            st = fleet.stats()
            assert st["migrations"] == 0, st
            assert st["migration_failures"] >= 1, st
            assert st["failovers"] >= 1, st
            assert all(r.finish_reason in ("eos", "max_new")
                       for r in reqs)
        finally:
            _close_balanced(fleet)


def test_prefix_cache_survives_replica_crash(served, prompts):
    """PR 15 residual: the quarantined replica's interned prefix pages
    are re-interned on a sibling, so the warm prefix outlives the
    replica that built it."""
    ex, model = served
    ekw = dict(EKW, prefix_cache=True)
    warm = np.arange(1, 9, dtype=np.int32)      # 8 tokens, 1 page
    with _quiet():
        fleet = _fleet(served, n=2, engine_kwargs=ekw)
        try:
            r0 = fleet.submit(warm, 4)
            fleet.wait([r0])
            victim = fleet._by_name(r0.engine)
            other = next(r for r in fleet._replicas if r is not victim)
            assert victim.engine.prefix_cache.hit_tokens(warm) >= 4
            assert other.engine.prefix_cache.hit_tokens(warm) == 0
            # crash the warm replica mid-flight; supervision re-interns
            reqs = [fleet.submit(p, 10) for p in prompts]
            fleet.pump(2)
            faults.crash_engine(victim.engine)
            fleet.wait(reqs)
            assert fleet.prefix_handoffs_done >= 1
            assert other.engine.prefix_cache.hit_tokens(warm) >= 4
        finally:
            _close_balanced(fleet)


# -- satellite: dispatch-wedge watcher for manual pump() fleets --------------

@pytest.mark.timeout(120)
def test_pump_fleet_quarantines_wedge_inside_dispatch(served, prompts,
                                                      tmp_path):
    """The deadline is armed BEFORE the tick: a step that wedges inside
    the dispatch is quarantined by the watcher thread while the pumping
    caller is still stuck, failed over bitwise, and the incident is
    tagged mode="dispatch" (post-hoc stall detection must not fire a
    second wedge for the same tick)."""
    base = _oracle(served, prompts, None, "greedy")
    telemetry.enable(incident_dir=str(tmp_path))
    fl = telemetry.get_flight()
    fl.clear()
    try:
        with _quiet():
            fleet = _fleet(served, n=2, wedge_timeout=0.25,
                           breaker_base=0.01)
            try:
                reqs = [fleet.submit(p, 10) for p in prompts]
                fleet.pump(2)
                victim = fleet._by_name(reqs[0].engine)
                faults.wedge_engine(victim.engine, 1.2)
                fleet.wait(reqs, timeout=60)
                got = [list(map(int, r.result())) for r in reqs]
                assert got == base
                assert fleet.stats()["failovers"] >= 1
                wedges = [e for e in fl.incidents()
                          if e["kind"] == "engine_wedge"]
                assert len(wedges) == 1, wedges
                dump = fl.load_dump(wedges[0]["path"])
                assert dump["extra"]["mode"] == "dispatch"
                assert dump["extra"]["engine"] == victim.name
            finally:
                fleet.stop()
                for r in fleet._replicas:
                    if r.engine is not None:
                        r.engine.close()
    finally:
        telemetry.disable()
        fl.clear()


def test_can_migrate_excludes_the_unmigratable(served, prompts):
    ex, model = served
    eng = InferenceEngine(ex, model, **EKW)
    try:
        req = eng.submit(prompts[0], 10)
        assert not can_migrate(eng, req)      # queued/prefilling: no
        for _ in range(4):
            eng.step()
        assert can_migrate(eng, req)
        # replaying requests already delivered their remainder —
        # re-emitting would break exactly-once
        replayed = eng.submit(prompts[1], 10,
                              replay=np.arange(1, 9, dtype=np.int32))
        for _ in range(3):
            eng.step()
        if not replayed.finished and replayed.slot is not None \
                and replayed.replaying:
            assert not can_migrate(eng, replayed)
        eng.run(max_iterations=300)
        assert not can_migrate(eng, req)      # finished: no
    finally:
        eng.close()
