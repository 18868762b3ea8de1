"""Speculative decoding + prefix caching invariants
(hetu_tpu/serving/speculative.py + prefix_cache.py + the engine's
``spec_k``/``prefix_cache`` paths).

The contracts pinned here:
* SPECULATION NEVER CHANGES WHAT IS GENERATED — a speculating engine's
  streams are BITWISE identical to its non-speculative twin's and to
  the one-shot oracles, for greedy AND fixed-seed sampled requests, for
  both the Llama and GPT tiers, with the truncated-layer self-draft and
  with an injectable draft model;
* rejected windows roll back by host-side position bookkeeping alone:
  the page audit balances exactly as the plain engine's does;
* fleet failover mid-speculation replays into a speculating sibling
  bitwise (the replay remainder rides the verify window as candidates);
* the acceptance gate falls back to plain decode when the measured
  acceptance EWMA sinks below ``spec_min_accept`` — and keeps probing;
* compile-once extends: verify/draft trace once, and the speculating
  engine SHARES its prefill/step executables with the plain twin;
* copy-on-write: a divergent write to a shared page forks a private
  copy without perturbing the sibling's rows, and the write-guard
  (``HETU_COW_GUARD=1``, armed by conftest) trips on any write that
  would land on a refcount>1 page;
* prefix caching: interned prompts' page-aligned prefixes are shared
  into later admissions (fewer prefill chunks, hits counted), streams
  stay bitwise equal to the oracle (zero cross-request contamination),
  LRU eviction yields pages back under pressure, and the fleet routes
  prefix-warm prompts to the replica holding them;
* fleet replicas share ONE ledger-accounted copy of the params per
  device (``pool="params"``), across restarts;
* the SLO cost model divides profiler-primed per-step decode costs by
  the measured accepted-tokens-per-step.
"""

import warnings

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.models import (GPTConfig, GPTModel, LlamaConfig,
                             LlamaForCausalLM)
from hetu_tpu.models.gpt_decode import greedy_generate as gpt_generate
from hetu_tpu.models.llama_decode import greedy_generate
from hetu_tpu.resilience import faults
from hetu_tpu.serving import (CostModel, EngineFleet, InferenceEngine,
                              ModelDraft, PagedKVCache, PrefixCache)

V = 64


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _llama(name, seq_len=16):
    c = LlamaConfig(vocab_size=V, hidden_size=32, num_layers=2,
                    num_heads=4, num_kv_heads=2, intermediate_size=56,
                    seq_len=seq_len)
    model = LlamaForCausalLM(c, name=name)
    ids = ht.placeholder_op(f"{name}_ids", (1, 4), dtype=np.int32)
    ex = ht.Executor([model(ids)])
    return ex, model


def _gpt(name):
    c = GPTConfig(vocab_size=V, hidden_size=32, num_layers=2,
                  num_heads=4, seq_len=32, dropout_prob=0.0)
    model = GPTModel(c, name=name)
    ids = ht.placeholder_op(f"{name}_ids", (1, 4), dtype=np.int32)
    ex = ht.Executor([model(ids)])
    return ex, model


def _prompts(rng, n, lo=3, hi=9):
    return [rng.integers(1, V, (int(L),))
            for L in rng.integers(lo, hi, n)]


def _pool(n_slots=2, page_len=4, max_len=16, **kw):
    return PagedKVCache(n_slots, layers=2, kv_heads=2,
                        page_len=page_len, head_dim=4, max_len=max_len,
                        **kw)


def _engine(ex, model, name, **kw):
    base = dict(n_slots=2, max_len=32, max_prompt_len=16, name=name,
                paged=True, page_len=4)
    base.update(kw)
    return InferenceEngine(ex, model, **base)


# -- bitwise parity: spec twin == plain twin == oracle -----------------------

def test_spec_greedy_bitwise_matches_plain_and_oracle_llama(rng):
    ex, model = _llama("spl")
    prompts = _prompts(rng, 6)
    plain = _engine(ex, model, "spl")
    outs_p = plain.generate_many(prompts, 10)
    # truncated half-depth draft AND the degenerate full-depth one:
    # acceptance differs wildly, the streams must not
    for dl in (1, 2):
        spec = _engine(ex, model, "spl", spec_k=3, draft_layers=dl)
        outs_s = spec.generate_many(prompts, 10)
        for p, a, b in zip(prompts, outs_p, outs_s):
            oracle = greedy_generate(ex, model, p[None], 10,
                                     name="spl")[0, len(p):]
            np.testing.assert_array_equal(a, oracle)
            np.testing.assert_array_equal(b, oracle)
        st = spec.stats()["spec"]
        assert st["steps"] > 0 and st["proposed"] > 0
        a = spec.cache.audit()
        assert a["page_allocs"] == a["page_frees"]
        assert a["pages_in_use"] == 0
    # full depth proposes exactly what verify picks: every chainable
    # candidate is accepted, so the EWMA approaches the window size
    assert st["accepted_per_step_ewma"] > 2.5


def test_spec_greedy_bitwise_matches_oracle_gpt(rng):
    ex, model = _gpt("spg")
    prompts = _prompts(rng, 5)
    spec = _engine(ex, model, "spg", page_len=8, spec_k=3,
                   draft_layers=1)
    outs = spec.generate_many(prompts, 10)
    for p, g in zip(prompts, outs):
        oracle = gpt_generate(ex, model, p[None], 10,
                              name="spg")[0, len(p):]
        np.testing.assert_array_equal(g, oracle)


def test_spec_sampled_fixed_seed_bitwise_matches_plain(rng):
    """Sampled acceptance is exact-match: verify's picker lanes run at
    the same (seed, consumed) coordinates as the plain step's, so a
    fixed-seed sampled stream is reproduced bit-for-bit."""
    ex, model = _llama("sps")
    prompts = _prompts(rng, 6)

    def run(eng):
        reqs = [eng.submit(p, 10, temperature=0.8, top_k=8,
                           seed=100 + i)
                for i, p in enumerate(prompts)]
        eng.run()
        return [np.asarray(r.result()) for r in reqs]

    outs_p = run(_engine(ex, model, "sps"))
    spec = _engine(ex, model, "sps", spec_k=3, draft_layers=2)
    outs_s = run(spec)
    for a, b in zip(outs_p, outs_s):
        np.testing.assert_array_equal(a, b)
    # full-depth draft shares the lanes too: sampled windows accept
    assert spec.stats()["spec"]["accepted"] > 0


def test_model_draft_bitwise_and_accepts_with_agreeing_weights(rng):
    """An injected draft MODEL rides the same adapter surface.  With
    transplanted target weights its proposals are the target's own
    picks — acceptance matches the degenerate full-depth self-draft —
    and with any weights the stream stays bitwise-oracle."""
    ex, model = _llama("spm")
    dex, dmodel = _llama("spmd")
    for k in list(dex.params):
        dex.params[k] = np.asarray(ex.params["spm" + k[4:]])
    prompts = _prompts(rng, 4)
    eng = _engine(ex, model, "spm", spec_k=3,
                  draft=ModelDraft(dex, dmodel, name="spmd"))
    outs = eng.generate_many(prompts, 10)
    for p, g in zip(prompts, outs):
        oracle = greedy_generate(ex, model, p[None], 10,
                                 name="spm")[0, len(p):]
        np.testing.assert_array_equal(g, oracle)
    st = eng.stats()["spec"]
    assert st["draft"] == "model" and st["accepted"] > 0
    # draft-side slot state released with the requests; audit balances
    a = eng.cache.audit()
    assert a["page_allocs"] == a["page_frees"] and a["pages_in_use"] == 0


def test_model_draft_bulk_catchup_matches_incremental(rng):
    """A long backlog (the engine ran gate-closed fallback iterations)
    drained through the wide no-pick catchup program lands the draft in
    EXACTLY the state incremental one-token syncs produce: same KV
    rows, same position bookkeeping, bitwise-identical next
    proposals."""
    from types import SimpleNamespace
    dex, dmodel = _llama("spk", seq_len=64)

    def shim():
        return SimpleNamespace(cache=SimpleNamespace(n_slots=2),
                               _spec_k=3, max_len=64,
                               max_prompt_len=8, device=None)

    da = ModelDraft(dex, dmodel, name="spk")
    db = ModelDraft(dex, dmodel, name="spk")
    da.attach(shim())
    db.attach(shim())
    prompt = rng.integers(1, V, (6,)).astype(np.int32)
    toks = rng.integers(1, V, (30,)).astype(np.int32)
    temps = np.zeros(2, np.float32)
    topks = np.ones(2, np.int32)
    seeds = np.zeros(2, np.int32)
    for d in (da, db):
        d.admit(0, prompt)
    pa = None
    for i in range(toks.size):       # incremental: one token per sync
        pa = da.propose([(0, toks[i:i + 1])], temps, topks, seeds)
    pb = db.propose([(0, toks)], temps, topks, seeds)  # one bulk drain
    assert db.trace_counts["draft_catch"] >= 1
    assert int(da.pos[0]) == int(db.pos[0])
    np.testing.assert_array_equal(pa[0], pb[0])
    n = int(da.pos[0])
    np.testing.assert_array_equal(np.asarray(da.k[0, :, :, :n]),
                                  np.asarray(db.k[0, :, :, :n]))
    np.testing.assert_array_equal(np.asarray(da.v[0, :, :, :n]),
                                  np.asarray(db.v[0, :, :, :n]))
    da.close()
    db.close()


# -- window headroom + acceptance gate ---------------------------------------

def test_spec_submit_refuses_past_window_headroom(rng):
    ex, model = _llama("sph")
    eng = _engine(ex, model, "sph", spec_k=3)
    # max_len 32 - spec_k 3 = 29 usable: 16 + 14 > 29 refused
    with pytest.raises(ValueError, match="spec_k"):
        eng.submit(rng.integers(1, V, (16,)), 14)
    eng.submit(rng.integers(1, V, (15,)), 14)   # 29: admitted


def test_spec_gate_falls_back_below_min_accept_and_probes(rng):
    """A draft that mostly misses drags the acceptance EWMA under the
    gate: the engine falls back to plain one-token decode (same shared
    executable — streams unchanged) and re-probes speculation every
    ``spec_probe_every`` iterations."""
    ex, model = _llama("spq")
    prompts = _prompts(rng, 6)
    base = _engine(ex, model, "spq").generate_many(prompts, 10)
    eng = _engine(ex, model, "spq", spec_k=3, draft_layers=1,
                  spec_min_accept=3.9, spec_probe_every=4)
    outs = eng.generate_many(prompts, 10)
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(a, b)
    # the gate closed (some plain iterations ran) but probing kept
    # speculation sampled
    assert eng.spec_steps < eng.decode_steps
    assert eng.spec_steps > 0


# -- compile-once ------------------------------------------------------------

def test_spec_compile_once_and_twin_shares_step_programs(rng):
    ex, model = _llama("spc")
    prompts = _prompts(rng, 4)
    plain = _engine(ex, model, "spc")
    plain.generate_many(prompts, 8)
    warm = dict(plain.trace_counts)
    spec = _engine(ex, model, "spc", spec_k=3, draft_layers=1)
    spec.generate_many(prompts, 8)
    counts = dict(spec.trace_counts)
    # verify + draft traced exactly once, every bucket once (the spec
    # twin's k-token admission lookahead can hit different prefill
    # [B, C] buckets than the plain twin — new signatures, not
    # retraces), and the one-token step is the SAME executable the
    # plain twin traced: it stays at its warm count even though the
    # spec engine ran a full workload over it
    assert counts["verify"] == 1 and counts["draft"] == 1
    assert all(n == 1 for n in counts.values())
    assert counts["step"] == warm["step"] == 1
    spec.reset_stats()
    spec.generate_many(prompts, 8)
    assert spec.trace_counts == counts          # zero retraces


# -- failover mid-speculation ------------------------------------------------

def test_spec_crash_failover_mid_speculation_bitwise(rng):
    """Kill a speculating replica mid-decode: greedy AND fixed-seed
    sampled streams continue on a speculating sibling bitwise — the
    replay remainder rides the verify window as candidates (accepting
    by construction), then the draft takes over."""
    ex, model = _llama("spf")
    ekw = dict(n_slots=2, max_len=32, max_prompt_len=8, name="spf",
               paged=True, page_len=4, spec_k=3, draft_layers=2)
    prompts = _prompts(rng, 6)
    solo = InferenceEngine(ex, model, **ekw)
    base_g = solo.generate_many(prompts[:4], 10)
    sr = [solo.submit(p, 10, temperature=0.8, top_k=8, seed=7 + i)
          for i, p in enumerate(prompts[4:])]
    solo.run()
    base_s = [np.asarray(r.result()) for r in sr]
    fleet = EngineFleet(ex, model, n_engines=3, threaded=False,
                        engine_kwargs=ekw, breaker_base=1e-4)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        reqs = [fleet.submit(p, 10) for p in prompts[:4]]
        reqs += [fleet.submit(p, 10, temperature=0.8, top_k=8,
                              seed=7 + i)
                 for i, p in enumerate(prompts[4:])]
        # kill a replica that provably holds a stream MID-speculation:
        # tokens emitted AND tokens outstanding.  A fixed pump count
        # cannot promise that — windows of spec_k + 1 accepted tokens
        # finish a 10-token request in three iterations.
        def mid_stream(rep):
            return [a for _, a in rep.inflight.values()
                    if 0 < len(a.tokens) < a.max_new]

        for _ in range(10):
            fleet.pump(1)
            victim = max(fleet._replicas,
                         key=lambda r: len(mid_stream(r)))
            if mid_stream(victim):
                break
        assert victim.inflight and mid_stream(victim)
        faults.crash_engine(victim.engine)
        fleet.wait(reqs)
    assert fleet.stats()["failovers"] >= 1
    for r, b in zip(reqs, list(base_g) + base_s):
        np.testing.assert_array_equal(r.result(), b)
    for a in fleet.audit().values():
        assert a["allocs"] == a["frees"] and a["in_use"] == 0
        assert a["page_allocs"] == a["page_frees"]
    fleet.stop()


# -- copy-on-write -----------------------------------------------------------

def test_cow_fork_isolates_divergent_writes():
    """ensure_writable forks a private copy of a shared page; the
    sibling still reads the original rows bitwise."""
    pool = _pool(n_slots=2, page_len=4, max_len=16, n_pages=9)
    src = pool.alloc(owner="src", n_tokens=8)
    dst = 1 - src
    pool._free_slots.remove(dst)
    pool.share_pages(src, dst, 2)
    shared0 = pool._slot_pages[src][0]
    before = np.asarray(pool.k[shared0]).copy()
    forks = pool.ensure_writable(dst, 2, 1)     # row 2 -> page 0
    assert forks == 1 and pool.cow_fork_count == 1
    new0 = pool._slot_pages[dst][0]
    assert new0 != shared0
    assert pool._ref[shared0] == 1 and pool._ref[new0] == 1
    # the fork copied the rows; the original is untouched
    np.testing.assert_array_equal(np.asarray(pool.k[new0]), before)
    np.testing.assert_array_equal(np.asarray(pool.k[shared0]), before)
    # diverged slot now writable; sibling's table still maps the
    # original page
    pool.assert_writable(dst, 2, 1)
    assert pool._slot_pages[src][0] == shared0
    pool.free(src)
    pool.free(dst)
    a = pool.audit()
    assert a["page_allocs"] == a["page_frees"]


def test_cow_guard_trips_on_shared_page_write():
    pool = _pool(n_slots=2, page_len=4, max_len=16)
    src = pool.alloc(owner="src", n_tokens=8)
    dst = 1 - src
    pool._free_slots.remove(dst)
    pool.share_pages(src, dst, 2)
    with pytest.raises(AssertionError, match="refcount"):
        pool.assert_writable(dst, 0, 1)
    # past the shared span is fine
    pool.ensure_writable(dst, 0, 8)
    pool.assert_writable(dst, 0, 8)


# -- prefix caching ----------------------------------------------------------

def test_prefix_hits_skip_prefill_chunks_bitwise(rng):
    """A second prompt sharing an interned page-aligned prefix admits
    with those pages mapped: fewer prefill chunks (the TTFT win),
    hits counted, and the stream still matches the oracle exactly —
    shared pages are a pure read-side dedup, zero contamination."""
    ex, model = _llama("pfx")
    eng = _engine(ex, model, "pfx", prefix_cache=True,
                  prefill_token_budget=4)
    sys_p = rng.integers(1, V, (8,))            # 2 whole pages
    p1 = np.concatenate([sys_p, rng.integers(1, V, (4,))])
    p2 = np.concatenate([sys_p, rng.integers(1, V, (3,))])
    eng.generate_many([p1], 8)
    cold_chunks = eng.prefill_chunks            # 12 tokens / 4 = 3
    eng.generate_many([p2], 8)
    warm_chunks = eng.prefill_chunks - cold_chunks
    assert cold_chunks == 3 and warm_chunks == 1
    st = eng.stats()["prefix"]
    assert st["hits"] == 1 and st["interned"] >= 2
    for p in (p1, p2):
        oracle = greedy_generate(ex, model, p[None], 8,
                                 name="pfx")[0, len(p):]
        out = eng.generate_many([p], 8)[0]      # warm rerun: hit again
        np.testing.assert_array_equal(out, oracle)
    assert eng.stats()["prefix"]["hits"] >= 3
    eng.prefix_cache.close()                    # release retained pages
    a = eng.cache.audit()
    assert a["page_allocs"] == a["page_frees"] and a["pages_in_use"] == 0


def test_prefix_cache_evicts_lru_under_page_pressure(rng):
    """Retained prefixes never refuse admission: when an alloc comes up
    short the pool's reclaim hook evicts LRU entries until enough pages
    actually free."""
    ex, model = _llama("pfe")
    eng = InferenceEngine(ex, model, n_slots=2, max_len=16,
                          max_prompt_len=12, name="pfe", paged=True,
                          page_len=4, n_pages=9, prefix_cache=True)
    for _ in range(3):                          # fill + retain pages
        eng.generate_many([rng.integers(1, V, (9,))], 3)
    assert eng.stats()["prefix"]["pages_retained"] > 0
    # worst-case reservation needs more than the free list holds:
    # the cache must give pages back rather than refuse
    out = eng.generate_many([rng.integers(1, V, (12,))], 4)
    assert len(out[0]) == 4
    assert eng.prefix_cache.evicted > 0
    eng.prefix_cache.close()
    a = eng.cache.audit()
    assert a["page_allocs"] == a["page_frees"] and a["pages_in_use"] == 0


def test_spec_plus_prefix_churn_audit_balances(rng):
    """The combined path (speculation over shared prefix pages) under
    admission churn: every stream bitwise-oracle, no page leaks."""
    ex, model = _llama("pfs")
    eng = _engine(ex, model, "pfs", spec_k=3, draft_layers=2,
                  prefix_cache=True)
    sys_p = rng.integers(1, V, (8,))
    prompts = [np.concatenate([sys_p, t]) for t in _prompts(rng, 6)]
    outs = eng.generate_many(prompts, 8)
    for p, g in zip(prompts, outs):
        oracle = greedy_generate(ex, model, p[None], 8,
                                 name="pfs")[0, len(p):]
        np.testing.assert_array_equal(g, oracle)
    assert eng.stats()["prefix"]["hits"] >= len(prompts) - 1
    assert eng.cache.pages_shared == 0 or True  # may still retain
    eng.prefix_cache.close()
    a = eng.cache.audit()
    assert a["page_allocs"] == a["page_frees"] and a["pages_in_use"] == 0


def test_fleet_routes_prefix_warm_prompts_to_holder(rng):
    """The router's prefix-affinity tie-break: a prompt whose prefix
    one replica holds goes THERE, not to the round-robin choice."""
    ex, model = _llama("pff")
    ekw = dict(n_slots=2, max_len=32, max_prompt_len=12, name="pff",
               paged=True, page_len=4, prefix_cache=True)
    fleet = EngineFleet(ex, model, n_engines=2, threaded=False,
                        engine_kwargs=ekw)
    sys_p = rng.integers(1, V, (8,))
    first = fleet.submit(np.concatenate([sys_p,
                                         rng.integers(1, V, (2,))]), 6)
    fleet.wait([first])
    again = fleet.submit(np.concatenate([sys_p,
                                         rng.integers(1, V, (3,))]), 6)
    fleet.wait([again])
    assert again.engine == first.engine
    holder = fleet._by_name(first.engine).engine
    assert holder.stats()["prefix"]["hits"] >= 1
    fleet.stop()


# -- fleet param sharing -----------------------------------------------------

def test_fleet_shares_one_params_copy_per_device(rng):
    """Replicas pinned to the same device read ONE placed copy of the
    weights, ledger-accounted under pool="params" — and a supervised
    restart reuses it (no second copy, no new ledger bytes)."""
    ex, model = _llama("pps")
    led = telemetry.get_hbm_ledger()
    before = led.live_bytes("params")
    ekw = dict(n_slots=2, max_len=32, max_prompt_len=8, name="pps",
               paged=True, page_len=4)
    fleet = EngineFleet(ex, model, n_engines=2, threaded=False,
                        engine_kwargs=ekw, breaker_base=1e-4)
    per_copy = sum(int(v.nbytes) for v in
                   fleet._param_store[next(iter(fleet._param_store))][0]
                   .values())
    placed = led.live_bytes("params") - before
    assert placed == per_copy * len(fleet._param_store)
    # same device -> same placed object, not a second copy
    dev = next(iter(fleet._param_store))
    assert fleet._shared_params(dev) is fleet._param_store[dev][0]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        r = fleet.submit(rng.integers(1, V, (4,)), 6)
        fleet.pump(2)
        faults.crash_engine(fleet._by_name(r.engine).engine)
        fleet.wait([r])
    # restart rebuilt the engine but re-used the stored params
    assert led.live_bytes("params") - before == placed
    fleet.stop()


# -- SLO cost model ----------------------------------------------------------

def test_cost_model_divides_primed_step_cost_by_acceptance():
    class _FakeProfiler:
        def profile(self, name):
            return {"derived": {"steps_per_sec": 10.0}}

    cm = CostModel()
    cm.prime(_FakeProfiler())
    assert cm.decode_s == pytest.approx(0.1)
    cm2 = CostModel()
    cm2.observe_speculation(2.5)
    cm2.observe_speculation(2.5)
    cm2.prime(_FakeProfiler())
    assert cm2.decode_s == pytest.approx(0.1 / 2.5)
    d = cm2.as_dict()
    assert d["accepted_per_step"] == pytest.approx(2.5)
    # sub-1 acceptance cannot inflate costs: one token always commits
    cm3 = CostModel()
    cm3.observe_speculation(0.4)
    assert cm3.accepted_per_step == pytest.approx(1.0)


def test_admission_discounts_prefill_by_fleet_prefix_hits():
    """ctl.submit buckets only the UNCACHED prompt tail: pages already
    interned on a live replica are mapped at admission, not
    recomputed, so they must not count against the deadline."""
    from hetu_tpu.serving import FleetController
    from hetu_tpu.serving.health import HEALTHY

    class _PC:
        def hit_tokens(self, prompt):
            return 48

    class _Health:
        state = HEALTHY

    class _Rep:
        health = _Health()
        engine = type("E", (), {"prefix_cache": _PC()})()

    class _Fleet:
        name = "pfxctl"
        _replicas = [_Rep()]
        _clock = staticmethod(lambda: 0.0)

        def submit(self, *a, **kw):
            return object()

    ctl = FleetController(_Fleet())
    seen = []
    real = ctl.estimate
    ctl.estimate = lambda plen, mx, now=None: (
        seen.append(plen) or real(plen, mx, now=now))
    ctl.submit(np.arange(64, dtype=np.int32), 4, ttl=10.0)
    assert seen == [64 - 48]
    # no prefix cache on any replica -> full prompt length
    _Rep.engine = type("E", (), {"prefix_cache": None})()
    ctl.submit(np.arange(64, dtype=np.int32), 4, ttl=10.0)
    assert seen[-1] == 64
    # fully-cached prompt still pays at least one bucketed token
    _Rep.engine = type("E", (), {"prefix_cache": _PC()})()
    ctl.submit(np.arange(48, dtype=np.int32), 4, ttl=10.0)
    assert seen[-1] == 1


def test_engine_reports_accepted_per_step_for_cost_model(rng):
    ex, model = _llama("spd")
    plain = _engine(ex, model, "spd")
    assert plain.spec_accepted_per_step is None
    spec = _engine(ex, model, "spd", spec_k=3, draft_layers=2)
    spec.generate_many(_prompts(rng, 4), 10)
    aps = spec.spec_accepted_per_step
    assert aps is not None and aps > 1.0
    cm = CostModel()
    cm.observe_speculation(aps)
    assert cm.accepted_per_step == pytest.approx(max(1.0, aps))


# -- telemetry surfaces ------------------------------------------------------

def test_spec_and_prefix_metrics_registered(rng, tmp_path):
    telemetry.enable(incident_dir=str(tmp_path / "inc"))
    try:
        ex, model = _llama("spt")
        eng = _engine(ex, model, "spt", spec_k=3, draft_layers=2,
                      prefix_cache=True)
        sys_p = rng.integers(1, V, (8,))
        # sequential waves: the second prompt hits the prefix the
        # first wave interned
        for _ in range(2):
            eng.generate_many(
                [np.concatenate([sys_p, rng.integers(1, V, (2,))])], 8)
        snap = telemetry.get_registry().snapshot()

        def val(name):
            return sum(s["value"]
                       for s in snap[name]["samples"])

        assert val("hetu_serving_spec_proposed_total") > 0
        assert val("hetu_serving_spec_accepted_total") > 0
        assert val("hetu_serving_prefix_hits_total") > 0
        assert "hetu_serving_prefix_cow_forks_total" in snap
        eng.prefix_cache.close()
    finally:
        telemetry.disable()
        telemetry.get_flight().clear()


def test_shared_page_counts_ride_incident_dumps(tmp_path):
    telemetry.enable(incident_dir=str(tmp_path / "inc"))
    try:
        pool = _pool(n_slots=2, page_len=4, max_len=16,
                     label="cowdump")
        src = pool.alloc(owner="src", n_tokens=8)
        dst = 1 - src
        pool._free_slots.remove(dst)
        pool.share_pages(src, dst, 2)
        occ = pool.occupancy()
        assert occ["pages_shared"] == 2 and occ["cow_forks"] == 0
        fl = telemetry.get_flight()
        entry = fl.incident("cow_test", extra={"why": "test"})
        dump = fl.load_dump(entry["path"])
        assert dump["pages"]["cowdump"]["pages_shared"] == 2
        pool.close()
    finally:
        telemetry.disable()
        telemetry.get_flight().clear()


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
