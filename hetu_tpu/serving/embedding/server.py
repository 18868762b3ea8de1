"""EmbeddingServer: batched CTR scoring behind the serving lifecycle.

One embedding request = one example's sparse feature ids (``[F]``
int32, stored where an LLM request stores its prompt) plus optional
dense features; it completes in a SINGLE scheduler iteration — admit,
one batched tier lookup, one jitted score step, retire with
``finish_reason="scored"``.  That makes embedding traffic the
microsecond-scale stress test of the serving lifecycle: the server
reuses the REAL :class:`~..scheduler.Scheduler` (not a clone), so
bounded-queue admission (typed ``EngineOverloaded``), TTL/deadlines at
admission and mid-flight, ``cancel()``, shed policies, rid scoping, and
the queue-depth telemetry all behave exactly as they do for LLM
requests — and ``EngineFleet(engine_factory=EmbeddingServer)`` routes,
health-checks, and fails embedding traffic over unchanged (a harvested
embedding request re-homes with an empty replay: nothing was delivered,
the sibling just scores it).

The scoring program is the engine pattern re-hosted: exactly ONE jitted
program per (model, shape) signature, shared process-wide
(compile-once; ``trace_counts`` is the witness), computing an in-graph
per-slot finiteness sentinel so the watchdog is a host-side decision
over the same executable.  Cached mode gathers rows from the
:class:`~.hot_cache.DeviceHotRowCache` via the ``packed_lookup`` pallas
path (ids are cache slots); ``cache_rows=None`` builds the UNCACHED
host-tier twin — every batch gathers its rows on the host and ships
them up, the DLRM-inference bottleneck the bench quantifies against.
"""

from __future__ import annotations

import time
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from ... import telemetry as _telemetry
from ...models.ctr import make_wdl_scorer
from ...ops.pallas.sparse_densify import packed_lookup
from ...ps.store import EmbeddingTable
from ..scheduler import Request, Scheduler
from .hot_cache import DeviceHotRowCache, EMBED_BUCKETS, as_host_tier


class BatchSlotPool:
    """Slot pool for batch seats (the SlotKVCache alloc/free surface
    without the K/V arrays): one in-flight embedding request owns one
    seat of the fixed ``[n_slots, F]`` scoring batch.  Reusing the
    exact surface lets the serving :class:`~..scheduler.Scheduler`
    drive admission unchanged."""

    def __init__(self, n_slots):
        if n_slots < 1:
            raise ValueError(f"n_slots must be >= 1, got {n_slots}")
        self.n_slots = int(n_slots)
        self._free = list(range(self.n_slots - 1, -1, -1))
        self._owner = [None] * self.n_slots
        self.alloc_count = 0
        self.free_count = 0

    @property
    def n_free(self):
        return len(self._free)

    @property
    def n_active(self):
        return self.n_slots - len(self._free)

    def alloc(self, owner=None, n_tokens=None):
        # ``n_tokens`` (worst-case token span) is a KV-pool concern the
        # scheduler passes uniformly; batch seats have no token axis
        del n_tokens
        if not self._free:
            return None
        slot = self._free.pop()
        self._owner[slot] = owner
        self.alloc_count += 1
        return slot

    def free(self, slot):
        slot = int(slot)
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range")
        if slot in self._free:
            raise RuntimeError(f"double free of slot {slot}")
        self._owner[slot] = None
        self._free.append(slot)
        self.free_count += 1

    def owner(self, slot):
        return self._owner[slot]

    def allocated_slots(self):
        free = set(self._free)
        return [s for s in range(self.n_slots) if s not in free]

    def audit(self):
        return {"allocs": self.alloc_count, "frees": self.free_count,
                "in_use": self.n_active}


class EmbedRequest(Request):
    """One scoring request: ``prompt`` holds the sparse ids ``[F]``,
    ``dense`` the dense features, ``scores`` the result.  ``tokens``
    stays EMPTY — an embedding request either finishes inside one
    iteration or was never served, so a fleet failover always re-homes
    it with no replay."""

    def __init__(self, ids, dense=None, **kw):
        super().__init__(ids, kw.pop("max_new", 1), **kw)
        self.dense = dense
        self.scores = []

    def result(self):
        return np.asarray(self.scores, np.float32)


class EmbeddingServer:
    """Tiered embedding serving through the Scheduler lifecycle.

    ``host_table=`` is the cold tier (``ps.EmbeddingTable``,
    ``ps.CacheSparseTable``, or anything with ``lookup``/``versions``);
    by default the server SPILLS the model's in-graph table to a fresh
    host-RAM ``EmbeddingTable`` — serve-a-trained-model without keeping
    the table in device memory.  ``cache_rows=`` sizes the device
    hot-row tier (must hold at least one batch of unique ids,
    ``n_slots * num_sparse``); ``cache_rows=None`` disables it — the
    uncached host-tier twin the bench compares against.

    ``close()`` (or the context manager) tears the server down,
    shutting down a ``CacheSparseTable`` cold tier's worker thread with
    it unless ``own_host_table=False`` says the table is shared (a
    fleet of replicas over one table)."""

    def __init__(self, executor, model, host_table=None, cache_rows=None,
                 n_slots=8, policy="lfu", staleness_bound=0,
                 max_queue=None, low_watermark=None,
                 shed_policy="reject_newest", watchdog=True, clock=None,
                 instance=None, latency_buckets=None, device=None,
                 name=None, own_host_table=None):
        self.params = executor.params
        self.model = model
        self.instance = None if instance is None else str(instance)
        self.device = device
        if device is not None:
            self.params = jax.device_put(self.params, device)
        self.num_sparse = int(model.num_sparse)
        self.dim = int(model.embedding_dim)
        self.num_dense = int(
            np.asarray(self.params[model.wide.weight.name]).shape[0])
        self.n_slots = int(n_slots)
        self.name = str(name) if name is not None else (
            self.instance or "embed")
        self.watchdog = bool(watchdog)
        self._clock = clock if clock is not None else time.perf_counter
        if host_table is None:
            # spill the trained in-graph table to host RAM: the device
            # never holds the full table again, exactly the
            # bigger-than-HBM serving shape the PS tier exists for
            rows = model.emb.host_table(self.params)
            table = EmbeddingTable(rows.shape[0], self.dim, lr=0.0,
                                   init_scale=0.0)
            table.set_rows(np.arange(rows.shape[0]), rows)
            host_table = table
            own_host_table = True if own_host_table is None \
                else own_host_table
        self._host_raw = host_table
        self.host = as_host_tier(host_table)
        self.own_host_table = (True if own_host_table is None
                               else bool(own_host_table))
        self._closed = False
        self.hot = None
        if cache_rows:
            if int(cache_rows) < self.n_slots * self.num_sparse:
                raise ValueError(
                    f"cache_rows={cache_rows} cannot hold one batch of "
                    f"unique ids (n_slots*num_sparse = "
                    f"{self.n_slots * self.num_sparse})")
            self.hot = DeviceHotRowCache(
                self.host, cache_rows, self.dim, policy=policy,
                staleness_bound=staleness_bound,
                name=f"{self.name}_hot", device=device)
        self.pool = BatchSlotPool(self.n_slots)
        self.cache = self.pool     # fleet-facing alias (engine.cache)
        self.scheduler = Scheduler(self.pool,
                                   prefill_budget=self.n_slots,
                                   max_queue=max_queue,
                                   low_watermark=low_watermark,
                                   shed_policy=shed_policy,
                                   rid_prefix=self.instance)
        self.records = []
        self.iterations = 0
        self.requests_scored = 0
        self.cancellations = 0
        self.expirations = 0
        self.watchdog_trips = 0
        self.streams_detached = 0
        self.lookup_seconds = []
        self.score_seconds = []
        reg = _telemetry.get_registry()
        hkw = {"buckets": (EMBED_BUCKETS if latency_buckets is None
                           else tuple(latency_buckets))}

        def _m(kind, mname, help, **kw):
            return getattr(reg, kind)(mname, help, labels=("server",),
                                      **kw).labels(server=self.name)

        self._m_scored = _m("counter", "hetu_embed_requests_total",
                            "Embedding requests retired (any "
                            "finish_reason)")
        self._m_rows = _m("counter", "hetu_embed_rows_served_total",
                          "Embedding rows gathered for scored requests")
        self._m_iters = _m("counter", "hetu_embed_iterations_total",
                           "Scoring iterations run")
        self._m_cancelled = _m("counter",
                               "hetu_embed_cancellations_total",
                               "Embedding requests cancelled")
        self._m_expired = _m("counter",
                             "hetu_embed_deadline_expired_total",
                             "Embedding requests expired past their TTL")
        self._m_watchdog = _m(
            "counter", "hetu_embed_watchdog_trips_total",
            "Scoring watchdog quarantines (non-finite score or a "
            "raising step)")
        self._m_lookup = reg.histogram(
            "hetu_embed_lookup_seconds",
            "Per-iteration tier lookup latency",
            labels=("server", "tier"), **hkw)
        self._m_score = _m("histogram", "hetu_embed_score_seconds",
                           "Per-iteration jitted scoring latency", **hkw)
        self._m_ttft = _m("histogram", "hetu_embed_ttft_seconds",
                          "Arrival -> score latency per request", **hkw)
        self._tr = _telemetry.get_tracer()
        self._rt = _telemetry.get_request_trace()
        self._fl = _telemetry.get_flight()
        self._build()

    # -- jitted scoring program --------------------------------------------
    # ONE compiled scorer per (model names, shapes, mode) signature in
    # the process, shared across server instances — same rationale as
    # InferenceEngine._PROGRAMS: twins/rebuilds/fleet replicas reuse the
    # executable, and the finiteness sentinel is in-graph for EVERY
    # server so protection stays a host-side decision.
    _PROGRAMS = {}

    def _program_key(self):
        mode = "cached" if self.hot is not None else "direct"
        shape = (self.n_slots, self.num_sparse, self.dim, self.num_dense,
                 None if self.hot is None else self.hot.padded_rows)
        return (type(self.model).__name__, self._names, shape, mode,
                jax.default_backend())

    def _build(self):
        score, self._names = make_wdl_scorer(self.model)
        entry = self._PROGRAMS.get(self._program_key())
        if entry is None:
            dim = self.dim
            p_rows = None if self.hot is None else self.hot.p_rows
            from ... import telemetry as _tel
            retrace = _tel.get_registry().counter(
                "hetu_embed_retraces_total",
                "Times each jitted scoring program was traced — >1 "
                "after warmup breaks the compile-once contract",
                labels=("program",))
            mode = "cached" if self.hot is not None else "direct"
            traces = {mode: 0}

            if self.hot is not None:
                def score_step(params, table_dev, slot_ids, dense,
                               active):
                    traces[mode] += 1   # host-side retrace witness
                    retrace.labels(program=mode).inc()
                    packed = table_dev.reshape(p_rows, 128)
                    rows = packed_lookup(packed, slot_ids, dim)
                    logits = score(params, rows, dense)
                    ok = jnp.isfinite(logits)
                    return jnp.where(active, logits, 0.0), ok
            else:
                def score_step(params, rows, dense, active):
                    traces[mode] += 1   # host-side retrace witness
                    retrace.labels(program=mode).inc()
                    logits = score(params, rows, dense)
                    ok = jnp.isfinite(logits)
                    return jnp.where(active, logits, 0.0), ok

            entry = {"fn": jax.jit(score_step), "traces": traces}
            self._PROGRAMS[self._program_key()] = entry
        self._score_fn = entry["fn"]
        self._traces = entry["traces"]

    @property
    def trace_counts(self):
        """Shared retrace counters (compile-once witness): 1 after
        warmup means every server with this signature runs one
        executable."""
        return dict(self._traces)

    # AOT scoring executables keyed by cost_signature(), mirroring the
    # engine: repeat raw cost_programs() calls stay retrace-flat
    _COST_PROGRAMS = {}

    def cost_signature(self):
        """Stable identity of the compiled scoring program at this
        server's serving shapes — the profiler's capture-cache key
        (same program key + slot/feature geometry means the same
        executable, so a cached cost capture is exact)."""
        return repr((self._program_key(), self.n_slots, self.num_dense,
                     self.num_sparse, self.dim))

    def cost_programs(self, force=False):
        """AOT-lower + compile the scoring program at this server's
        exact serving shapes; ``{"score": compiled}`` for the profiling
        layer.  Pure analysis; results are cached per
        :meth:`cost_signature`, so only the first call per signature
        re-traces the shared python callable (``force=True`` rebuilds
        unconditionally)."""
        sig = self.cost_signature()
        if not force:
            cached = self._COST_PROGRAMS.get(sig)
            if cached is not None:
                return dict(cached)

        def ab(x):
            return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype)

        params = jax.tree_util.tree_map(ab, self.params)
        n = self.n_slots
        dense = jax.ShapeDtypeStruct((n, self.num_dense), jnp.float32)
        active = jax.ShapeDtypeStruct((n,), jnp.bool_)
        if self.hot is not None:
            gathered = (ab(self.hot.packed_view()),
                        jax.ShapeDtypeStruct((n, self.num_sparse),
                                             jnp.int32))
        else:
            gathered = (jax.ShapeDtypeStruct(
                (n, self.num_sparse, self.dim), jnp.float32),)
        progs = {"score": self._score_fn.lower(
            params, *gathered, dense, active).compile()}
        self._COST_PROGRAMS[sig] = dict(progs)
        return progs

    # -- request API --------------------------------------------------------
    def submit(self, ids, max_new=1, stream=None, eos_id=None,
               arrival=None, deadline=None, ttl=None, replay=None,
               rid=None, dense=None):
        """Queue one scoring request (ids ``[num_sparse]`` int); the
        engine-compatible signature lets ``EngineFleet`` dispatch and
        fail embedding traffic over unchanged.  ``stream(score, req)``
        fires once, when the score is produced.  Raises
        :class:`~..scheduler.EngineOverloaded` when the bounded queue
        refuses admission."""
        self._require_open()
        ids = np.asarray(ids, np.int32).reshape(-1)
        if ids.size != self.num_sparse:
            raise ValueError(
                f"expected {self.num_sparse} sparse ids per request, "
                f"got {ids.size}")
        if dense is None:
            dense = np.zeros(self.num_dense, np.float32)
        dense = np.asarray(dense, np.float32).reshape(-1)
        if dense.size != self.num_dense:
            raise ValueError(
                f"expected {self.num_dense} dense features, got "
                f"{dense.size}")
        now = self._now()
        if ttl is not None:
            if deadline is not None:
                raise ValueError("pass ttl= or deadline=, not both")
            if ttl <= 0:
                raise ValueError(f"ttl must be > 0, got {ttl}")
            deadline = now + float(ttl)
        req = EmbedRequest(ids, dense=dense,
                           arrival=now if arrival is None else arrival,
                           stream=stream, eos_id=eos_id,
                           deadline=deadline, replay=replay, rid=rid)
        try:
            self.scheduler.submit(req, now=now)
        finally:
            for shed in self.scheduler.drain_shed():
                self.expirations += 1
                self._m_expired.inc()
                self._finalize_unadmitted(shed, "deadline", now)
        return req

    def cancel(self, rid):
        """Cancel the live request with this rid (queued, or running if
        caught inside an iteration); finishes with
        ``finish_reason="cancelled"``."""
        req = self.scheduler.find(rid)
        if req is None:
            return False
        now = self._now()
        req.cancel_requested = True
        if req.slot is not None:
            self._finalize_active(req, "cancelled", now)
        else:
            self.scheduler.remove_queued(req)
            self._finalize_unadmitted(req, "cancelled", now)
        self.cancellations += 1
        self._m_cancelled.inc()
        return True

    def harvest(self):
        """Remove every live request for fleet failover (attempt-level
        ``finish_reason="failover"``); running before queued, the order
        a sibling re-admits them in.  Embedding attempts never delivered
        anything, so the fleet re-homes them with an empty replay."""
        now = self._now()
        out = []
        for req in list(self.scheduler.running.values()):
            self._finalize_active(req, "failover", now)
            out.append(req)
        while self.scheduler.queue:
            req = self.scheduler.queue.popleft()
            self._finalize_unadmitted(req, "failover", now)
            out.append(req)
        return out

    def _now(self):
        return self._clock()

    def _require_open(self):
        if self._closed:
            raise RuntimeError(f"EmbeddingServer {self.name} is closed")

    # -- bookkeeping --------------------------------------------------------
    def _record(self, req):
        self.records.append({
            "id": req.rid, "prompt_len": int(req.prompt.size),
            "n_tokens": len(req.scores),
            "queue_wait": req.queue_wait, "ttft": req.ttft,
            "tpot": req.tpot, "finish_reason": req.finish_reason})
        # same timeline vocabulary as the LLM engine (request_trace.py)
        reason = req.finish_reason
        if reason == "deadline":
            self._rt.event(req.rid, "expired", engine=self.instance)
        elif reason == "cancelled":
            self._rt.event(req.rid, "cancelled", engine=self.instance)
        elif reason == "failover":
            self._rt.event(req.rid, "harvested", engine=self.instance)
        self._rt.event(req.rid, "finish", engine=self.instance,
                       reason=reason, scores=len(req.scores))
        self._m_scored.inc()
        if req.ttft is not None:
            self._m_ttft.observe(req.ttft)

    def _finalize_active(self, req, reason, now):
        req.t_done = now
        self.scheduler.retire(req, reason)
        self._record(req)

    def _finalize_unadmitted(self, req, reason, now):
        req.t_done = now
        req.finished = True
        req.finish_reason = reason
        self._record(req)

    def _expire(self, now):
        for req in self.scheduler.take_expired(now):
            self.expirations += 1
            self._m_expired.inc()
            self._finalize_unadmitted(req, "deadline", now)

    def _trip(self, req, why, now):
        self.watchdog_trips += 1
        self._m_watchdog.inc()
        warnings.warn(
            f"embedding watchdog: {why} for request {req.rid} — "
            "quarantined (finish_reason='error')")
        self._rt.event(req.rid, "watchdog_trip", engine=self.instance,
                       why=why)
        self._fl.incident("watchdog", rid=req.rid,
                          extra={"engine": self.instance, "why": why})
        self._finalize_active(req, "error", now)

    def _emit(self, req, value, now):
        req.scores.append(float(value))
        if req.t_first is None:
            req.t_first = now
        if req.stream is not None:
            try:
                req.stream(float(value), req)
            except Exception as e:
                if not self.watchdog:
                    raise
                req.stream = None
                self.streams_detached += 1
                warnings.warn(
                    f"stream callback for request {req.rid} raised "
                    f"{type(e).__name__}: {e} — detached (score lands "
                    "in result())")

    # -- the iteration ------------------------------------------------------
    def step(self):
        """One scheduler iteration: expire, admit up to ``n_slots``
        requests, ONE batched tier lookup, ONE jitted score step, retire
        everything scored.  Returns the number of requests scored."""
        self._require_open()
        now = self._now()
        self._expire(now)
        for req, slot in self.scheduler.admit():
            req.t_admit = now
            self._rt.event(req.rid, "admitted", engine=self.instance,
                           slot=slot)
            if req.expired(now):
                # mid-flight expiry: admitted this very iteration but
                # already past deadline — partial terminal, seat freed
                self.expirations += 1
                self._m_expired.inc()
                self._finalize_active(req, "deadline", now)
        live = sorted(self.scheduler.running.items())
        if not live:
            return 0
        slots = [s for s, _ in live]
        reqs = [r for _, r in live]
        ids = np.stack([r.prompt for r in reqs])            # [A, F]
        dense = np.zeros((self.n_slots, self.num_dense), np.float32)
        dense[slots] = np.stack([r.dense for r in reqs])
        active = np.zeros(self.n_slots, bool)
        active[slots] = True
        tier = "device_hot" if self.hot is not None else "host_table"
        t0 = time.perf_counter()
        hot0 = ((self.hot.hits, self.hot.misses + self.hot.refreshes)
                if self.hot is not None else (0, 0))
        try:
            with self._tr.span("embed_lookup"):
                if self.hot is not None:
                    slot_ids = np.zeros((self.n_slots, self.num_sparse),
                                        np.int32)
                    slot_ids[slots] = self.hot.lookup_slots(ids)
                    gathered = (self.hot.packed_view(),
                                jnp.asarray(slot_ids))
                else:
                    # the uncached twin: the DLRM-paper host gather —
                    # every batch fetches its rows from host RAM and
                    # ships them up
                    rows = np.zeros(
                        (self.n_slots, self.num_sparse, self.dim),
                        np.float32)
                    rows[slots] = self.host.lookup(
                        ids.reshape(-1)).reshape(ids.shape + (self.dim,))
                    gathered = (jnp.asarray(rows),)
            dt = time.perf_counter() - t0
            self.lookup_seconds.append(dt)
            self._m_lookup.labels(server=self.name, tier=tier).observe(dt)
            # per-tier lookup events, batch-attributed: the tier gather
            # is ONE batched op, so every live request gets one event
            # naming where its iteration's rows came from (cache hits
            # vs host pulls for misses+stale; the uncached twin always
            # pulls from the host table)
            if self._rt.enabled:
                if self.hot is not None:
                    d_hits = self.hot.hits - hot0[0]
                    d_pulls = (self.hot.misses + self.hot.refreshes
                               - hot0[1])
                    for req in reqs:
                        if d_hits:
                            self._rt.event(req.rid, "hot_hit",
                                           engine=self.instance,
                                           tier=tier,
                                           batch_rows=d_hits)
                        if d_pulls:
                            self._rt.event(req.rid, "host_pull",
                                           engine=self.instance,
                                           tier=tier,
                                           batch_rows=d_pulls)
                else:
                    for req in reqs:
                        self._rt.event(req.rid, "host_pull",
                                       engine=self.instance, tier=tier,
                                       batch_rows=int(ids.size))
            t1 = time.perf_counter()
            with self._tr.span("embed_score"):
                scores, ok = self._score_fn(
                    self.params, *gathered, jnp.asarray(dense),
                    jnp.asarray(active))
                scores = np.asarray(scores)
                ok = np.asarray(ok)
            dt = time.perf_counter() - t1
            self.score_seconds.append(dt)
            self._m_score.observe(dt)
        except Exception as e:
            if not self.watchdog:
                raise
            now = self._now()
            for req in list(self.scheduler.running.values()):
                self._trip(req, f"scoring step raised "
                           f"{type(e).__name__}: {e}", now)
            return 0
        self.iterations += 1
        self._m_iters.inc()
        now = self._now()
        produced = 0
        for slot, req in zip(slots, reqs):
            if self.watchdog and not ok[slot]:
                self._trip(req, "non-finite score", now)
                continue
            self._emit(req, scores[slot], now)
            self.requests_scored += 1
            produced += 1
            self._m_rows.inc(self.num_sparse)
            self._finalize_active(req, "scored", now)
        return produced

    def run(self, max_iterations=None):
        """Step until queue and seats drain; returns iterations used."""
        it = 0
        while not self.scheduler.idle:
            if max_iterations is not None and it >= max_iterations:
                raise RuntimeError(
                    f"server did not drain in {max_iterations} "
                    "iterations")
            self.step()
            it += 1
        return it

    def score_many(self, ids_batch, dense_batch=None, max_iterations=None):
        """Synchronous batch API: submit all, drain, return the scores
        ``[n]`` float32 (NaN for any request that did not finish
        "scored")."""
        n = len(ids_batch)
        reqs = [self.submit(ids_batch[i],
                            dense=None if dense_batch is None
                            else dense_batch[i])
                for i in range(n)]
        self.run(max_iterations=max_iterations or 2 * n + 4)
        return np.asarray(
            [r.scores[0] if r.scores else np.nan for r in reqs],
            np.float32)

    # -- teardown -----------------------------------------------------------
    def close(self):
        """Tear the server down: refuse new work and shut down an OWNED
        cold tier (a ``CacheSparseTable``'s worker thread dies here —
        the teardown ownership the thread-leak gate's allowlist names).
        Idempotent."""
        if self._closed:
            return
        self._closed = True
        if self.hot is not None:
            self.hot.close()   # ends its hot_cache HBM-ledger entry
        if self.own_host_table and hasattr(self._host_raw, "close"):
            self._host_raw.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- reporting ----------------------------------------------------------
    def reset_stats(self):
        """Clear per-request records and counters (NOT the shared trace
        counters — the compile-once guard still needs them)."""
        self.records = []
        self.iterations = 0
        self.requests_scored = 0
        self.cancellations = 0
        self.expirations = 0
        self.watchdog_trips = 0
        self.streams_detached = 0
        self.lookup_seconds = []
        self.score_seconds = []

    def stats(self):
        out = {"n_slots": self.n_slots,
               "iterations": self.iterations,
               "requests_finished": len(self.records),
               "requests_scored": self.requests_scored,
               "slot_allocs": self.pool.alloc_count,
               "slot_frees": self.pool.free_count,
               "rejections": self.scheduler.rejected,
               "queue_depth_peak": self.scheduler.queue_depth_peak,
               "cancellations": self.cancellations,
               "expirations": self.expirations,
               "watchdog_trips": self.watchdog_trips,
               "streams_detached": self.streams_detached,
               "trace_counts": self.trace_counts}
        if self.hot is not None:
            out["hot_cache"] = self.hot.stats()
        return out
