"""Fleet serving: N supervised engine replicas behind a failover router.

PR 5 made one ``InferenceEngine`` survive poisoned slots, deadline
churn, and overload — but one engine is still one blast radius: a
crashed or wedged engine takes every in-flight stream with it.
``EngineFleet`` is the cluster-level robustness layer:

* **replicas** — N in-process :class:`~.engine.InferenceEngine`\\ s,
  one driver thread each (``threaded=True``; ``threaded=False`` gives a
  deterministic ``pump()`` loop for tests and seeded benches), pinned
  one-per-device when the backend has multiple devices;
* **latency-aware dispatch** — ``submit`` routes to the replica with
  the lowest ``(queue_depth + in_flight + 1) * TPOT_EWMA`` score, the
  telemetry signals PR 5 left as the "latency-aware admission"
  follow-up.  Request ids are CLUSTER-level: ``"e0-7"`` names the
  engine instance that admitted the request and stays with the request
  across failover;
* **health state machine** — each replica runs
  HEALTHY → DEGRADED → QUARANTINED → RESTARTING (health.py), driven by
  heartbeats (a wedged ``step()`` shows as a stale heartbeat) and
  watchdog-trip deltas; DRAINING/STOPPED support rolling restarts;
* **circuit breaker** — quarantine opens a per-replica breaker with
  exponential backoff; the supervisor restarts the replica only after
  the backoff elapses, and the breaker resets only after clean ticks —
  a crash-looping replica backs off geometrically;
* **failover of in-flight requests** — the headline property.  When a
  replica crashes, wedges, or is quarantined mid-decode, its unfinished
  requests are harvested and re-submitted on a sibling with
  ``replay=tokens_so_far``: the sibling re-prefills the prompt through
  the SAME shared executable and teacher-forces the already-delivered
  tokens (one decode step each, fused into its normal iteration), so a
  greedy stream continues BITWISE identically to an uninterrupted run
  and is never re-delivered.  Every accepted rid reaches a terminal
  ``finish_reason``;
* **supervised restart** — dead replicas are rebuilt cheaply: the
  compile-once program cache (``InferenceEngine._PROGRAMS``) is shared
  process-wide, so a restart allocates a fresh KV pool but never
  retraces (retrace counters stay flat — the bench asserts it).

Failure containment ladder: a poisoned SLOT is the engine watchdog's
job (that request alone retires "error" — and then the fleet retries it
on a sibling); a sick ENGINE is the fleet's job (quarantine + failover
+ supervised restart); only losing the whole process is left to the
layer above.

Hedged dispatch (``submit(..., hedge=True)``) duplicates a request onto
the two best replicas; the first terminal success wins and the loser is
cancelled — tail-latency insurance for critical requests (greedy
streams are identical on both, so the race is benign).

Usage::

    fleet = EngineFleet(ex, model, n_engines=3,
                        engine_kwargs=dict(n_slots=4, max_len=128))
    h = fleet.submit(prompt, max_new=64)       # -> FleetRequest
    fleet.wait([h]); print(h.result())
    fleet.rolling_restart()                    # zero accepted-rid loss
    fleet.stop()
"""

from __future__ import annotations

import threading
import time
import warnings
from collections import deque

import numpy as np

from .. import telemetry as _telemetry
from .engine import InferenceEngine
from .health import (CircuitBreaker, DEGRADED, DISPATCHABLE, DRAINING,
                     HEALTH_STATE_CODES, HEALTHY, QUARANTINED,
                     ReplicaHealth, RESTARTING, STOPPED)
from .scheduler import EngineOverloaded, TERMINAL_OK

__all__ = ["EngineFleet", "FleetRequest", "FleetUnavailable"]

#: replica roles for disaggregated serving (EngineFleet(roles=...)):
#: "prefill" replicas admit + prefill and hand streams off, "decode"
#: replicas receive migrated streams, "mixed" (the default) does both.
_ROLES = ("prefill", "decode", "mixed")


class FleetUnavailable(RuntimeError):
    """No replica can take the request: every engine is circuit-broken,
    quarantined, draining, or stopped.  Mirrors ``ps.PSUnavailable`` —
    a TYPED terminal error carrying enough state to act on: ``states``
    maps each engine to its health state, and ``retry_after`` (seconds,
    or None when no breaker is counting down) aggregates the breaker
    backoffs into the soonest moment a retry could succeed."""

    def __init__(self, states, retry_after=None):
        hint = ("no restart pending" if retry_after is None
                else f"retry after ~{retry_after:.2f}s")
        super().__init__(
            f"fleet unavailable: no dispatchable replica ({states}; "
            f"{hint})")
        self.states = dict(states)
        self.retry_after = (None if retry_after is None
                            else float(retry_after))


class FleetRequest:
    """Cluster-level request handle.

    The engine-level :class:`~.scheduler.Request` is one ATTEMPT; this
    handle survives failover (same ``rid``, new attempt on a sibling)
    and is what client code holds.  ``tokens``/``result()`` always show
    the full stream from token 0 — a failed-over attempt replays its
    predecessor's tokens, so the latest attempt's token list IS the
    stream.  ``stream`` callbacks fire exactly once per token: replayed
    tokens are never re-delivered, and late emits from a superseded
    (wedged) attempt are fenced off."""

    def __init__(self, prompt, max_new, stream=None, eos_id=None,
                 deadline=None, arrival=None, hedge=False,
                 temperature=None, top_k=None, seed=None):
        self.rid = None             # set at first dispatch
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        self.max_new = int(max_new)
        self.stream_cb = stream
        self.eos_id = eos_id
        self.deadline = None if deadline is None else float(deadline)
        # per-request sampling (paged replicas): request-scoped, so a
        # failover re-dispatch samples under the SAME seed and the
        # continued stream stays bit-exact
        self.temperature = temperature
        self.top_k = top_k
        self.seed = seed
        self.hedge = bool(hedge)
        self.attempt = None         # current engine-level Request
        self.engine = None          # replica name serving the attempt
        self.engines = []           # replica names tried, in order
        self.failovers = 0
        self.hedge_attempt = None   # (replica_name, Request) secondary
        self.cancel_requested = False
        self.t_arrival = arrival
        self.t_done = None
        self._finished = False
        self._finish_reason = None
        self._tokens_snapshot = []  # last harvest fence (see fleet)

    @property
    def finished(self):
        return self._finished

    @property
    def finish_reason(self):
        return self._finish_reason

    @property
    def tokens(self):
        att = self.attempt
        return list(att.tokens) if att is not None \
            else list(self._tokens_snapshot)

    def result(self):
        return np.asarray(self.tokens, np.int32)

    def __repr__(self):
        state = ("done" if self._finished
                 else "live" if self.attempt is not None else "pending")
        return (f"FleetRequest(id={self.rid}, engine={self.engine}, "
                f"failovers={self.failovers}, {state})")


class _Replica:
    """One supervised engine slot: the engine, its driver thread, its
    health + breaker, and the fleet requests in flight on it."""

    def __init__(self, index, name, engine, health, breaker,
                 role="mixed"):
        self.index = index
        self.name = name
        self.engine = engine
        self.health = health
        self.breaker = breaker
        self.role = role           # "prefill" | "decode" | "mixed"
        self.lock = threading.RLock()
        self.thread = None
        self.generation = 0        # bumped to fence a zombie driver
        self.incarnation = 0       # restarts survived (rid uniqueness)
        self.inflight = {}         # rid -> (FleetRequest, attempt)
        self.dispatches = 0
        self.last_trips = 0        # engine.watchdog_trips at last tick
        self.last_error = None
        self.ttft_ewma = None
        self.tpot_ewma = None


class EngineFleet:
    """Health-checked multi-engine router with failover and supervised
    restart (see module doc).

    ``engine_kwargs`` is passed to every replica's
    :class:`~.engine.InferenceEngine` (n_slots, max_len, max_queue, …);
    the fleet itself supplies ``instance`` (cluster rids), ``clock``,
    ``latency_buckets``, and per-replica ``device`` pinning when the
    backend has multiple devices.  ``threaded=False`` disables the
    driver/supervisor threads: drive the fleet deterministically with
    :meth:`pump` (each tick is wedge-bounded: a stalled step is
    reported and quarantined when the pump regains control).

    ``engine_factory=`` swaps the replica type for any engine speaking
    the same surface (``submit``/``step``/``cancel``/``harvest``/
    ``scheduler``/``cache.audit``/``watchdog_trips``/``trace_counts``)
    — ``serving.embedding.EmbeddingServer`` rides the whole
    routing/health/failover machinery unchanged this way (a harvested
    embedding attempt delivered nothing, so it re-homes with an empty
    replay; read scores from ``freq.attempt.result()``)."""

    def __init__(self, executor, model, n_engines=2, engine_kwargs=None,
                 *, threaded=True, clock=None, name="fleet",
                 degraded_after=1, quarantine_after=3, recover_after=8,
                 breaker_base=0.25, breaker_cap=30.0, max_failovers=3,
                 wedge_timeout=None, wedge_floor=5.0, wedge_safety=50.0,
                 supervise_interval=0.02,
                 idle_sleep=0.001, auto_restart=True, ewma_alpha=0.3,
                 latency_buckets=None, engine_factory=None,
                 replica_prefix="e", tp_size=1, roles=None):
        if n_engines < 1:
            raise ValueError(f"n_engines must be >= 1, got {n_engines}")
        # disaggregated prefill/decode: roles=("prefill", "decode", ...)
        # names one role per initial replica.  "prefill" replicas take
        # new submissions; once a stream has >= 1 generated token the
        # supervision pass migrates its pages to a "decode"/"mixed"
        # sibling (kv_transfer), so prefill-heavy replicas never spend
        # iterations decoding.  None (default) = every replica "mixed",
        # behavior unchanged.
        if roles is not None:
            roles = [str(r) for r in roles]
            if len(roles) != int(n_engines):
                raise ValueError(
                    f"roles has {len(roles)} entries for "
                    f"n_engines={n_engines}")
            bad = [r for r in roles if r not in _ROLES]
            if bad:
                raise ValueError(
                    f"unknown roles {bad}; expected one of {_ROLES}")
        self._roles = roles
        self._executor = executor
        self._model = model
        self._engine_factory = (InferenceEngine if engine_factory is None
                                else engine_factory)
        self._ekw = dict(engine_kwargs or {})
        self._ekw.pop("instance", None)
        self._ekw.pop("clock", None)
        self.name = str(name)
        self.threaded = bool(threaded)
        self._clock = clock if clock is not None else time.perf_counter
        self._hp = dict(degraded_after=degraded_after,
                        quarantine_after=quarantine_after,
                        recover_after=recover_after)
        self._bp = dict(base=breaker_base, cap=breaker_cap)
        self.max_failovers = int(max_failovers)
        # wedge_timeout=None derives the bound from the replica's
        # observed TPOT (effective_wedge_timeout); an explicit value is
        # an absolute override, as before
        self.wedge_timeout = (None if wedge_timeout is None
                              else float(wedge_timeout))
        self.wedge_floor = float(wedge_floor)
        self.wedge_safety = float(wedge_safety)
        self.supervise_interval = float(supervise_interval)
        self.idle_sleep = float(idle_sleep)
        self.auto_restart = bool(auto_restart)
        self.ewma_alpha = float(ewma_alpha)
        self._latency_buckets = latency_buckets
        # one replica per device when the mesh offers several (ROADMAP
        # direction 1's scale-out shape); on one device they time-share.
        # tp_size > 1 upgrades the unit of pinning from one device to a
        # contiguous group of tp_size devices: each replica becomes a
        # tensor-parallel engine on its own (replica=1, model=tp_size)
        # sub-mesh, and failover re-homes onto a sharded sibling
        import jax
        devs = jax.devices()
        self.tp_size = int(tp_size)
        if self.tp_size < 1:
            raise ValueError(f"tp_size must be >= 1, got {tp_size}")
        if self.tp_size > 1:
            if not self._ekw.get("paged"):
                raise ValueError(
                    "tp_size > 1 requires paged=True engine_kwargs — "
                    "the sharded executables are the paged pair")
            if len(devs) < self.tp_size:
                raise ValueError(
                    f"tp_size={self.tp_size} needs that many devices, "
                    f"have {len(devs)}")
            from . import sharding as _shd
            n_groups = len(devs) // self.tp_size
            self._meshes = [
                _shd.serving_mesh(
                    self.tp_size,
                    devices=devs[g * self.tp_size:(g + 1) * self.tp_size])
                for g in range(n_groups)]
            self._devices = [None]
        else:
            self._meshes = None
            self._devices = devs if len(devs) > 1 else [None] * n_engines
        self._requests = {}        # rid -> FleetRequest (accepted ever)
        self._flock = threading.Lock()
        # (FleetRequest, tokens, blob) to re-home: blob is the donor's
        # kv_transfer snapshot when one could be taken (page migration
        # first), None otherwise (teacher-forced replay only)
        self._failover = deque()
        self._cancels = deque()    # (replica_name, rid) deferred cancels
        self._prefix_handoffs = deque()   # (donor_name, prefix blob)
        # test/fault hook (resilience/faults.py): every migration blob
        # passes through this callable on its way to the receiver; None
        # return = dropped in flight, mutated bytes = corruption — the
        # CRC framing catches it and replay takes over
        self.transfer_filter = None
        self._migrate_lock = threading.Lock()   # one migration at a time
        # manual-mode dispatch-wedge watcher (armed around pump ticks)
        self._watch_armed = None
        self._watch_thread = None
        self._running = False
        self._sup_thread = None
        self.submitted = 0
        self.completed = 0
        self.failovers_done = 0
        self.migrations_done = 0
        self.migration_failures = 0
        self.prefix_handoffs_done = 0
        self.hedged = 0
        self.hedges_skipped = 0
        self.replica_prefix = str(replica_prefix)
        self._next_index = int(n_engines)   # add_replica allocation
        self.finish_counts = {}   # reason -> count (O(1) controller read)
        reg = _telemetry.get_registry()
        self._m_health = reg.gauge(
            "hetu_fleet_engine_health_state",
            "Replica health (0 healthy, 1 degraded, 2 quarantined, "
            "3 restarting, 4 draining, 5 stopped)", labels=("engine",))
        self._m_dispatch = reg.counter(
            "hetu_fleet_dispatches_total",
            "Requests routed to each replica", labels=("engine",))
        self._m_failovers = reg.counter(
            "hetu_fleet_failovers_total",
            "In-flight requests re-homed onto a sibling replica")
        self._m_breaker = reg.counter(
            "hetu_fleet_breaker_opens_total",
            "Circuit-breaker opens (quarantines)", labels=("engine",))
        self._m_restarts = reg.counter(
            "hetu_fleet_restarts_total",
            "Supervised replica restarts", labels=("engine",))
        self._m_drains = reg.counter(
            "hetu_fleet_drains_total",
            "Replica drains requested", labels=("engine",))
        self._m_crashes = reg.counter(
            "hetu_fleet_engine_crashes_total",
            "Driver-observed engine exceptions", labels=("engine",))
        self._m_wedges = reg.counter(
            "hetu_fleet_engine_wedges_total",
            "Stale-heartbeat quarantines (wedged step)",
            labels=("engine",))
        self._m_hedged = reg.counter(
            "hetu_fleet_hedged_dispatches_total",
            "Requests duplicated onto a second replica")
        self._m_unavail = reg.counter(
            "hetu_fleet_unavailable_total",
            "Submits refused with FleetUnavailable")
        self._m_migrations = reg.counter(
            "hetu_migrate_attempts_total",
            "Live KV page migrations attempted, by path (failover, "
            "rebalance, drain, handoff)", labels=("path",))
        self._m_migrate_fail = reg.counter(
            "hetu_migrate_failures_total",
            "Migrations that fell back to teacher-forced replay "
            "(torn/corrupt transfer, geometry drift, receiver refusal)",
            labels=("path",))
        self._m_migrate_bytes = reg.counter(
            "hetu_migrate_bytes_total",
            "Wire bytes of successfully spliced KV transfer blobs")
        self._m_migrate_prefix = reg.counter(
            "hetu_migrate_prefix_entries_total",
            "Prefix-cache entries re-interned on a sibling after their "
            "replica was quarantined")
        self._m_handoffs = reg.counter(
            "hetu_serving_role_handoffs_total",
            "Prefill->decode stream handoffs between role groups")
        self._g_role = reg.gauge(
            "hetu_serving_role_replicas",
            "Replicas per disaggregation role",
            labels=("fleet", "role"))
        self._rt = _telemetry.get_request_trace()
        self._fl = _telemetry.get_flight()
        self._tr = _telemetry.get_tracer()
        # multi-replica-per-chip param sharing: one placed copy of the
        # weights per device, every co-resident replica reads it —
        # device -> (placed pytree, HBM ledger handle, pool="params")
        self._param_store = {}
        self._replicas = [self._make_replica(i) for i in range(n_engines)]
        self._sync_role_gauge()
        self.start()

    # -- construction ------------------------------------------------------
    def _instance_name(self, index, incarnation):
        base = f"{self.replica_prefix}{index}"
        return base if incarnation == 0 else f"{base}.{incarnation}"

    def _shared_params(self, dev):
        """One placed copy of the weights per device, shared by every
        replica pinned there (and by every incarnation across
        restarts): N co-resident replicas cost 1x params HBM, not Nx.
        The copy is ledger-accounted once under ``pool="params"`` — the
        kv pools stay per-replica, so the incident-dump HBM view shows
        exactly what is deduplicated and what is not."""
        ent = self._param_store.get(dev)
        if ent is None:
            if dev is None:
                placed = self._executor.params
            else:
                import jax
                placed = {k: jax.device_put(v, dev)
                          for k, v in self._executor.params.items()}
            nbytes = sum(int(v.nbytes) for v in placed.values())
            handle = _telemetry.get_hbm_ledger().alloc(
                "params", nbytes,
                owner=f"fleet:{self.name}:params:{dev or 'host'}")
            ent = self._param_store[dev] = (placed, handle)
        return ent[0]

    def _build_engine(self, index, incarnation):
        if self._meshes is not None:
            # sub-mesh pinning: replicas round-robin the contiguous
            # device groups (same group across restarts — the rebuilt
            # engine reuses the incarnation-independent index, so the
            # compile-once cache keyed on device ids still hits)
            pin = dict(mesh=self._meshes[index % len(self._meshes)])
        else:
            # single-device pinning: the replica reads the fleet's
            # per-device shared copy of the params instead of placing
            # its own (engine_factory overrides — embedding fleets —
            # keep their own placement path)
            dev = self._devices[index % len(self._devices)]
            pin = dict(device=dev)
            if self._engine_factory is InferenceEngine:
                pin["shared_params"] = self._shared_params(dev)
        return self._engine_factory(
            self._executor, self._model,
            instance=self._instance_name(index, incarnation),
            clock=self._clock,
            latency_buckets=self._latency_buckets,
            **pin, **self._ekw)

    def _role_for(self, index):
        """Initial replicas get their configured role; replicas added
        later (controller scale-up) join as "mixed" — they can absorb
        whatever the fleet is short of."""
        if self._roles is not None and index < len(self._roles):
            return self._roles[index]
        return "mixed"

    @property
    def _has_roles(self):
        return self._roles is not None

    def _sync_role_gauge(self):
        counts = {r: 0 for r in _ROLES}
        for rep in self._replicas:
            counts[rep.role] = counts.get(rep.role, 0) + 1
        for role, n in counts.items():
            self._g_role.labels(fleet=self.name, role=role).set(n)

    def _make_replica(self, index):
        name = f"{self.replica_prefix}{index}"
        rep = _Replica(
            index, name, self._build_engine(index, 0),
            ReplicaHealth(name, clock=self._clock, **self._hp),
            CircuitBreaker(clock=self._clock, **self._bp),
            role=self._role_for(index))
        self._m_health.labels(engine=name).set(HEALTH_STATE_CODES[HEALTHY])
        return rep

    # -- elastic scale (the controller's actuators) ------------------------
    def add_replica(self):
        """Scale up: build one fresh replica at the next free index
        (indices are never reused, so rids stay unique across the
        fleet's whole life) and start its driver when threaded.
        Returns the new replica's name."""
        index = self._next_index
        self._next_index += 1
        rep = self._make_replica(index)
        # atomic list swap: readers iterate a snapshot, never a
        # half-mutated list
        self._replicas = self._replicas + [rep]
        self._sync_role_gauge()
        if self.threaded and self._running:
            self._start_driver(rep)
        return rep.name

    def remove_replica(self, name, wait=True, timeout=60.0):
        """Scale down with zero accepted-rid loss: drain the replica
        (siblings keep serving), then drop it from the fleet.  With
        ``wait=False`` the replica is left DRAINING and the call
        returns ``False``; call again once a later pump/supervise pass
        has drained it (the controller's two-phase scale-down).  The
        last replica cannot be removed."""
        rep = self._by_name(name, required=True)
        if len(self._replicas) <= 1:
            raise ValueError("cannot remove the last replica")
        if rep.health.state not in (QUARANTINED, STOPPED):
            self.drain(name, wait=wait, timeout=timeout)
        if rep.health.state not in (QUARANTINED, STOPPED):
            return False            # still draining (wait=False path)
        # QUARANTINED work was already harvested into the failover
        # queue; STOPPED means drained-to-idle — either way nothing of
        # ours runs there any more
        rep.generation += 1         # fence any driver thread
        if rep.health.state != STOPPED:
            rep.health.to(STOPPED, "removed")
        self._set_health(rep)
        if rep.engine is not None:
            rep.engine.close()
        self._replicas = [r for r in self._replicas if r is not rep]
        self._sync_role_gauge()
        return True

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        """Start driver + supervisor threads (no-op when already running
        or ``threaded=False``)."""
        if self._running:
            return self
        self._running = True
        if self.threaded:
            for rep in self._replicas:
                self._start_driver(rep)
            self._sup_thread = threading.Thread(
                target=self._supervise_loop, daemon=True,
                name=f"{self.name}-supervisor")
            self._sup_thread.start()
        return self

    def _start_driver(self, rep):
        rep.thread = threading.Thread(
            target=self._drive, args=(rep, rep.generation), daemon=True,
            name=f"{self.name}-{rep.name}-driver")
        rep.thread.start()

    def stop(self, finalize_pending=True):
        """Stop drivers + supervisor (joined; wedged zombies are fenced
        and abandoned as daemons).  Pending failovers that never found a
        home finalize with ``finish_reason="error"`` unless told not
        to."""
        self._running = False
        threads = [self._sup_thread, self._watch_thread] \
            + [r.thread for r in self._replicas]
        for rep in self._replicas:
            rep.generation += 1       # fence every driver
        for t in threads:
            if t is not None:
                t.join(timeout=2.0)
        self._sup_thread = None
        self._watch_thread = None
        if finalize_pending:
            with self._flock:
                pending, self._failover = list(self._failover), deque()
            for freq, *_ in pending:
                self._finalize(freq, "error")
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()

    # -- dispatch ----------------------------------------------------------
    def _score(self, rep):
        """Latency-aware routing score: expected time for a NEW request
        to clear this replica — (waiting + running + itself) iterations
        at the replica's observed decode rate.  Unknown TPOT borrows the
        fleet mean so cold replicas aren't shunned."""
        sch = rep.engine.scheduler
        depth = len(sch.queue) + len(sch.running)
        known = [r.tpot_ewma for r in self._replicas
                 if r.tpot_ewma]
        default = sum(known) / len(known) if known else 1.0
        tpot = rep.tpot_ewma if rep.tpot_ewma else default
        return (depth + 1.0) * tpot

    def _candidates(self):
        return [r for r in self._replicas
                if r.health.dispatchable and r.engine is not None]

    def _choose(self, prefer_not=None, exclude=(), prompt=None,
                roles=None, strict_roles=False):
        cands = [r for r in self._candidates() if r.name not in exclude]
        if roles is not None and cands:
            # role preference: fall back to ANY dispatchable replica
            # unless strict (a role-pure handoff that has no valid
            # target should just not happen, not bounce) — no request
            # is ever refused because the "right" role is down
            wanted = [r for r in cands if r.role in roles]
            cands = wanted if (wanted or strict_roles) else cands
        if not cands:
            return None
        if prefer_not is not None and len(cands) > 1:
            others = [r for r in cands if r.name != prefer_not]
            cands = others or cands
        if prompt is not None and len(cands) > 1:
            # prefix-affinity tie-break: prefix caches are per-replica
            # (page ids are pool-local), so a prompt whose prefix some
            # replica already holds prefills fastest THERE — route to
            # the longest hit unless that replica is meaningfully more
            # loaded (>2x the best latency score; load still wins)
            hits, floor = {}, None
            for r in cands:
                fn = getattr(r.engine, "prefix_hit_tokens", None)
                hits[r.name] = int(fn(prompt)) if fn is not None else 0
            if any(hits.values()):
                floor = 2.0 * min(self._score(r) for r in cands)
                best = max(hits.values())
                warm = [r for r in cands
                        if hits[r.name] == best
                        and self._score(r) <= floor]
                cands = warm or cands
        return min(cands,
                   key=lambda r: (self._score(r), r.dispatches, r.name))

    def _unavailable(self, now=None, count=True):
        now = self._clock() if now is None else now
        states = {r.name: r.health.state for r in self._replicas}
        waits = [r.breaker.retry_after(now) for r in self._replicas
                 if r.health.state in (QUARANTINED, RESTARTING)]
        if count:
            self._m_unavail.inc()
            self._fl.incident("fleet_unavailable", health=self.health(),
                              extra={"states": dict(states)})
        return FleetUnavailable(states,
                                min(waits) if waits else None)

    def _wrap_stream(self, freq):
        if freq.stream_cb is None:
            return None

        def cb(tok, attempt_req):
            # fence: only the CURRENT attempt delivers — a superseded
            # (wedged/failed-over) attempt's late emits are dropped, and
            # replayed tokens never reach here (the engine absorbs them)
            if freq.finished or freq.attempt is not attempt_req:
                return
            freq.stream_cb(int(tok), freq)

        return cb

    def _submit_on(self, rep, freq, replay=None, secondary=False):
        """Dispatch (or re-dispatch) one fleet request onto a replica.
        Caller picked ``rep``; raises EngineOverloaded through."""
        # sampling kwargs ride along only when set: LLM engines accept
        # them (paged ones honor them), EmbeddingServer fleets never
        # see unexpected keywords
        kw = {k: getattr(freq, k) for k in ("temperature", "top_k",
                                            "seed")
              if getattr(freq, k, None) is not None}
        with rep.lock:
            attempt = rep.engine.submit(
                freq.prompt, freq.max_new,
                stream=self._wrap_stream(freq), eos_id=freq.eos_id,
                deadline=freq.deadline, replay=replay, rid=freq.rid,
                **kw)
            rep.inflight[attempt.rid] = (freq, attempt)
            rep.dispatches += 1
        if secondary:
            freq.hedge_attempt = (rep.name, attempt)
        else:
            freq.attempt = attempt
            freq.engine = rep.name
            if freq.rid is None:
                freq.rid = attempt.rid
        freq.engines.append(rep.name)
        self._m_dispatch.labels(engine=rep.name).inc()
        return attempt

    def submit(self, prompt, max_new, stream=None, eos_id=None,
               ttl=None, deadline=None, hedge=False, temperature=None,
               top_k=None, seed=None):
        """Route one request to the best replica; returns its
        :class:`FleetRequest`.  Raises :class:`FleetUnavailable` when no
        replica is dispatchable, or the last replica's
        :class:`~.scheduler.EngineOverloaded` when every dispatchable
        replica refused admission (the cluster is full, not down).
        ``hedge=True`` duplicates onto the second-best replica too —
        first terminal success wins, the loser is cancelled."""
        now = self._clock()
        if ttl is not None:
            if deadline is not None:
                raise ValueError("pass ttl= or deadline=, not both")
            if ttl <= 0:
                raise ValueError(f"ttl must be > 0, got {ttl}")
            deadline = now + float(ttl)
        freq = FleetRequest(prompt, max_new, stream=stream,
                            eos_id=eos_id, deadline=deadline,
                            arrival=now, hedge=hedge,
                            temperature=temperature, top_k=top_k,
                            seed=seed)
        # role routing: new work lands on prefill/mixed replicas;
        # decode-role replicas receive migrated streams (with graceful
        # fallback inside _choose when no prefill replica is up)
        rep = self._place(freq, now=now,
                          roles=(("prefill", "mixed") if self._has_roles
                                 else None))
        self._requests[freq.rid] = freq
        self.submitted += 1
        if hedge:
            second = self._choose(exclude={rep.name})
            if second is not None:
                try:
                    self._submit_on(second, freq, secondary=True)
                    self.hedged += 1
                    self._m_hedged.inc()
                except EngineOverloaded:
                    # hedging is best-effort insurance: the primary is
                    # already placed, so a full second replica only
                    # costs the duplicate — record and move on
                    self.hedges_skipped += 1
        return freq

    def _place(self, freq, now=None, prefer_not=None, replay=None,
               count_unavailable=True, roles=None):
        """Dispatch onto the best replica, falling through overloaded
        ones (each replica is tried at most once — the loop is bounded
        by the fleet size).  Raises the last EngineOverloaded when every
        dispatchable replica is full, FleetUnavailable when none is
        dispatchable at all; returns the replica on success.
        ``count_unavailable=False`` keeps internal retries (failover
        parking) out of the client-facing refusal counter."""
        tried, last_overload = set(), None
        for _ in range(len(self._replicas)):
            rep = self._choose(prefer_not=prefer_not, exclude=tried,
                               prompt=freq.prompt, roles=roles)
            if rep is None:
                break
            try:
                self._submit_on(rep, freq, replay=replay)
                return rep
            except EngineOverloaded as e:
                tried.add(rep.name)
                last_overload = e
        if last_overload is not None:
            raise last_overload
        raise self._unavailable(now, count=count_unavailable)

    def cancel(self, rid):
        """Cancel the live fleet request with this rid on whichever
        replica(s) hold an attempt (or in the failover queue).  Returns
        True if a live request was found."""
        freq = self._requests.get(rid)
        if freq is None or freq.finished:
            return False
        freq.cancel_requested = True
        hit = False
        for rep in self._replicas:
            if rid in rep.inflight and rep.engine is not None:
                with rep.lock:
                    hit = rep.engine.cancel(rid) or hit
        with self._flock:
            for i, (f, *_) in enumerate(self._failover):
                if f is freq:
                    del self._failover[i]
                    self._finalize(freq, "cancelled")
                    hit = True
                    break
        if not self.threaded:
            self._reap_all()
        return hit

    # -- the drive loop ----------------------------------------------------
    def _drive(self, rep, gen):
        while self._running and rep.generation == gen:
            busy = self._tick(rep, gen)
            if not busy:
                time.sleep(self.idle_sleep)

    def _tick(self, rep, gen=None):
        """One driver pass over a replica: heartbeat, one engine
        iteration, then fault/terminal bookkeeping.  Returns True when
        the replica did work."""
        gen = rep.generation if gen is None else gen
        rep.health.heartbeat()
        actions = None
        with rep.lock:
            if rep.generation != gen:
                return False
            state = rep.health.state
            if state in (QUARANTINED, RESTARTING, STOPPED):
                return False
            if rep.engine.scheduler.idle:
                if state == DRAINING:
                    rep.health.to(STOPPED, "drained")
                    self._set_health(rep)
                busy = False
                actions = self._reap_locked(rep)
            else:
                try:
                    rep.engine.step()
                except Exception as e:      # engine crash
                    if rep.generation != gen:
                        return False
                    actions = self._on_crash_locked(rep, e)
                    busy = False
                else:
                    if rep.generation != gen:
                        return False
                    busy = True
                    actions = self._after_step_locked(rep)
        if actions:
            self._queue_failovers(actions)
        self._run_cancels()
        return busy

    def _set_health(self, rep):
        self._m_health.labels(engine=rep.name).set(
            HEALTH_STATE_CODES[rep.health.state])

    def _after_step_locked(self, rep):
        """Post-step bookkeeping under the replica lock: feed the health
        machine, quarantine on a trip streak (harvest + failover), and
        map finished attempts onto fleet terminals.  Returns requests
        needing a new home (dispatched OUTSIDE the lock — two drivers
        failing over toward each other must not deadlock)."""
        trips = rep.engine.watchdog_trips
        delta = trips - rep.last_trips
        rep.last_trips = trips
        state = rep.health.observe(delta)
        self._set_health(rep)
        if state == QUARANTINED:
            return self._quarantine_locked(
                rep, rep.health.last_reason or "watchdog trips")
        if (state == HEALTHY and rep.breaker.failures
                and rep.health.clean_ticks >= rep.health.recover_after):
            rep.breaker.close()     # probation served: reset the backoff
        return self._reap_locked(rep)

    def _reap_locked(self, rep):
        """Map finished engine-level attempts to fleet-level outcomes."""
        failovers = []
        for rid in [r for r, (_, a) in rep.inflight.items()
                    if a.finished]:
            freq, attempt = rep.inflight.pop(rid)
            reason = attempt.finish_reason
            if freq.finished or reason == "failover":
                continue    # hedge loser / already harvested
            if reason in TERMINAL_OK:
                if freq.attempt is not attempt:
                    # hedge secondary finished first: promote it
                    freq.attempt = attempt
                    freq.engine = rep.name
                self._update_ewma(rep, attempt)
                self._finalize(freq, reason, cancel_others=True)
            elif reason in ("deadline", "cancelled"):
                if freq.attempt is attempt:
                    if reason == "cancelled" and not freq.cancel_requested:
                        # engine-side cancel the fleet didn't ask for
                        # (shouldn't happen) — treat as an error attempt
                        failovers.extend(
                            self._failover_or_fail(freq, attempt))
                    else:
                        self._finalize(freq, reason, cancel_others=True)
                # a cancelled hedge loser needs nothing
            elif reason == "error":
                other = self._promote_survivor(freq, attempt)
                if not other:
                    failovers.extend(
                        self._failover_or_fail(freq, attempt))
        return failovers

    def _promote_survivor(self, freq, dead_attempt):
        """Hedged request lost one attempt: bind to the live one."""
        if freq.attempt is dead_attempt and freq.hedge_attempt:
            name, att = freq.hedge_attempt
            if not att.finished:
                freq.attempt, freq.engine = att, name
                freq.hedge_attempt = None
                return True
        if (freq.hedge_attempt
                and freq.hedge_attempt[1] is dead_attempt):
            freq.hedge_attempt = None
            return freq.attempt is not None \
                and not freq.attempt.finished
        return False

    def _failover_or_fail(self, freq, attempt, blob=None):
        """The attempt died: queue a re-home, or give up past the cap.
        ``blob`` is the donor's page snapshot when one was taken before
        harvest — the dispatcher tries to splice it into a sibling
        before falling back to teacher-forced replay."""
        freq.failovers += 1
        tokens = list(attempt.tokens)
        freq._tokens_snapshot = tokens
        freq.attempt = None         # fence late emits from the old one
        if freq.failovers > self.max_failovers:
            self._finalize(freq, "error")
            return []
        return [(freq, tokens, blob)]

    def _quarantine_locked(self, rep, reason, harvest=True):
        """Open the breaker and (when the engine is still callable)
        harvest every live request for failover.  Before the harvest
        frees anything, the replica's migratable decode state is
        snapshotted: page blobs ride the failover queue so streams
        splice onto a sibling instead of replaying, and the prefix
        cache is exported for re-interning elsewhere (the interned
        pages would otherwise die with this replica)."""
        rep.health.to(QUARANTINED, reason)
        self._set_health(rep)
        rep.breaker.open_()
        self._m_breaker.labels(engine=rep.name).inc()
        self._fl.incident("breaker_open", health=self.health(),
                          extra={"engine": rep.name, "why": reason})
        out = []
        if harvest and rep.engine is not None:
            blobs = self._snapshot_for_failover(rep)
            self._stash_prefix_handoff(rep)
            harvested = rep.engine.harvest()
            for req in harvested:
                entry = rep.inflight.pop(req.rid, None)
                if entry is None:
                    continue
                freq, attempt = entry
                if freq.finished:
                    continue
                if self._promote_survivor(freq, attempt):
                    continue    # hedged twin still live elsewhere
                out.extend(self._failover_or_fail(
                    freq, attempt, blobs.get(req.rid)))
            # anything else finished in the same iteration
            out.extend(self._reap_locked(rep))
        return out

    def _snapshot_for_failover(self, rep):
        """Page blobs for every migratable in-flight stream (rid ->
        blob), taken BEFORE harvest frees the pages.  Best-effort:
        anything that cannot snapshot just rides replay."""
        from . import kv_transfer as kvt
        blobs = {}
        eng = rep.engine
        sch = getattr(eng, "scheduler", None)
        if sch is None:
            return blobs
        for req in list(sch.running.values()):
            if not kvt.can_migrate(eng, req):
                continue
            try:
                blobs[req.rid] = kvt.snapshot_request(eng, req)
            except Exception as e:
                self._note_migrate_failure(
                    "failover", req.rid, rep.name, None, e)
        return blobs

    def _stash_prefix_handoff(self, rep):
        """Export the quarantined replica's interned prefix pages; a
        later supervision pass re-interns them on the healthiest
        sibling (outside any replica lock)."""
        from . import kv_transfer as kvt
        try:
            blob = kvt.snapshot_prefix_cache(rep.engine)
        except Exception as e:
            self._note_migrate_failure("prefix", None, rep.name, None, e)
            return
        if blob is not None:
            with self._flock:
                self._prefix_handoffs.append((rep.name, blob))

    def _install_prefix_handoffs(self):
        """Drain stashed prefix-cache blobs into the best live sibling
        that runs a prefix cache (re-parked when none is up yet).  One
        bounded pass: each stashed blob is tried once; blobs stashed
        mid-pass wait for the next supervision tick."""
        from . import kv_transfer as kvt
        with self._flock:
            pending = list(self._prefix_handoffs)
            self._prefix_handoffs.clear()
        for i, (src_name, blob) in enumerate(pending):
            cands = [r for r in self._candidates()
                     if getattr(r.engine, "prefix_cache", None)
                     is not None and r.name != src_name]
            if not cands:
                with self._flock:
                    # re-park this and everything after it, in order,
                    # ahead of anything stashed while we worked
                    self._prefix_handoffs.extendleft(
                        reversed(pending[i:]))
                return
            dst = min(cands, key=lambda r: (self._score(r), r.name))
            try:
                with dst.lock:
                    n = kvt.install_prefix_cache(dst.engine, blob)
            except kvt.TransferError as e:
                self._note_migrate_failure(
                    "prefix", None, src_name, dst.name, e)
                continue
            self.prefix_handoffs_done += n
            if n:
                self._m_migrate_prefix.inc(n)

    def _note_migrate_failure(self, path, rid, src, dst, err):
        self.migration_failures += 1
        self._m_migrate_fail.labels(path=path).inc()
        self._fl.incident(
            "migrate_failed", health=self.health(),
            extra={"path": path, "rid": rid, "from": src, "to": dst,
                   "error": f"{type(err).__name__}: {err}"})

    def _on_crash_locked(self, rep, exc):
        rep.last_error = exc
        self._m_crashes.labels(engine=rep.name).inc()
        self._fl.incident(
            "engine_crash", health=self.health(),
            extra={"engine": rep.name,
                   "error": f"{type(exc).__name__}: {exc}"})
        warnings.warn(
            f"fleet {self.name}: engine {rep.name} crashed with "
            f"{type(exc).__name__}: {exc} — quarantined, in-flight "
            "requests failing over")
        return self._quarantine_locked(
            rep, f"engine crashed: {type(exc).__name__}")

    def _update_ewma(self, rep, attempt):
        a = self.ewma_alpha
        for field, val in (("ttft_ewma", attempt.ttft),
                           ("tpot_ewma", attempt.tpot)):
            if val is None:
                continue
            cur = getattr(rep, field)
            setattr(rep, field,
                    float(val) if cur is None
                    else (1.0 - a) * cur + a * float(val))

    def _finalize(self, freq, reason, cancel_others=False):
        if freq.finished:
            return
        freq._finished = True
        freq._finish_reason = reason
        freq.t_done = self._clock()
        self.completed += 1
        self.finish_counts[reason] = self.finish_counts.get(reason, 0) + 1
        if freq.rid is not None:
            # cluster-level terminal (idempotent over the engine-level
            # finish for healthy completions; the ONLY terminal for
            # requests that died in the failover queue)
            self._rt.event(freq.rid, "finish", engine=freq.engine,
                           reason=reason, cluster=True,
                           failovers=freq.failovers)
        if cancel_others and freq.hedge_attempt is not None:
            name, att = freq.hedge_attempt
            freq.hedge_attempt = None
            if not att.finished:
                self._cancels.append((name, att.rid))

    def _run_cancels(self):
        """Deferred cross-replica cancels (hedge losers): issued outside
        any other replica's lock to keep lock order acyclic."""
        while self._cancels:
            try:
                name, rid = self._cancels.popleft()
            except IndexError:
                return
            rep = self._by_name(name)
            if rep is None or rep.engine is None:
                continue
            with rep.lock:
                rep.inflight.pop(rid, None)
                rep.engine.cancel(rid)

    # -- failover + supervision --------------------------------------------
    def _queue_failovers(self, items):
        if not items:
            return
        with self._flock:
            self._failover.extend(items)
        if not self.threaded:
            self._dispatch_failovers()

    def _dispatch_failovers(self):
        """Re-home harvested requests: replay their tokens-so-far on the
        best sibling.  Requests that cannot be placed yet stay queued
        (the supervisor retries each pass); expired ones finalize.  One
        bounded pass over the queue snapshot per call."""
        with self._flock:
            pending, self._failover = list(self._failover), deque()
        for i, (freq, tokens, blob) in enumerate(pending):
            if freq.finished:
                continue
            now = self._clock()
            if freq.deadline is not None and now >= freq.deadline:
                self._finalize(freq, "deadline")
                continue
            # page migration first: splice the donor's snapshot into a
            # sibling's pool and the stream continues without replaying
            # a single token.  ANY transfer failure falls through to
            # replay — migration can only ever improve on it.
            if blob is not None and self._resume_from_blob(freq, blob):
                continue
            try:
                self._place(freq, now=now,
                            prefer_not=(freq.engines[-1]
                                        if freq.engines else None),
                            replay=tokens or None,
                            count_unavailable=False)
            except (EngineOverloaded, FleetUnavailable):
                # no home right now: park this and everything behind it
                # (order preserved) until capacity or a restart returns
                with self._flock:
                    self._failover.extendleft(reversed(pending[i:]))
                return
            self.failovers_done += 1
            self._m_failovers.inc()
            # the stitching seam: same cluster rid continues on the
            # sibling that _place just chose, replaying tokens-so-far
            self._rt.event(
                freq.rid, "failover_replay",
                engine=freq.engines[-1] if freq.engines else None,
                replayed=len(tokens),
                from_engine=(freq.engines[-2]
                             if len(freq.engines) > 1 else None))

    def _can_adopt(self, rep):
        """A migration target needs a FREE slot right now (adoption
        cannot queue the way replay-submit can) on a paged engine
        without a ModelDraft."""
        eng = rep.engine
        return (eng is not None and getattr(eng, "_paged", False)
                and eng._draft is None
                and len(eng.scheduler.running) < eng.cache.n_slots)

    def _resume_from_blob(self, freq, blob):
        """Try to re-home a harvested stream by splicing its page blob
        into the best sibling.  True on success; False (after counting
        the failure) sends the caller down the replay path.  The whole
        attempt — choose, wire, splice — runs under the ``kv_migrate``
        span either way: a dropped transfer spent its wire time too, and
        the goodput ledger's kv_migration bucket must see it."""
        from . import kv_transfer as kvt
        with self._tr.span("kv_migrate"):
            return self._resume_from_blob_inner(freq, blob, kvt)

    def _resume_from_blob_inner(self, freq, blob, kvt):
        last = freq.engines[-1] if freq.engines else None
        full = {r.name for r in self._replicas
                if not self._can_adopt(r)}
        rep = self._choose(prefer_not=last, exclude=full,
                           roles=(("decode", "mixed") if self._has_roles
                                  else None))
        if rep is None:
            return False    # nobody can adopt NOW: replay can queue
        self._m_migrations.labels(path="failover").inc()
        try:
            filt = self.transfer_filter
            wired = blob if filt is None else filt(blob)
            if wired is None:
                raise kvt.TransferError("transfer dropped in flight")
            with rep.lock:
                att = kvt.resume_request(rep.engine, wired,
                                         stream=self._wrap_stream(freq))
                rep.inflight[att.rid] = (freq, att)
                rep.dispatches += 1
                freq.attempt = att
                freq.engine = rep.name
        except kvt.TransferError as e:
            self._note_migrate_failure(
                "failover", freq.rid, last, rep.name, e)
            return False
        freq.engines.append(rep.name)
        self._m_dispatch.labels(engine=rep.name).inc()
        self.migrations_done += 1
        self._m_migrate_bytes.inc(len(blob))
        self.failovers_done += 1
        self._m_failovers.inc()
        self._rt.event(freq.rid, "migrated", engine=rep.name,
                       path="failover", bytes=len(blob),
                       from_engine=last)
        return True

    # -- live migration (both replicas up) ----------------------------------
    def _migrate_attempt(self, src, freq, att, dst, path):
        """Live-migrate one running stream from ``src`` to ``dst``:
        snapshot under the donor lock (the donor cannot step past the
        snapshot), splice into the receiver, rebind the stream fence,
        then ack the donor (which frees its pages).  Serialized
        fleet-wide by ``_migrate_lock`` so two replicas never migrate
        toward each other with crossed locks.  Returns True on success;
        on ANY transfer failure the stream stays on the donor untouched
        — migrating is strictly no worse than not migrating."""
        from . import kv_transfer as kvt
        if dst is None or dst is src:
            return False
        # "kv_migrate" span: snapshot + wire + splice + ack, including
        # the fleet-wide serialization wait — the goodput ledger's
        # kv_migration bucket (failed attempts count too: their time
        # was spent either way)
        with self._tr.span("kv_migrate"), self._migrate_lock:
            with src.lock:
                if src.engine is None or dst.engine is None:
                    return False
                if (freq.finished or freq.attempt is not att
                        or att.finished
                        or freq.hedge_attempt is not None
                        or not kvt.can_migrate(src.engine, att)):
                    return False
                self._m_migrations.labels(path=path).inc()
                try:
                    blob = kvt.snapshot_request(src.engine, att)
                    filt = self.transfer_filter
                    wired = blob if filt is None else filt(blob)
                    if wired is None:
                        raise kvt.TransferError(
                            "transfer dropped in flight")
                    with dst.lock:
                        new = kvt.resume_request(
                            dst.engine, wired,
                            stream=self._wrap_stream(freq))
                        dst.inflight[new.rid] = (freq, new)
                        dst.dispatches += 1
                        # rebind INSIDE the receiver lock: the stream
                        # fence flips to the new attempt before the
                        # receiver can deliver a single token
                        freq.attempt = new
                        freq.engine = dst.name
                except kvt.TransferError as e:
                    self._note_migrate_failure(
                        path, freq.rid, src.name, dst.name, e)
                    return False
                # donor ack: only now does the donor free its side —
                # the receiver already owns the adopted stream
                src.inflight.pop(freq.rid, None)
                src.engine.release_migrated(freq.rid)
        freq.engines.append(dst.name)
        self.migrations_done += 1
        self._m_migrate_bytes.inc(len(blob))
        self._m_dispatch.labels(engine=dst.name).inc()
        self._rt.event(freq.rid, "migrated", engine=dst.name,
                       path=path, bytes=len(blob),
                       from_engine=src.name)
        return True

    def migrate_out(self, name, path="drain", roles=None):
        """Preemptively move every migratable stream off ``name`` onto
        siblings (scale-down, maintenance: migrate-then-drain).
        Returns the number moved; whatever cannot move simply stays and
        drains normally — no stream is ever worse off for the try."""
        rep = self._by_name(name, required=True)
        if rep.engine is None:
            return 0
        if roles is None and self._has_roles:
            roles = ("decode", "mixed")
        moved = 0
        for rid, (freq, att) in list(rep.inflight.items()):
            if freq.finished or att.finished \
                    or freq.attempt is not att:
                continue
            full = {r.name for r in self._replicas
                    if not self._can_adopt(r)}
            dst = self._choose(exclude={rep.name} | full, roles=roles)
            if dst is None:
                break
            if self._migrate_attempt(rep, freq, att, dst, path):
                moved += 1
        return moved

    def rebalance(self, src, dst=None, max_requests=1,
                  path="rebalance"):
        """Move up to ``max_requests`` running decode streams off the
        ``src`` replica onto ``dst`` (or the best-scored sibling) — the
        SLO controller calls this to shed load from a hot replica
        without restarting anything.  Returns the number moved."""
        s = self._by_name(src, required=True)
        if s.engine is None:
            return 0
        moved = 0
        for rid, (freq, att) in list(s.inflight.items()):
            if moved >= int(max_requests):
                break
            if freq.finished or att.finished \
                    or freq.attempt is not att:
                continue
            full = {r.name for r in self._replicas
                    if not self._can_adopt(r)}
            d = (self._by_name(dst, required=True) if dst is not None
                 else self._choose(exclude={s.name} | full))
            if d is None or not self._can_adopt(d) \
                    or not d.health.dispatchable:
                break
            if self._migrate_attempt(s, freq, att, d, path):
                moved += 1
        return moved

    def _migration_pass(self):
        """Disaggregation pass (role fleets only): any decode stream
        still running on a prefill-role replica is handed off to a
        decode/mixed sibling as soon as one can take it — prefill
        replicas stay free to absorb new prompts, decode replicas own
        the long tail.  Runs every supervision pass / pump."""
        if not self._has_roles:
            return
        for rep in list(self._replicas):
            if rep.role != "prefill" or rep.engine is None \
                    or rep.health.state not in (HEALTHY, DEGRADED):
                continue
            self._handoff_from(rep)

    def _handoff_from(self, rep):
        for rid, (freq, att) in list(rep.inflight.items()):
            if freq.finished or att.finished \
                    or freq.attempt is not att:
                continue
            # strict: a role-pure handoff with no decode sibling up
            # should just not happen (keep decoding here), not bounce
            # to another prefill replica
            full = {r.name for r in self._replicas
                    if not self._can_adopt(r)}
            dst = self._choose(roles=("decode", "mixed"),
                               exclude={rep.name} | full,
                               strict_roles=True)
            if dst is None:
                return
            if self._migrate_attempt(rep, freq, att, dst, "handoff"):
                self._m_handoffs.inc()

    def _supervise_loop(self):
        while self._running:
            try:
                self._supervise_once()
            except Exception as e:      # supervisor must never die
                warnings.warn(
                    f"fleet {self.name}: supervisor error "
                    f"{type(e).__name__}: {e}")
            time.sleep(self.supervise_interval)

    def effective_wedge_timeout(self, rep=None):
        """The heartbeat-staleness bound that counts as a wedge.  An
        explicit ``wedge_timeout=`` kwarg is absolute; by default the
        bound is derived from the replica's OBSERVED decode rate —
        ``max(wedge_floor, wedge_safety × TPOT_EWMA)`` — so detection
        survives real TPU step times instead of assuming 5 s ≫ one
        step.  A replica with no TPOT yet borrows the slowest sibling's
        (conservative: slow siblings imply slow steps here too) and
        falls back to the floor before any EWMA exists."""
        if self.wedge_timeout is not None:
            return self.wedge_timeout
        tpot = rep.tpot_ewma if rep is not None else None
        if not tpot:
            known = [r.tpot_ewma for r in self._replicas if r.tpot_ewma]
            tpot = max(known) if known else 0.0
        return max(self.wedge_floor, self.wedge_safety * tpot)

    def _compiling(self, rep):
        """True while the replica's engine is inside, or less than one
        wedge bound past, the first call of a program variant: that
        call may trace and compile, which takes tens of seconds at
        published widths on a chip and is not a wedge.  Only the derived
        bound waits for it; an explicit ``wedge_timeout=`` stays absolute.
        The price: a hang inside a first call is left to the caller's own
        timeout."""
        eng = rep.engine
        if self.wedge_timeout is not None or eng is None:
            return False
        if getattr(eng, "cold_dispatch", None) is not None:
            return True
        return (time.perf_counter()
                - getattr(eng, "cold_until", float("-inf"))
                ) < self.effective_wedge_timeout(rep)

    def _supervise_once(self):
        """One supervision pass: wedge detection (threaded only),
        breaker-gated restarts, failover dispatch, deferred cancels."""
        now = self._clock()
        for rep in list(self._replicas):
            if (self.threaded and rep.thread is not None
                    and rep.thread.is_alive()
                    and rep.health.state in (HEALTHY, DEGRADED)
                    and rep.engine is not None
                    and not rep.engine.scheduler.idle
                    and rep.health.heartbeat_age(now)
                    > self.effective_wedge_timeout(rep)
                    and not self._compiling(rep)):
                self._on_wedge(rep, rep.health.heartbeat_age(now))
            if (rep.health.state == QUARANTINED and self.auto_restart
                    and rep.breaker.allow(now)):
                self.restart(rep.name)
        self._migration_pass()
        self._install_prefix_handoffs()
        self._dispatch_failovers()
        self._run_cancels()

    def _on_wedge(self, rep, age):
        """A driver stuck inside ``step()`` (hung device call, stalled
        callback): fence it, harvest from a SNAPSHOT (the zombie holds
        the lock, so no clean retire — the engine is abandoned and
        replaced at restart), fail the requests over."""
        rep.generation += 1         # zombie exits when step returns
        self._m_wedges.labels(engine=rep.name).inc()
        self._fl.incident(
            "engine_wedge", health=self.health(),
            extra={"engine": rep.name, "heartbeat_age_s": round(age, 4)})
        warnings.warn(
            f"fleet {self.name}: engine {rep.name} heartbeat stale "
            f"{age:.2f}s — wedged; quarantining and failing over")
        inflight, rep.inflight = rep.inflight, {}
        out = []
        for rid, (freq, attempt) in inflight.items():
            if freq.finished:
                continue
            if self._promote_survivor(freq, attempt):
                continue
            # no clean engine-side harvest exists (the zombie driver
            # owns the engine) — mark the seam from the fleet side
            self._rt.event(rid, "harvested", engine=rep.name,
                           why="wedge")
            out.extend(self._failover_or_fail(freq, attempt))
        # lockless state flip: the zombie only touches the engine, and
        # every post-step path re-checks the generation fence
        rep.health.to(QUARANTINED, f"heartbeat stale {age:.2f}s")
        self._set_health(rep)
        rep.breaker.open_()
        self._m_breaker.labels(engine=rep.name).inc()
        rep.engine = None           # abandoned with the zombie
        self._queue_failovers(out)

    # -- restart / drain ---------------------------------------------------
    def restart(self, name):
        """Supervised restart: fence any old driver, rebuild the engine
        (fresh KV pool; the compile-once program cache is shared, so no
        retrace), and return the replica to HEALTHY.  The breaker keeps
        its failure streak until the replica proves itself with clean
        ticks — a crash loop backs off exponentially."""
        rep = self._by_name(name, required=True)
        if rep.inflight and rep.engine is not None \
                and rep.health.state not in (QUARANTINED, RESTARTING):
            # operator restart of a LIVE replica: fail its work over
            # first (an imposed quarantine), never drop bookkeeping
            with rep.lock:
                actions = self._quarantine_locked(rep,
                                                  "operator restart")
            self._queue_failovers(actions)
        rep.generation += 1
        rep.health.to(RESTARTING, "supervised restart")
        self._set_health(rep)
        rep.incarnation += 1
        # a wedged zombie may hold the old lock forever: new lock too
        rep.lock = threading.RLock()
        rep.engine = self._build_engine(rep.index, rep.incarnation)
        rep.last_trips = 0
        rep.inflight = {}
        rep.health.to(HEALTHY, "restarted")
        self._set_health(rep)
        self._m_restarts.labels(engine=rep.name).inc()
        if self.threaded and self._running:
            self._start_driver(rep)
        return rep.name

    def drain(self, name=None, wait=True, timeout=60.0, migrate=False):
        """Stop dispatching to the replica(s) but finish what they hold;
        DRAINING flips to STOPPED at idle.  ``wait=True`` blocks (or
        pumps, when ``threaded=False``) until drained.
        ``migrate=True`` first live-migrates every migratable decode
        stream to a sibling (scale-down: the long decode tail moves NOW
        instead of being waited out), then drains whatever remains."""
        reps = ([self._by_name(name, required=True)] if name is not None
                else list(self._replicas))
        for rep in reps:
            if rep.health.state in (QUARANTINED, RESTARTING, STOPPED):
                continue
            rep.health.to(DRAINING, "drain requested")
            self._set_health(rep)
            self._m_drains.labels(engine=rep.name).inc()
            if migrate:
                # flip DRAINING first (no new work lands mid-migration),
                # then move the tail; non-migratable streams just drain
                self.migrate_out(rep.name, path="drain")
        if wait:
            self._wait_for(
                lambda: all(r.health.state != DRAINING for r in reps),
                timeout, "drain")
        return self

    def rolling_restart(self, timeout=60.0):
        """Zero-accepted-loss rolling restart: drain each replica in
        turn (siblings keep serving), restart it, move on."""
        for rep in list(self._replicas):
            self.drain(rep.name, wait=True, timeout=timeout)
            self.restart(rep.name)
        return self

    # -- pumping / waiting -------------------------------------------------
    def pump(self, iterations=1):
        """Deterministic manual drive (``threaded=False`` fleets): one
        tick per replica per iteration, then one supervision pass.

        Each tick is bounded by the same wedge check the threaded
        supervisor runs: a step that stalls past
        :meth:`effective_wedge_timeout` has, by the time the pump loop
        regains control, already blocked the caller — it cannot be
        pre-empted from inside one thread, but it IS reported (wedge
        metric + incident) and the replica is quarantined + failed
        over instead of silently degrading every later iteration."""
        if self.threaded:
            raise RuntimeError(
                "pump() drives threaded=False fleets; this one runs "
                "driver threads")
        for _ in range(int(iterations)):
            for rep in list(self._replicas):
                busy = (rep.health.state in (HEALTHY, DEGRADED)
                        and rep.engine is not None
                        and not rep.engine.scheduler.idle)
                t0 = self._clock()
                if busy:
                    # arm the dispatch watcher BEFORE the tick: if this
                    # step wedges inside the device call, the caller is
                    # stuck and cannot report it — the watcher thread
                    # quarantines + fails over from the side instead
                    bound = self.effective_wedge_timeout(rep)
                    self._ensure_watcher()
                    self._watch_armed = (rep, rep.generation,
                                         time.perf_counter() + bound,
                                         bound)
                try:
                    self._tick(rep)
                finally:
                    self._watch_armed = None
                dur = self._clock() - t0
                if busy and dur > self.effective_wedge_timeout(rep) \
                        and rep.health.state in (HEALTHY, DEGRADED) \
                        and rep.engine is not None \
                        and not self._compiling(rep):
                    self._on_pump_stall(rep, dur)
            self._supervise_once()
        return self

    def _ensure_watcher(self):
        """Lazy dispatch watcher for manual (``threaded=False``)
        fleets: the pump loop arms a deadline before every busy tick,
        so a step that wedges INSIDE the dispatch is detected while the
        pumping caller is still stuck — the manual-mode mirror of the
        threaded supervisor's heartbeat check.  One daemon thread per
        fleet, started on first use, joined at stop()."""
        t = self._watch_thread
        if t is not None and t.is_alive():
            return
        t = threading.Thread(target=self._watch_loop,
                             name=f"{self.name}-dispatch-watch",
                             daemon=True)
        self._watch_thread = t
        t.start()

    def _watch_loop(self):
        # wall-clock on purpose: a ManualClock fleet still wedges in
        # real time, and the stuck caller cannot advance any clock
        while self._running:
            armed = self._watch_armed
            if armed is not None:
                rep, gen, deadline, bound = armed
                if (time.perf_counter() >= deadline
                        and rep.generation == gen
                        and self._watch_armed is armed
                        and not self._compiling(rep)):
                    self._watch_armed = None
                    try:
                        self._on_dispatch_wedge(rep, gen, bound)
                    except Exception as e:   # watcher must never die
                        warnings.warn(
                            f"fleet {self.name}: dispatch watcher "
                            f"error {type(e).__name__}: {e}")
            time.sleep(min(self.supervise_interval, 0.005))

    def _on_dispatch_wedge(self, rep, gen, bound):
        """An armed pump tick blew past its wedge bound with the caller
        still stuck inside the dispatch: same fencing as a threaded
        wedge (:meth:`_on_wedge`), run from the watcher thread, tagged
        ``mode="dispatch"`` so operators can tell the two apart."""
        if rep.generation != gen \
                or rep.health.state not in (HEALTHY, DEGRADED):
            return
        rep.generation += 1     # fence: the stuck tick discards itself
        self._m_wedges.labels(engine=rep.name).inc()
        self._fl.incident(
            "engine_wedge", health=self.health(),
            extra={"engine": rep.name, "mode": "dispatch",
                   "wedge_timeout_s": round(bound, 4)})
        warnings.warn(
            f"fleet {self.name}: engine {rep.name} dispatch stuck past "
            f"{bound:.2f}s — wedged; quarantining and failing over")
        inflight, rep.inflight = rep.inflight, {}
        out = []
        for rid, (freq, attempt) in inflight.items():
            if freq.finished:
                continue
            if self._promote_survivor(freq, attempt):
                continue
            # the zombie dispatch owns the engine (and its pool): no
            # clean harvest, no page snapshot — replay is the seam
            self._rt.event(rid, "harvested", engine=rep.name,
                           why="wedge")
            out.extend(self._failover_or_fail(freq, attempt))
        rep.health.to(QUARANTINED,
                      f"dispatch stuck past {bound:.2f}s")
        self._set_health(rep)
        rep.breaker.open_()
        self._m_breaker.labels(engine=rep.name).inc()
        rep.engine = None           # abandoned with the stuck call
        self._queue_failovers(out)

    def _on_pump_stall(self, rep, dur):
        """A manual-mode tick stalled past the wedge bound.  Unlike a
        threaded wedge the step RETURNED (nobody holds the engine), so
        the replica is quarantined through the clean harvest path and
        its work failed over; auto_restart revives it through the
        breaker like any other quarantine."""
        self._m_wedges.labels(engine=rep.name).inc()
        self._fl.incident(
            "engine_wedge", health=self.health(),
            extra={"engine": rep.name, "stalled_step_s": round(dur, 4),
                   "mode": "pump"})
        warnings.warn(
            f"fleet {self.name}: engine {rep.name} pump tick stalled "
            f"{dur:.2f}s — wedged; quarantining and failing over")
        with rep.lock:
            actions = self._quarantine_locked(
                rep, f"pump tick stalled {dur:.2f}s")
        self._queue_failovers(actions)

    def _reap_all(self):
        """Manual-mode bookkeeping sweep without stepping engines."""
        for rep in self._replicas:
            if rep.engine is None:
                continue
            with rep.lock:
                actions = self._reap_locked(rep)
            self._queue_failovers(actions)

    @property
    def idle(self):
        with self._flock:
            if self._failover:
                return False
        for rep in self._replicas:
            if rep.health.state in (QUARANTINED, RESTARTING):
                continue        # harvested; nothing of ours runs there
            if rep.engine is not None \
                    and not rep.engine.scheduler.idle:
                return False
        return True

    def _wait_for(self, cond, timeout, what):
        if not self.threaded:
            it = 0
            while not cond():
                if it >= 100000:
                    raise RuntimeError(
                        f"fleet {what} did not complete in {it} pumps")
                self.pump()
                it += 1
            return
        deadline = time.perf_counter() + timeout
        while not cond():
            if time.perf_counter() >= deadline:
                raise TimeoutError(
                    f"fleet {what} did not complete within {timeout}s")
            time.sleep(self.idle_sleep)

    def wait(self, reqs=None, timeout=60.0):
        """Block (threaded) or pump (manual) until ``reqs`` (default:
        every accepted request) all reach a terminal finish_reason."""
        reqs = list(self._requests.values()) if reqs is None else reqs
        self._wait_for(lambda: all(r.finished for r in reqs), timeout,
                       "wait")
        return reqs

    def generate_many(self, prompts, max_new, eos_id=None, timeout=60.0):
        """Synchronous batch API across the fleet."""
        reqs = [self.submit(p, max_new, eos_id=eos_id) for p in prompts]
        self.wait(reqs, timeout=timeout)
        return [r.result() for r in reqs]

    # -- introspection -----------------------------------------------------
    def _by_name(self, name, required=False):
        for rep in self._replicas:
            if rep.name == name:
                return rep
        if required:
            raise KeyError(f"no replica named {name!r}")
        return None

    def health(self):
        """{engine: health snapshot} for every replica."""
        return {r.name: r.health.snapshot() for r in self._replicas}

    def audit(self):
        """Per-replica slot audit of every LIVE engine (a wedged
        engine's pool is abandoned with it and replaced at restart)."""
        return {r.name: r.engine.cache.audit()
                for r in self._replicas if r.engine is not None}

    def trace_counts(self):
        """The shared compile-once witness (max over live replicas —
        they share the program cache, so these are the same entry)."""
        out = {}
        for r in self._replicas:
            if r.engine is None:
                continue
            for k, v in r.engine.trace_counts.items():
                out[k] = max(out.get(k, 0), v)
        return out

    def stats(self):
        with self._flock:
            pending = len(self._failover)
        reasons = {}
        for freq in self._requests.values():
            if freq.finished:
                reasons[freq.finish_reason] = \
                    reasons.get(freq.finish_reason, 0) + 1
        return {
            "n_engines": len(self._replicas),
            "tp_size": self.tp_size,
            "submitted": self.submitted,
            "completed": self.completed,
            "failovers": self.failovers_done,
            "migrations": self.migrations_done,
            "migration_failures": self.migration_failures,
            "prefix_handoffs": self.prefix_handoffs_done,
            "hedged": self.hedged,
            "hedges_skipped": self.hedges_skipped,
            "pending_failovers": pending,
            "finish_reasons": reasons,
            "trace_counts": self.trace_counts(),
            "engines": {
                r.name: {
                    "state": r.health.state,
                    "role": r.role,
                    "incarnation": r.incarnation,
                    "dispatches": r.dispatches,
                    "ttft_ewma": r.ttft_ewma,
                    "tpot_ewma": r.tpot_ewma,
                    "breaker_opens": r.breaker.opens,
                    "breaker_failures": r.breaker.failures,
                    "engine": (None if r.engine is None
                               else r.engine.stats()),
                } for r in self._replicas},
        }
