"""TPU-native continuous-batching inference engine.

``InferenceEngine`` wraps an Executor-trained (or HF-imported) decode
model into exactly TWO jitted programs whose shapes never change:

* ``prefill(params, k, v, prompt [1, P], p_len, slot, key)`` — run one
  prompt (padded to the fixed bucket P = ``max_prompt_len``) through all
  layers, deposit its K/V into ``slot`` of the pooled cache, and emit
  the request's first token from the true last prompt row;
* ``step(params, k, v, tokens [S], positions [S], active [S], key)`` —
  ONE decode iteration for every slot at once, each slot at its own
  position (adapters.py vmaps the per-layer block over slots).  Inactive
  slots compute masked garbage — the price of a static shape — and
  their outputs are discarded host-side.

``paged=True`` swaps the dense ``SlotKVCache`` for a
:class:`~.kv_cache.PagedKVCache` (fixed page pool + per-slot block
tables) and rebuilds both programs around an in-graph page gather:

* decode gains block-table + per-slot sampling operands
  (``tables [S, max_pages]``, ``temps/top_ks/seeds [S]``) — slot
  capacity and sampling become data, not compile-time constants, so
  the compile-once contract is untouched by request mix;
* prefill becomes BATCHED and CHUNKED: every admitted prompt chunk in
  one padded ``[B, C]`` call (both axes pow2-bucketed to bound compile
  variants), long prompts split across iterations under
  ``prefill_token_budget`` so decode interleaves between chunks
  instead of stalling behind one long prompt.

Sampling keys derive in-graph from ``fold_in(fold_in(key(0), seed),
consumed)`` — per request, not per engine — so a sampled stream at a
fixed seed is deterministic and continues bit-exactly through fleet
failover replay.  Greedy lanes run the identical argmax as the slot
engine: the paged twin's greedy streams are bitwise equal to the
dense twin's (the serve bench asserts it).

Both programs also return a FINITENESS SENTINEL computed in-graph (the
StepGuard idea from the training path, re-hosted per slot): ``prefill``
returns one ok scalar for its logits row, ``step`` returns a per-slot
ok vector.  The sentinel rides the same fusion as the logits reduction,
so the protected and unprotected engines run the SAME executable — the
watchdog is a host-side decision about what to do with the bit, not a
different program.

Failure surface (all enabled by default, see the ctor):

* **admission control** — ``max_queue`` bounds the waiting line;
  ``submit`` raises :class:`~.scheduler.EngineOverloaded` (with a
  queue-depth hint) once the high watermark is hit, reopening at the
  low watermark (scheduler.py documents the shed policies);
* **deadlines** — ``submit(..., ttl=)`` / ``deadline=`` attaches a TTL
  checked at admission and once per iteration; an expired request
  frees its KV slot immediately mid-flight and finishes with
  ``finish_reason="deadline"`` carrying its partial tokens;
* **cancellation** — ``cancel(rid)`` removes a queued request or
  retires a running one mid-flight (``finish_reason="cancelled"``,
  partial tokens, slot freed on the spot);
* **decode watchdog** — a slot whose logits go non-finite (poisoned
  KV, overflowed activation) is QUARANTINED: retired with
  ``finish_reason="error"``, its slot reclaimed, the other slots'
  token streams untouched — the engine loop survives the fault the
  way the training path survives a NaN batch.  A RAISING jitted step
  cannot be attributed to one slot, so it retires everything in
  flight with "error" and keeps the engine alive for new work.
  ``watchdog=False`` builds the unprotected twin the chaos bench
  wedges for contrast;
* **slot-leak reconcile** — any cache slot owned by nobody (a leak,
  however induced) is swept back to the free list each iteration;
* **consumer protection** — a stream callback that raises is detached
  (the request keeps decoding, tokens land in ``result()``); with
  ``stream_stall_timeout`` set, a callback that stalls longer than the
  bound is detached too, so one stuck client can't hold the whole
  batch hostage more than once.

Because every call sees identical shapes, XLA compiles each program
once — and the compiled pair is SHARED across engine instances with the
same (model, sampling) signature, so twins/rebuilds reuse the same
executable (no recompile, and bitwise-identical token streams across
engines — XLA:CPU recompiles of the same program are not bit-stable).
``trace_counts`` exposes the shared retrace counters; the compile-once
test pins them at 1 after warmup.

The scheduler (scheduler.py) interleaves admission-prefill with decode
at iteration granularity, and the slot pool (kv_cache.py) recycles a
retired request's slot on the next iteration.  Per-request TTFT / TPOT /
queue-wait land in ``records`` as plain dicts; summarize with
``hetu_tpu.metrics.request_latency_summary``.

Usage::

    engine = InferenceEngine(ex, model, n_slots=8, max_len=256,
                             max_queue=64)
    outs = engine.generate_many(prompts, max_new=64)      # batch API
    h = engine.submit(prompt, max_new=64, ttl=2.0,
                      stream=lambda tok, req: print(tok)) # callback API
    engine.cancel(h.rid)                                  # mid-flight
    for tok in engine.stream(prompt, max_new=64):         # generator API
        ...
"""

from __future__ import annotations

import contextlib
import time
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from ..models._decode_common import (make_picker, make_slot_picker,
                                     param_prefix, pad_prompts)
from . import sharding as _shd
from .adapters import adapter_for
from .kv_cache import (PagedKVCache, SlotKVCache, ceil_div, gather_pages,
                       scatter_rows)
from .scheduler import Request, Scheduler


def _p2(n):
    """Next power of two >= n (the prefill bucket rounding)."""
    return 1 << max(0, (int(n) - 1).bit_length())


class InferenceEngine:
    """Continuous-batching generation over a slot-pooled KV cache.

    ``gang=True`` degrades scheduling to static batching (admit only
    when every slot is free) — the serve bench's baseline twin; the
    numerics and jitted programs are identical, only admission differs.
    ``watchdog=False`` disables every host-side protection (quarantine,
    exception containment, leak reconcile) — the chaos bench's
    unprotected twin; the jitted programs are still identical.
    """

    def __init__(self, executor, model, n_slots=4, max_len=128,
                 max_prompt_len=None, prefill_budget=2, eos_id=None,
                 temperature=0.0, top_k=0, seed=0, name=None,
                 gang=False, max_queue=None, low_watermark=None,
                 shed_policy="reject_newest", watchdog=True,
                 stream_stall_timeout=None, clock=None, instance=None,
                 latency_buckets=None, device=None, paged=False,
                 page_len=16, n_pages=None, prefill_token_budget=None,
                 mesh=None, spec_k=0, draft=None, draft_layers=None,
                 spec_min_accept=None, spec_probe_every=32,
                 shared_params=None, prefix_cache=None, kv_dtype=None,
                 gather_dtype=None):
        # shared_params (fleet multi-replica-per-chip): a param pytree
        # ALREADY placed on this engine's device — replicas pinned to
        # the same chip pass one placed copy instead of re-uploading
        # per engine (the HBM ledger's pool=params books it once)
        self.params = (executor.params if shared_params is None
                       else shared_params)
        self.instance = None if instance is None else str(instance)
        self.device = device
        self.mesh = mesh
        self._tp = 1
        if mesh is not None:
            # tensor-parallel serving (serving/sharding.py): this engine
            # spans every device of a (replica=1, model=tp) mesh; GSPMD
            # inserts the collectives from the shardings threaded through
            # the paged program pair below
            if not paged:
                raise ValueError(
                    "mesh= (tensor-parallel serving) requires paged=True "
                    "— the sharded executables are the paged pair")
            if device is not None:
                raise ValueError(
                    "pass device= (single-chip pinning) or mesh= "
                    "(tensor-parallel), not both")
            self._tp = _shd.mesh_axis_size(mesh)
        self._rep = None if mesh is None else _shd.replicated(mesh)
        if shared_params is not None and mesh is not None:
            raise ValueError(
                "shared_params is the single-chip replica-sharing path; "
                "mesh engines own mesh-placed params (see _shd.shard_params)")
        if device is not None and shared_params is None:
            # fleet replica pinning: park THIS engine's params + cache on
            # one device so N replicas split the chips instead of
            # contending for device 0 (jit follows the operands' device)
            self.params = jax.device_put(self.params, device)
        # -- quantized serving plane (ops/quant.py) -----------------------
        # kv_dtype quantizes the paged pool at rest; gather_dtype
        # quantizes the TP all-gathers.  Both default off, and OFF means
        # bitwise-identical programs to an engine built before these
        # knobs existed (the program key only grows a component when one
        # is set, so default engines keep sharing the same executables).
        self._kv_dtype = None if kv_dtype is None else str(kv_dtype)
        if self._kv_dtype is not None and not paged:
            raise ValueError(
                "kv_dtype (quantized KV pages) requires paged=True — "
                "the dense slot pool has no per-page scale layout")
        self._gather_dtype = (None if gather_dtype is None
                              else str(gather_dtype))
        if self._gather_dtype is not None and mesh is None:
            raise ValueError(
                "gather_dtype (quantized TP gathers) requires mesh= — "
                "a single-chip engine has no cross-shard gathers")
        name = name or param_prefix(
            executor, "_embed_table"
            if hasattr(model.config, "rope_theta") else "_wte_table")
        self.adapter = adapter_for(model, name, mesh=mesh,
                                   gather_dtype=self._gather_dtype)
        if mesh is not None:
            _shd.validate_tp(self.adapter, self._tp)
            # every mesh engine owns a mesh-placed copy of the params —
            # fleet replicas on disjoint sub-meshes must not share one
            self.params = _shd.shard_params(mesh, self.adapter,
                                            self.params)
        cap = self.adapter.position_cap
        if cap is not None and max_len > cap:
            raise ValueError(
                f"max_len={max_len} exceeds the model's learned-position "
                f"table ({cap}); build the model with a longer seq_len")
        self.max_len = int(max_len)
        self.max_prompt_len = int(max_prompt_len or max(1, max_len // 2))
        if self.max_prompt_len > self.max_len:
            raise ValueError(
                f"max_prompt_len={self.max_prompt_len} > max_len="
                f"{self.max_len}")
        emb = self.params[self.adapter.embed_param]
        self._paged = bool(paged)
        if self._paged:
            meshkw = ({} if mesh is None else
                      dict(shards=self._tp,
                           put_sharding=_shd.replicated(mesh)))
            self.cache = PagedKVCache(
                n_slots, self.adapter.layers, self.adapter.kv_heads,
                page_len, self.adapter.head_dim, max_len=self.max_len,
                n_pages=n_pages, dtype=emb.dtype,
                kv_dtype=self._kv_dtype,
                label=self.instance or f"{name}:{id(self):x}", **meshkw)
        else:
            self.cache = SlotKVCache(
                n_slots, self.adapter.layers, self.adapter.kv_heads,
                self.max_len, self.adapter.head_dim, dtype=emb.dtype)
        if device is not None:
            self.cache.k = jax.device_put(self.cache.k, device)
            self.cache.v = jax.device_put(self.cache.v, device)
        elif mesh is not None:
            # the page pool splits kv_heads / tp per chip — the dominant
            # serving HBM saving the mesh buys (pages/slots replicated
            # host-side, so the allocator and block tables are untouched)
            kvsh = _shd.kv_sharding(mesh)
            self.cache.k = jax.device_put(self.cache.k, kvsh)
            self.cache.v = jax.device_put(self.cache.v, kvsh)
        if prefill_token_budget is not None:
            prefill_token_budget = int(prefill_token_budget)
            if prefill_token_budget < 1:
                raise ValueError(
                    f"prefill_token_budget must be >= 1, got "
                    f"{prefill_token_budget}")
            if not self._paged:
                raise ValueError(
                    "prefill_token_budget requires paged=True (the slot "
                    "engine prefills whole prompts)")
        self.prefill_token_budget = prefill_token_budget
        # -- speculative decoding (serving/speculative.py) ----------------
        spec_k = int(spec_k)
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k and not self._paged:
            raise ValueError(
                "spec_k (speculative decoding) requires paged=True — the "
                "verify program is the paged step widened to a window")
        self._spec_k = spec_k
        self._draft = None           # ModelDraft instance (or None)
        self._draft_layers = 0       # SelfDraft depth (0 = model draft)
        if spec_k:
            from . import speculative as _spec
            if draft is None:
                draft = _spec.SelfDraft(draft_layers)
            elif callable(draft) and not hasattr(draft, "kind"):
                draft = draft()      # factory: each replica gets its own
            if draft.kind == "self":
                dl = draft.layers
                if dl is None:
                    dl = (int(draft_layers) if draft_layers is not None
                          else max(1, self.adapter.layers // 2))
                if not 1 <= dl <= self.adapter.layers:
                    raise ValueError(
                        f"draft_layers={dl} outside [1, "
                        f"{self.adapter.layers}]")
                self._draft_layers = int(dl)
            else:
                if mesh is not None:
                    raise ValueError(
                        "ModelDraft is single-chip only; mesh engines "
                        "use the truncated-layer SelfDraft")
                self._draft = draft
        # adaptive gate: fall back to plain decode when the accepted-
        # tokens-per-iteration EWMA sags below spec_min_accept (None =
        # always speculate), re-probing every spec_probe_every plain
        # iterations so recovered acceptance re-enables speculation
        self._spec_min_accept = (None if spec_min_accept is None
                                 else float(spec_min_accept))
        self._spec_probe_every = max(1, int(spec_probe_every))
        self._spec_accept_ewma = float(spec_k + 1)
        self._spec_since_probe = 0
        # -- prefix caching (serving/prefix_cache.py) ---------------------
        self.prefix_cache = None
        if prefix_cache:
            if not self._paged:
                raise ValueError("prefix_cache requires paged=True — a "
                                 "shared prefix is shared PAGES")
            if prefix_cache is True:
                from .prefix_cache import PrefixCache
                self.prefix_cache = PrefixCache(self.cache)
            else:
                if prefix_cache.pool is not self.cache:
                    raise ValueError(
                        "prefix_cache is bound to a different page pool")
                self.prefix_cache = prefix_cache
        # paged prefill batching: lanes per call (B bucket cap) and the
        # chunk-length cap (C bucket cap = the prompt bucket)
        self._lane_cap = min(8, _p2(n_slots))
        self._chunk_cap = _p2(self.max_prompt_len)
        self._prefilling = {}      # slot -> {"req", "start"} mid-chunk
        self._prefill_order = []   # admission order of those slots
        self.scheduler = Scheduler(self.cache,
                                   prefill_budget=prefill_budget,
                                   gang=gang, max_queue=max_queue,
                                   low_watermark=low_watermark,
                                   shed_policy=shed_policy,
                                   rid_prefix=self.instance,
                                   lookahead=spec_k)
        if self.prefix_cache is not None:
            self.scheduler.prefix_lookup = self.prefix_cache.lookup
        self.eos_id = eos_id
        self.watchdog = bool(watchdog)
        self.stream_stall_timeout = (
            None if stream_stall_timeout is None
            else float(stream_stall_timeout))
        self._clock = clock if clock is not None else time.perf_counter
        self._sampling = (float(temperature), int(top_k))
        self._pick = make_picker(temperature, top_k)
        self._key = jax.random.key(seed)
        self._default_seed = int(seed)
        self._last_tokens = np.zeros(n_slots, np.int32)
        # per-slot sampling operands (paged engines thread these through
        # the programs; engine defaults unless submit() overrides)
        self._temps = np.full(n_slots, self._sampling[0], np.float32)
        self._topks = np.full(n_slots, self._sampling[1], np.int32)
        self._seeds = np.full(n_slots, self._default_seed, np.int32)
        # cached device copies of the sampling operands (dropped on
        # admission, the only writer) and of the last active-lane mask:
        # both change at request boundaries but are decode operands
        # EVERY step, and per-step upload dispatch dwarfs the compiled
        # step itself at serving batch sizes
        self._dev_sampling = None
        self._dev_active = (None, None)
        # per-request latency records + per-iteration occupancy log
        # (the per-request API; the registry mirrors below are the LIVE
        # surface — same numbers, scrapeable mid-run via /metrics)
        self.records = []
        self.occupancy = []
        self.decode_steps = 0
        self.prefills = 0
        self.prefill_chunks = 0
        self.peak_active = 0
        self.peak_live_tokens = 0
        self.cancellations = 0
        self.expirations = 0
        self.watchdog_trips = 0
        self.slot_leaks_reclaimed = 0
        self.streams_detached = 0
        self.replayed_tokens = 0
        self.migrated_in = 0       # streams adopted from a sibling's pages
        self.migrated_out = 0      # streams released to a sibling post-ack
        self.spec_steps = 0        # speculative iterations dispatched
        self.spec_proposed = 0     # draft-origin window candidates
        self.spec_accepted = 0     # of those, accepted by verify
        mode = "gang" if gang else "continuous"
        reg = _telemetry.get_registry()
        # per-deployment histogram bucket overrides: real TPU TTFT/TPOT
        # shapes may not fit the default 100us..10s ladder (ROADMAP
        # carry-over).  The registry caches instruments by NAME and
        # rejects a bucket mismatch, so every engine in one process must
        # agree on the ladder — pass the same latency_buckets to each
        # (EngineFleet threads one value through all replicas).
        hkw = ({} if latency_buckets is None
               else {"buckets": tuple(latency_buckets)})

        def _m(kind, name, help, **kw):
            return getattr(reg, kind)(name, help, labels=("scheduler",),
                                      **kw).labels(scheduler=mode)

        self._m_occ = _m("gauge", "hetu_serving_slot_occupancy",
                         "Active-slot fraction of the last decode "
                         "iteration")
        self._m_tokens = _m("counter", "hetu_serving_tokens_total",
                            "Generated tokens emitted")
        self._m_prefill_iters = _m(
            "counter", "hetu_serving_prefill_total",
            "Prompt prefills run (admissions)")
        self._m_decode_iters = _m(
            "counter", "hetu_serving_decode_iterations_total",
            "Slot-batched decode iterations run")
        self._m_finished = _m("counter", "hetu_serving_requests_total",
                              "Requests retired (any finish_reason)")
        self._m_cancelled = _m(
            "counter", "hetu_serving_cancellations_total",
            "Requests cancelled via engine.cancel (queued or running)")
        self._m_expired = _m(
            "counter", "hetu_serving_deadline_expired_total",
            "Requests retired because their deadline passed")
        self._m_watchdog = _m(
            "counter", "hetu_serving_watchdog_trips_total",
            "Decode watchdog quarantines (non-finite logits or a "
            "raising step)")
        self._m_leaks = _m(
            "counter", "hetu_serving_slot_leaks_reclaimed_total",
            "Orphaned KV slots swept back to the free list")
        self._m_detached = _m(
            "counter", "hetu_serving_streams_detached_total",
            "Stream callbacks detached (raised or stalled past the "
            "bound)")
        self._m_replayed = _m(
            "counter", "hetu_serving_replayed_tokens_total",
            "Tokens teacher-forced during failover replay (rebuilt, "
            "never re-emitted)")
        self._m_migrated_in = _m(
            "counter", "hetu_serving_migrated_in_total",
            "Decode streams adopted mid-flight from a sibling's "
            "exported KV pages")
        self._m_migrated_out = _m(
            "counter", "hetu_serving_migrated_out_total",
            "Decode streams released after a sibling acked adoption "
            "of their KV pages")
        self._m_spec_proposed = _m(
            "counter", "hetu_serving_spec_proposed_total",
            "Draft tokens proposed into speculative verify windows")
        self._m_spec_accepted = _m(
            "counter", "hetu_serving_spec_accepted_total",
            "Draft-proposed tokens the verify step accepted")
        self._m_ttft = _m("histogram", "hetu_serving_ttft_seconds",
                          "Time to first token (arrival -> first emit)",
                          **hkw)
        self._m_tpot = _m("histogram", "hetu_serving_tpot_seconds",
                          "Mean time per output token after the first",
                          **hkw)
        self._m_qwait = _m("histogram", "hetu_serving_queue_wait_seconds",
                           "Arrival -> slot admission wait", **hkw)
        if mesh is not None:
            minst = self.instance or name
            reg.gauge(
                "hetu_mesh_tp_size",
                "Model-axis (tensor-parallel) degree of the engine's "
                "serving mesh",
                labels=("engine",)).labels(engine=minst).set(self._tp)
            reg.gauge(
                "hetu_mesh_kv_per_chip_bytes",
                "Bytes of the sharded KV page pool resident per chip",
                labels=("engine",)).labels(engine=minst).set(
                _shd.per_chip_bytes((self.cache.k, self.cache.v)))
            reg.gauge(
                "hetu_mesh_param_per_chip_bytes",
                "Bytes of the engine's (partially sharded) params "
                "resident per chip",
                labels=("engine",)).labels(engine=minst).set(
                _shd.per_chip_bytes(self.params))
        self._tr = _telemetry.get_tracer()
        self._rt = _telemetry.get_request_trace()
        self._fl = _telemetry.get_flight()
        self._verify_fn = None
        self._draft_fn = None
        self._spec_traces = {}
        # cold dispatches: the first call of each program variant on this
        # engine may trace and compile, for tens of seconds at published
        # widths on a chip.  A supervisor (fleet.py) reads these two to
        # tell a compiling replica from a wedged one.
        self._warm = set()
        self.cold_dispatch = None   # tag of the cold call in flight
        self.cold_until = 0.0       # perf_counter() when the last ended
        self._build()
        if self._spec_k:
            self._build_spec()
            if self._draft is not None:
                self._draft.attach(self)

    # -- jitted programs ---------------------------------------------------
    # ONE compiled (prefill, step) pair per (adapter signature, sampling)
    # in the process, shared across engine instances.  Two reasons:
    # * the gang twin and any engine rebuild reuse the executable
    #   instead of recompiling it (the serve bench builds two engines);
    # * XLA:CPU compilation is not bitwise-reproducible across compiles
    #   of the same program in one process (observed: near-tie argmax
    #   flips between two freshly-built engines on identical inputs,
    #   tier-1 flakes in the serving determinism/twin tests), so "the
    #   twin runs the same programs" must mean the same EXECUTABLE, not
    #   a byte-equivalent recompile.
    # The watchdog sentinel is part of the program for EVERY engine
    # (protected and unprotected alike) for the same reason: the
    # executable must be identical so protection is a host-side choice.
    _PROGRAMS = {}

    def _program_key(self):
        cfg = tuple(sorted((k, repr(v)) for k, v in
                           vars(self.adapter.config).items()))
        # paged and slot programs must NEVER collide in _PROGRAMS (or in
        # the profiler caches keyed off cost_signature): the paged pair
        # has different operand signatures (block tables + sampling
        # vectors) and different cache geometry.  Paged sampling is an
        # OPERAND, so the closure constants drop out of its key; the
        # page geometry takes their place.
        if self._paged:
            sampling = ("operands",)
            geometry = ("paged", self.cache.page_len, self.cache.n_pages,
                        self.cache.max_pages)
            if self.mesh is not None:
                # a mesh engine's executables bake device assignments in
                # via in_shardings; fleet sub-meshes on different device
                # groups (and the single-device twin) must not collide
                geometry = geometry + (
                    ("tp", self._tp) + _shd.device_ids(self.mesh),)
        else:
            sampling = self._sampling
            geometry = ("slot",)
        # quantization components are appended ONLY when the knobs are
        # set: a default f32 engine's key — and therefore its cached
        # executables — is byte-identical to one built before the
        # quantized plane existed (the strictly-opt-in guarantee)
        if self._kv_dtype is not None:
            geometry = geometry + (("kv_dtype", self._kv_dtype),)
        if self._gather_dtype is not None:
            geometry = geometry + (("gather_dtype", self._gather_dtype),)
        return (type(self.adapter).__name__, self.adapter.name, cfg,
                sampling, geometry, jax.default_backend())

    def _build(self):
        if self._paged:
            self._build_paged()
            return
        entry = self._PROGRAMS.get(self._program_key())
        if entry is None:
            adapter, pick = self.adapter, self._pick
            from .. import telemetry as _tel
            retrace = _tel.get_registry().counter(
                "hetu_serving_retraces_total",
                "Times each jitted serving program was traced — >1 "
                "after warmup breaks the compile-once contract",
                labels=("program",))
            traces = {"prefill": 0, "step": 0}

            def prefill(params, k, v, prompt, p_len, slot, key):
                traces["prefill"] += 1     # host-side retrace witness
                retrace.labels(program="prefill").inc()
                logits, kn, vn = adapter.prefill(params, prompt)
                k = jax.lax.dynamic_update_slice(k, kn[None],
                                                 (slot, 0, 0, 0, 0))
                v = jax.lax.dynamic_update_slice(v, vn[None],
                                                 (slot, 0, 0, 0, 0))
                row = jax.lax.dynamic_slice_in_dim(logits, p_len - 1, 1,
                                                   0)
                # watchdog sentinel: finiteness of the row that seeds
                # the request (fuses with the logits reduction)
                ok = jnp.all(jnp.isfinite(row))
                tok = pick(row, key)[0].astype(jnp.int32)
                return k, v, tok, ok

            def step(params, k, v, tokens, positions, active, key):
                traces["step"] += 1        # host-side retrace witness
                retrace.labels(program="step").inc()
                logits, k, v = adapter.decode(params, tokens, positions,
                                              k, v)
                # per-slot watchdog sentinel: a poisoned slot flags ONLY
                # itself (slots attend their own cache rows only), so
                # the host can quarantine it without touching the rest
                slot_ok = jnp.all(jnp.isfinite(logits), axis=-1)
                nxt = pick(logits, key).astype(jnp.int32)
                return k, v, jnp.where(active, nxt, 0), slot_ok

            # donate the cache buffers so the pool is updated in place
            # on accelerator backends (on CPU jax cannot donate; skip
            # the per-call warning)
            donate = () if jax.default_backend() == "cpu" else (1, 2)
            entry = {"prefill": jax.jit(prefill, donate_argnums=donate),
                     "step": jax.jit(step, donate_argnums=donate),
                     "traces": traces}
            self._PROGRAMS[self._program_key()] = entry
        self._prefill_fn = entry["prefill"]
        self._step_fn = entry["step"]
        self._traces = entry["traces"]

    def _build_paged(self):
        """The paged program pair: same math as the slot pair, but both
        programs gather per-slot caches from the page pool through the
        block-table operand, write the new rows back with a scatter
        (inactive/pad lanes routed to sentinel page 0), and sample from
        per-slot operand vectors.  Prefill is batched ``[B, C]`` — one
        jitted callable retracing once per pow2 (B, C) bucket, each
        bucket its own entry in the retrace witness."""
        entry = self._PROGRAMS.get(self._program_key())
        if entry is None:
            adapter = self.adapter
            pick = make_slot_picker()
            from .. import telemetry as _tel
            retrace = _tel.get_registry().counter(
                "hetu_serving_retraces_total",
                "Times each jitted serving program was traced — >1 "
                "after warmup breaks the compile-once contract",
                labels=("program",))
            traces = {"step": 0}

            def prefill(params, k, v, prompts, p_lens, starts,
                        chunk_lens, tables, temps, top_ks, seeds):
                bb, cb = prompts.shape
                tag = f"prefill[{bb}x{cb}]"   # retrace witness per bucket
                traces[tag] = traces.get(tag, 0) + 1
                retrace.labels(program=tag).inc()
                nl, nkv, nd = k.shape[1], k.shape[2], k.shape[4]
                page_len, mp = k.shape[3], tables.shape[1]
                kc = gather_pages(k, tables)
                vc = gather_pages(v, tables)
                # pad the gathered time axis by C so the in-block write
                # at ``start`` never clamps (dynamic_update_slice CLAMPS
                # an out-of-range start, which would silently shift a
                # pad lane's garbage onto valid rows)
                pad = ((0, 0), (0, 0), (0, 0), (0, cb), (0, 0))
                kc, vc = jnp.pad(kc, pad), jnp.pad(vc, pad)
                logits, kc, vc = adapter.prefill_chunk(
                    params, prompts, starts, kc, vc)
                # write-back: chunk rows -> (page, offset); rows past the
                # lane's true chunk length go to sentinel page 0
                rows = starts[:, None] + jnp.arange(cb)[None, :]
                valid = jnp.arange(cb)[None, :] < chunk_lens[:, None]
                pidx = jnp.clip(rows // page_len, 0, mp - 1)
                pages = jnp.where(
                    valid, jnp.take_along_axis(tables, pidx, axis=1), 0)
                offs = rows % page_len
                rix = jnp.clip(rows, 0,
                               kc.shape[3] - 1)[:, None, None, :, None]
                krows = jnp.take_along_axis(kc, rix, axis=3)
                vrows = jnp.take_along_axis(vc, rix, axis=3)
                n = bb * cb
                k = scatter_rows(
                    k, pages.reshape(n), offs.reshape(n),
                    krows.transpose(0, 3, 1, 2, 4).reshape(n, nl, nkv, nd))
                v = scatter_rows(
                    v, pages.reshape(n), offs.reshape(n),
                    vrows.transpose(0, 3, 1, 2, 4).reshape(n, nl, nkv, nd))
                last = jnp.clip(chunk_lens - 1, 0, cb - 1)
                lrow = jnp.take_along_axis(
                    logits, last[:, None, None], axis=1)[:, 0]   # [B, V]
                ok = jnp.all(jnp.isfinite(lrow), axis=-1)
                # sampling key folds the request's consumed count: the
                # first generated token is token p_len of the stream
                tok = pick(lrow, temps, top_ks, seeds,
                           p_lens).astype(jnp.int32)
                return k, v, tok, ok

            def step(params, k, v, tokens, positions, tables, active,
                     temps, top_ks, seeds):
                traces["step"] += 1        # host-side retrace witness
                retrace.labels(program="step").inc()
                page_len, mp = k.shape[3], tables.shape[1]
                kc = gather_pages(k, tables)
                vc = gather_pages(v, tables)
                logits, kc, vc = adapter.decode(params, tokens,
                                                positions, kc, vc)
                slot_ok = jnp.all(jnp.isfinite(logits), axis=-1)
                nxt = pick(logits, temps, top_ks, seeds,
                           positions + 1).astype(jnp.int32)
                pidx = jnp.clip(positions // page_len, 0, mp - 1)
                pages = jnp.where(
                    active,
                    jnp.take_along_axis(tables, pidx[:, None],
                                        axis=1)[:, 0],
                    0)
                offs = positions % page_len
                rix = jnp.clip(positions, 0,
                               kc.shape[3] - 1)[:, None, None, None, None]
                krow = jnp.take_along_axis(kc, rix, axis=3)[:, :, :, 0]
                vrow = jnp.take_along_axis(vc, rix, axis=3)[:, :, :, 0]
                k = scatter_rows(k, pages, offs, krow)
                v = scatter_rows(v, pages, offs, vrow)
                return k, v, jnp.where(active, nxt, 0), slot_ok

            donate = () if jax.default_backend() == "cpu" else (1, 2)
            pjkw, sjkw = {}, {}
            if self.mesh is not None:
                # thread NamedShardings through both programs: params by
                # their layout map, the page pool on kv_heads, every
                # host-built operand (and every token/sentinel output)
                # replicated — XLA inserts the all-gathers at the
                # gather= hook points in the block math
                psh = _shd.param_shardings(self.mesh, adapter,
                                           self.params)
                kvsh = _shd.kv_sharding(self.mesh)
                rep = _shd.replicated(self.mesh)
                pjkw = dict(in_shardings=(psh, kvsh, kvsh) + (rep,) * 8,
                            out_shardings=(kvsh, kvsh, rep, rep))
                sjkw = dict(in_shardings=(psh, kvsh, kvsh) + (rep,) * 7,
                            out_shardings=(kvsh, kvsh, rep, rep))
            entry = {"prefill": jax.jit(prefill, donate_argnums=donate,
                                        **pjkw),
                     "step": jax.jit(step, donate_argnums=donate,
                                     **sjkw),
                     "traces": traces}
            self._PROGRAMS[self._program_key()] = entry
        self._prefill_fn = entry["prefill"]
        self._step_fn = entry["step"]
        self._traces = entry["traces"]

    def _build_spec(self):
        """The speculative program pair, cached under the paged program
        key EXTENDED with the window geometry.  Extending (never
        changing) the key keeps this engine's prefill and one-token
        step as the SAME executables its non-speculative twin runs —
        the bitwise-parity and equal-footing contracts — while verify/
        draft are shared across engines with the same signature."""
        from . import speculative as _spec
        key = self._program_key() + (
            ("spec", self._spec_k, self._draft_layers),)
        entry = self._PROGRAMS.get(key)
        if entry is None:
            adapter = self.adapter
            pick = make_slot_picker()
            from .. import telemetry as _tel
            retrace = _tel.get_registry().counter(
                "hetu_serving_retraces_total",
                "Times each jitted serving program was traced — >1 "
                "after warmup breaks the compile-once contract",
                labels=("program",))
            traces = {"verify": 0}
            verify_core = _spec.make_verify_fn(adapter, pick,
                                               self._spec_k + 1)

            def verify(*a):
                traces["verify"] += 1      # host-side retrace witness
                retrace.labels(program="verify").inc()
                return verify_core(*a)

            draft_jit = None
            if self._draft_layers:
                traces["draft"] = 0
                draft_core = _spec.make_self_draft_fn(
                    adapter, pick, self._spec_k, self._draft_layers)

                def draft(*a):
                    traces["draft"] += 1   # host-side retrace witness
                    retrace.labels(program="draft").inc()
                    return draft_core(*a)

            donate = () if jax.default_backend() == "cpu" else (1, 2)
            vjkw, djkw = {}, {}
            if self.mesh is not None:
                psh = _shd.param_shardings(self.mesh, adapter,
                                           self.params)
                kvsh = _shd.kv_sharding(self.mesh)
                rep = _shd.replicated(self.mesh)
                vjkw = dict(in_shardings=(psh, kvsh, kvsh) + (rep,) * 7,
                            out_shardings=(kvsh, kvsh, rep, rep))
                djkw = dict(in_shardings=(psh, kvsh, kvsh) + (rep,) * 6,
                            out_shardings=rep)
            if self._draft_layers:
                # NO donation: the draft is carry-only over the pool
                draft_jit = jax.jit(draft, **djkw)
            entry = {"verify": jax.jit(verify, donate_argnums=donate,
                                       **vjkw),
                     "draft": draft_jit,
                     "traces": traces}
            self._PROGRAMS[key] = entry
        self._verify_fn = entry["verify"]
        self._draft_fn = entry["draft"]
        self._spec_traces = entry["traces"]

    @property
    def trace_counts(self):
        """{'prefill': n, 'step': n, ...} — times each (shared) program
        was traced; 1 after warmup means every engine with this
        signature runs the same executable at the same shapes.
        Speculative engines add their verify/draft witnesses (and a
        ModelDraft its prefill/step pair) to the same dict."""
        out = dict(self._traces)
        out.update(self._spec_traces)
        if self._draft is not None:
            out.update(self._draft.trace_counts)
        return out

    @contextlib.contextmanager
    def _dispatching(self, tag):
        """Around one call of a jitted program: marks the engine cold
        while the first call of variant ``tag`` is in flight."""
        if tag in self._warm:
            yield
            return
        self.cold_dispatch = tag
        try:
            yield
            self._warm.add(tag)
        finally:
            self.cold_until = time.perf_counter()
            self.cold_dispatch = None

    def _dev_put(self, host_array):
        """Upload a host-built operand.  Mesh engines place it
        replicated over their devices ONCE, so the cached copies below
        aren't resharded by every jit dispatch."""
        if self.mesh is not None:
            return jax.device_put(host_array, self._rep)
        return jnp.asarray(host_array)

    # AOT (prefill, decode) executables keyed by cost_signature():
    # engines sharing a signature share exact shapes, so the compiled
    # analysis pair is identical and a raw cost_programs() call is
    # retrace-free after the first per signature
    _COST_PROGRAMS = {}

    def cost_programs(self, force=False):
        """AOT-lower + compile the (prefill, decode) pair at this
        engine's exact serving shapes and return ``{"prefill":
        compiled, "decode": compiled}`` for the profiling layer
        (``telemetry.profiling.ProgramProfiler.capture``).

        Pure analysis — nothing executes and no engine state changes.
        Results are cached per :meth:`cost_signature` (like the shared
        serving programs), so only the FIRST call per signature pays
        the re-lower/re-trace; repeat calls — and
        :meth:`capture_cost_profiles` misses — stay retrace-flat even
        inside a compile-once assertion window.  ``force=True``
        rebuilds (and refreshes the cache) unconditionally."""
        sig = self.cost_signature()
        if not force:
            cached = self._COST_PROGRAMS.get(sig)
            if cached is not None:
                return dict(cached)

        def ab(x):
            return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype)

        params = jax.tree_util.tree_map(ab, self.params)
        # quantized pools are pytrees (codes + scales): abstract per leaf
        k = jax.tree_util.tree_map(ab, self.cache.k)
        v = jax.tree_util.tree_map(ab, self.cache.v)
        key = ab(self._key)
        n = self.cache.n_slots
        lane = jax.ShapeDtypeStruct((n,), jnp.int32)
        active = jax.ShapeDtypeStruct((n,), jnp.bool_)
        if self._paged:
            # analysis shapes: a full-lane [B=lane_cap, C=chunk_cap]
            # prefill bucket and the (only) decode signature
            b = self._lane_cap
            mp = self.cache.max_pages
            prompts = jax.ShapeDtypeStruct((b, self._chunk_cap),
                                           jnp.int32)
            blane = jax.ShapeDtypeStruct((b,), jnp.int32)
            bf32 = jax.ShapeDtypeStruct((b,), jnp.float32)
            btab = jax.ShapeDtypeStruct((b, mp), jnp.int32)
            tab = jax.ShapeDtypeStruct((n, mp), jnp.int32)
            f32 = jax.ShapeDtypeStruct((n,), jnp.float32)
            progs = {"prefill": self._prefill_fn.lower(
                         params, k, v, prompts, blane, blane, blane,
                         btab, bf32, blane, blane).compile(),
                     "decode": self._step_fn.lower(
                         params, k, v, lane, lane, tab, active, f32,
                         lane, lane).compile()}
        else:
            prompt = jax.ShapeDtypeStruct((1, self.max_prompt_len),
                                          jnp.int32)
            scalar = jax.ShapeDtypeStruct((), jnp.int32)
            progs = {"prefill": self._prefill_fn.lower(
                         params, k, v, prompt, scalar, scalar,
                         key).compile(),
                     "decode": self._step_fn.lower(
                         params, k, v, lane, lane, active, key).compile()}
        self._COST_PROGRAMS[sig] = dict(progs)
        return progs

    def cost_signature(self):
        """Stable identity of the compiled (prefill, decode) pair at
        this engine's serving shapes — the profiler's capture-cache
        key.  Same adapter/config/sampling/backend (the shared program
        key) plus the same slot geometry means the same executables,
        so a cached cost/memory capture is exact, not approximate."""
        return repr((self._program_key(), self.cache.n_slots,
                     self.max_len, self.max_prompt_len))

    def capture_cost_profiles(self, profiler, kind="serve", prefix=None):
        """Capture cost/memory for both serving programs through
        ``profiler``'s signature cache (profile names
        ``{prefix}_prefill`` / ``{prefix}_decode``; the prefix defaults
        to the adapter name).  Only a
        cache MISS builds the AOT programs — :meth:`cost_programs` runs
        at most once per call and not at all when both signatures hit,
        so calling this every controller tick never re-traces."""
        prefix = self.adapter.name if prefix is None else str(prefix)
        sig = self.cost_signature()
        progs = {}

        def deferred(which):
            def build():
                if not progs:
                    progs.update(self.cost_programs())
                return progs[which]
            return build

        return {which: profiler.capture(
                    f"{prefix}_{which}", deferred(which), kind=kind,
                    signature=f"{sig}:{which}")
                for which in ("prefill", "decode")}

    def close(self):
        """Release engine-owned HBM-ledger accounting (the KV slot
        pool, a ModelDraft's cache, the prefix cache's retained pages).
        Idempotent; scheduler/stats state stays readable."""
        if self._draft is not None:
            self._draft.close()
        if self.prefix_cache is not None:
            self.prefix_cache.close()
        self.cache.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    # -- request API -------------------------------------------------------
    def submit(self, prompt, max_new, stream=None, eos_id=None,
               arrival=None, deadline=None, ttl=None, replay=None,
               rid=None, temperature=None, top_k=None, seed=None):
        """Queue one generation request; returns its Request handle.
        ``stream(token, request)`` is called per generated token.
        ``ttl`` (seconds from now) or ``deadline`` (absolute, on the
        engine's monotonic clock) bounds the request's lifetime: past
        it, the request finishes with ``finish_reason="deadline"`` and
        whatever tokens it produced.  ``replay=`` (fleet failover)
        teacher-forces a previous attempt's tokens to rebuild the KV
        state without re-emitting them, and ``rid=`` keeps the failed
        attempt's cluster-level id.  ``temperature=`` / ``top_k=`` /
        ``seed=`` override the engine defaults for THIS request (paged
        engines only — per-slot sampling is a decode operand there, a
        compile-time constant on the slot engine).  Raises
        :class:`~.scheduler.EngineOverloaded` when the bounded queue
        refuses admission."""
        if not self._paged and (temperature is not None
                                or top_k is not None or seed is not None):
            raise ValueError(
                "per-request sampling (temperature/top_k/seed) requires "
                "a paged engine (paged=True); the slot engine bakes "
                "sampling into the compiled program")
        if temperature is not None and float(temperature) < 0:
            raise ValueError(
                f"temperature must be >= 0, got {temperature}")
        if top_k is not None and int(top_k) < 0:
            raise ValueError(f"top_k must be >= 0, got {top_k}")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size > self.max_prompt_len:
            raise ValueError(
                f"prompt length {prompt.size} exceeds max_prompt_len="
                f"{self.max_prompt_len}")
        max_new = int(max_new)
        if prompt.size + max_new > self.max_len - self._spec_k:
            # the spec_k headroom is the verify window's worst-case
            # overhang: admission reserves it so the window can never
            # scatter past a slot's pages mid-flight (admission stays
            # the only refusal point)
            spec = (f" - spec_k={self._spec_k}" if self._spec_k else "")
            raise ValueError(
                f"prompt ({prompt.size}) + max_new ({max_new}) exceeds "
                f"max_len={self.max_len}{spec}")
        now = self._now()
        if ttl is not None:
            if deadline is not None:
                raise ValueError("pass ttl= or deadline=, not both")
            if ttl <= 0:
                raise ValueError(f"ttl must be > 0, got {ttl}")
            deadline = now + float(ttl)
        req = Request(prompt, max_new,
                      arrival=now if arrival is None else arrival,
                      stream=stream,
                      eos_id=self.eos_id if eos_id is None else eos_id,
                      deadline=deadline, replay=replay, rid=rid,
                      temperature=temperature, top_k=top_k, seed=seed)
        try:
            self.scheduler.submit(req, now=now)
        finally:
            # drop_expired_first may have shed dead seats even when the
            # newcomer was still refused — their records must not be lost
            for shed in self.scheduler.drain_shed():
                self.expirations += 1
                self._m_expired.inc()
                self._finalize_unadmitted(shed, "deadline", now)
        return req

    def cancel(self, rid):
        """Cancel the live request with this rid: a queued request
        leaves the queue, a running one is retired MID-FLIGHT (slot
        freed immediately).  Either way it finishes with
        ``finish_reason="cancelled"`` and its partial tokens in
        ``result()``.  Returns True if a live request was cancelled,
        False if the rid is unknown or already finished."""
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

    def prefix_hit_tokens(self, prompt):
        """Tokens of ``prompt`` an interned prefix would cover at
        admission (0 without a prefix cache) — the fleet's routing
        tie-break toward the replica holding the warmest prefix."""
        if self.prefix_cache is None:
            return 0
        return self.prefix_cache.hit_tokens(
            np.asarray(prompt, np.int32).reshape(-1))

    @property
    def spec_accepted_per_step(self):
        """Measured accepted-tokens-per-verify-step EWMA (None when not
        speculating) — the SLO cost model's per-token decode divisor."""
        return self._spec_accept_ewma if self._spec_k else None

    def _now(self):
        return self._clock()

    def _absorb_replay(self, req, tok):
        """Book a teacher-forced replay token: it lands in ``tokens``
        (so eos/max_new accounting and ``result()`` see the full stream)
        but is never re-emitted — the client already received it from
        the previous attempt."""
        req.tokens.append(int(tok))
        self.replayed_tokens += 1
        self._m_replayed.inc()

    def _emit(self, req, tok, now):
        req.tokens.append(int(tok))
        self._m_tokens.inc()
        if req.t_first is None:
            req.t_first = now
        if req.stream is not None:
            t0 = self._clock()
            try:
                req.stream(int(tok), req)
            except Exception as e:
                if not self.watchdog:
                    raise
                # a raising consumer is the CLIENT's fault — detach it
                # and keep decoding; the tokens still land in result()
                req.stream = None
                self.streams_detached += 1
                self._m_detached.inc()
                warnings.warn(
                    f"stream callback for request {req.rid} raised "
                    f"{type(e).__name__}: {e} — detached (decode "
                    "continues, tokens land in result())")
                return
            if (self.stream_stall_timeout is not None
                    and self._clock() - t0 > self.stream_stall_timeout):
                # one stalled delivery already cost a full iteration for
                # every slot; don't let it happen again
                req.stream = None
                self.streams_detached += 1
                self._m_detached.inc()
                warnings.warn(
                    f"stream callback for request {req.rid} stalled "
                    f"longer than {self.stream_stall_timeout}s — "
                    "detached (decode continues)")

    def _record(self, req):
        self.records.append({
            "id": req.rid, "prompt_len": int(req.prompt.size),
            "n_tokens": len(req.tokens),
            "queue_wait": req.queue_wait, "ttft": req.ttft,
            "tpot": req.tpot, "finish_reason": req.finish_reason})
        # timeline: the marker event for HOW the attempt ended, then the
        # terminal itself ("failover" is attempt-terminal only — the
        # fleet continues the same rid on a sibling, so the timeline
        # stays live past a "harvested"+finish(failover) pair)
        reason = req.finish_reason
        if reason == "deadline":
            self._rt.event(req.rid, "expired", engine=self.instance)
        elif reason == "cancelled":
            self._rt.event(req.rid, "cancelled", engine=self.instance)
        elif reason == "failover":
            self._rt.event(req.rid, "harvested", engine=self.instance)
        self._rt.event(req.rid, "finish", engine=self.instance,
                       reason=reason, tokens=len(req.tokens))
        # registry mirror of the record: the same latencies land in
        # scrape-able histograms without changing records' shape
        self._m_finished.inc()
        for m, v in ((self._m_qwait, req.queue_wait),
                     (self._m_ttft, req.ttft),
                     (self._m_tpot, req.tpot)):
            if v is not None:
                m.observe(v)

    def _finalize_active(self, req, reason, now):
        """Retire a RUNNING request (slot freed immediately).  A
        request retired mid-chunked-prefill (cancel/expire/harvest)
        also leaves the in-progress prefill registry."""
        if req.slot is not None and req.slot in self._prefilling:
            self._prefilling.pop(req.slot, None)
            if req.slot in self._prefill_order:
                self._prefill_order.remove(req.slot)
        if self._draft is not None and req.slot is not None:
            self._draft.release(req.slot)
        req.t_done = now
        self.scheduler.retire(req, reason)
        self._record(req)

    def _finalize_unadmitted(self, req, reason, now):
        """Finish a request that never held a slot (expired or
        cancelled while queued): zero tokens, ttft None."""
        req.t_done = now
        req.finished = True
        req.finish_reason = reason
        self._record(req)

    def _maybe_retire(self, req, tok, now):
        done_eos = req.eos_id is not None and int(tok) == req.eos_id
        if done_eos or len(req.tokens) >= req.max_new:
            self._finalize_active(req, "eos" if done_eos else "max_new",
                                  now)

    def _expire(self, now):
        """Deadline sweep: queued requests past their deadline finish
        without ever taking a slot; running ones retire mid-flight with
        their partial tokens."""
        for req in self.scheduler.take_expired(now):
            self.expirations += 1
            self._m_expired.inc()
            self._finalize_unadmitted(req, "deadline", now)
        expired = [r for r in self.scheduler.running.values()
                   if r.expired(now)]
        for req in expired:
            self.expirations += 1
            self._m_expired.inc()
            self._finalize_active(req, "deadline", now)

    def harvest(self):
        """Remove every live request for fleet failover: running ones
        retire with the attempt-level ``finish_reason="failover"`` (slot
        freed on the spot, so this engine's alloc/free audit stays
        balanced), queued ones leave the queue the same way.  Returns
        the harvested requests, running (admission order) before queued
        (FIFO) — the order a sibling should re-admit them in.  The
        cluster-level request is NOT finished by this: the fleet
        re-submits the same rid elsewhere with ``replay=`` carrying each
        request's tokens-so-far."""
        now = self._now()
        out = []
        for rid in self.scheduler.admitted_order:
            req = next((r for r in self.scheduler.running.values()
                        if r.rid == rid), None)
            if req is not None:
                self._finalize_active(req, "failover", now)
                out.append(req)
        # defensive: any running request not in admitted_order
        for req in list(self.scheduler.running.values()):
            self._finalize_active(req, "failover", now)
            out.append(req)
        while self.scheduler.queue:
            req = self.scheduler.queue.popleft()
            self._finalize_unadmitted(req, "failover", now)
            out.append(req)
        return out

    # -- live KV migration (serving/kv_transfer.py rides these) ------------
    def adopt_request(self, prompt, tokens, pages, position, max_new, *,
                      rid=None, stream=None, eos_id=None, deadline=None,
                      temperature=None, top_k=None, seed=None,
                      arrival=None):
        """Resume a sibling's mid-decode stream from spliced pages.

        ``pages`` are ids from THIS pool's :meth:`~.kv_cache.PagedKVCache.
        import_pages` (one caller-owned reference each); ``tokens`` are
        the stream's already-delivered generated ids (never re-emitted);
        ``position`` is the donor's cached-row count, which for a stream
        with T >= 1 generated tokens is exactly ``prompt + T - 1`` — the
        newest token is a decode operand, not a cache row.  Paged
        sampling keys fold only the per-request seed and the consumed
        count, so the continued stream is BITWISE the uninterrupted one.

        On success the request owns the pages (the caller's reference is
        released here) and decodes on the next iteration.  Returns None
        when admission is refused (no slot/pages — caller keeps its page
        reference and falls back to replay)."""
        if not self._paged:
            raise ValueError("adopt_request requires a paged engine — "
                             "migration moves pages, not slots")
        if self._draft is not None:
            raise ValueError(
                "adopt_request cannot target a ModelDraft engine: the "
                "draft's per-slot state is not part of the wire format "
                "(use replay, or the truncated-layer SelfDraft)")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        tokens = [int(t) for t in tokens]
        if len(tokens) < 1:
            raise ValueError(
                "adopt_request needs >= 1 generated token (a mid-prefill "
                "stream has no decode state to move — replay it)")
        max_new = int(max_new)
        if len(tokens) >= max_new:
            raise ValueError(
                f"stream already holds {len(tokens)} >= max_new="
                f"{max_new} tokens — nothing left to decode")
        if int(position) != prompt.size + len(tokens) - 1:
            raise ValueError(
                f"position {int(position)} != prompt ({prompt.size}) + "
                f"tokens ({len(tokens)}) - 1 — donor state torn")
        if prompt.size > self.max_prompt_len:
            raise ValueError(
                f"prompt length {prompt.size} exceeds max_prompt_len="
                f"{self.max_prompt_len}")
        if prompt.size + max_new > self.max_len - self._spec_k:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new ({max_new}) exceeds "
                f"max_len={self.max_len}")
        now = self._now()
        slot = self.cache.alloc(
            owner=rid,
            n_tokens=prompt.size + max_new + self.scheduler.lookahead,
            shared=pages)
        if slot is None:
            return None
        # the slot now holds its own reference on every page; dropping
        # the caller's makes them private again (refcount 1) so the
        # next decode write into the partially-filled last page is an
        # in-place write, not a copy-on-write fork
        self.cache.release_pages(pages)
        req = Request(prompt, max_new,
                      arrival=now if arrival is None else arrival,
                      stream=stream,
                      eos_id=self.eos_id if eos_id is None else eos_id,
                      deadline=deadline, rid=rid,
                      temperature=temperature, top_k=top_k, seed=seed)
        if req.rid is None:
            n = next(self.scheduler._ids)
            req.rid = (n if self.scheduler.rid_prefix is None
                       else f"{self.scheduler.rid_prefix}-{n}")
        req.tokens = tokens
        req.prefix_tokens = 0
        req.slot = slot
        req.t_admit = now
        req.t_first = now
        self.cache.positions[slot] = int(position)
        self._last_tokens[slot] = tokens[-1]
        self._temps[slot] = (self._sampling[0] if temperature is None
                             else float(temperature))
        self._topks[slot] = (self._sampling[1] if top_k is None
                             else int(top_k))
        self._seeds[slot] = (self._default_seed if seed is None
                             else int(seed))
        self._dev_sampling = None
        self.scheduler.running[slot] = req
        self.scheduler.admitted_order.append(req.rid)
        self.migrated_in += 1
        self._m_migrated_in.inc()
        self._rt.event(req.rid, "migrated", engine=self.instance,
                       tokens=len(tokens), pages=len(pages))
        return req

    def release_migrated(self, rid):
        """Donor-side ack: a sibling adopted this stream, so retire the
        local attempt with the attempt-level ``finish_reason="failover"``
        (the cluster-level request lives on over there) and free its
        slot and pages NOW — never before the receiver holds its own
        copy.  Returns True if a live request was released."""
        req = self.scheduler.find(rid)
        if req is None:
            return False
        now = self._now()
        if req.slot is not None:
            self._finalize_active(req, "failover", now)
        else:
            self.scheduler.remove_queued(req)
            self._finalize_unadmitted(req, "failover", now)
        self.migrated_out += 1
        self._m_migrated_out.inc()
        return True

    def _quarantine_all(self, reason, now):
        """A fault that cannot be attributed to one slot (the jitted
        step itself raised): retire everything in flight with "error"
        and keep the engine alive for new work."""
        for req in list(self.scheduler.running.values()):
            self._rt.event(req.rid, "watchdog_trip",
                           engine=self.instance, why="step_raise")
            self._finalize_active(req, "error", now)
        self.watchdog_trips += 1
        self._m_watchdog.inc()
        self._fl.incident("watchdog",
                          extra={"engine": self.instance,
                                 "why": reason})
        warnings.warn(
            f"decode watchdog: {reason} — all in-flight requests "
            "retired with finish_reason='error'; engine continues")

    # -- the iteration -----------------------------------------------------
    def _prefill_paged(self):
        """Paged admission/prefill: continue in-flight chunked prefills
        (admission order), admit new requests up to the scheduler's
        count budget AND the per-iteration ``prefill_token_budget``,
        then run ALL lanes as ONE batched ``[B, C]`` prefill call (both
        axes pow2-bucketed).  Lanes whose final chunk lands emit their
        first token; the rest park in ``_prefilling`` and decode
        proceeds around them.  Returns tokens produced."""
        produced = 0
        budget = self.prefill_token_budget
        used = 0
        work = []   # [req, slot, start, chunk_len]
        for slot in list(self._prefill_order):
            if (len(work) >= self._lane_cap
                    or (budget is not None and used >= budget)):
                break
            st = self._prefilling[slot]
            req = st["req"]
            clen = min(int(req.prompt.size) - st["start"],
                       self._chunk_cap)
            if budget is not None:
                clen = min(clen, budget - used)
            if clen <= 0:
                break
            work.append((req, slot, st["start"], clen))
            used += clen
        if (len(work) < self._lane_cap
                and (budget is None or used < budget)):
            tb = None if budget is None else budget - used
            for req, slot in self.scheduler.admit(token_budget=tb):
                req.t_admit = self._now()
                self._rt.event(req.rid, "admitted",
                               engine=self.instance, slot=slot)
                self._rt.event(req.rid, "prefill_start",
                               engine=self.instance, slot=slot,
                               prompt_len=int(req.prompt.size))
                self._temps[slot] = (self._sampling[0]
                                     if req.temperature is None
                                     else req.temperature)
                self._topks[slot] = (self._sampling[1]
                                     if req.top_k is None else req.top_k)
                self._seeds[slot] = (self._default_seed
                                     if req.seed is None else req.seed)
                self._dev_sampling = None
                # prefix-cache hit: the scheduler shared the interned
                # pages into this slot at alloc — prefill starts AFTER
                # them (rows < start read the shared pages via the
                # gathered block table; nothing is recomputed)
                start0 = int(getattr(req, "prefix_tokens", 0))
                if start0:
                    self._rt.event(req.rid, "prefix_hit",
                                   engine=self.instance, slot=slot,
                                   tokens=start0)
                self._prefilling[slot] = {"req": req, "start": start0}
                self._prefill_order.append(slot)
                clen = min(int(req.prompt.size) - start0,
                           self._chunk_cap)
                if budget is not None:
                    clen = min(clen, budget - used)
                if clen > 0 and len(work) < self._lane_cap:
                    work.append((req, slot, start0, clen))
                    used += clen
        if not work:
            return 0
        bb = min(_p2(len(work)), self._lane_cap)
        cb = _p2(max(w[3] for w in work))
        mp = self.cache.max_pages
        prompts = np.zeros((bb, cb), np.int32)
        p_lens = np.ones(bb, np.int32)
        starts = np.zeros(bb, np.int32)
        chunk_lens = np.zeros(bb, np.int32)   # pad lanes: 0 valid rows
        tables = np.zeros((bb, mp), np.int32)
        temps = np.zeros(bb, np.float32)
        topks = np.zeros(bb, np.int32)
        seeds = np.zeros(bb, np.int32)
        for i, (req, slot, start, clen) in enumerate(work):
            prompts[i, :clen] = req.prompt[start:start + clen]
            p_lens[i] = req.prompt.size
            starts[i] = start
            chunk_lens[i] = clen
            tables[i] = self.cache.block_tables[slot]
            temps[i] = self._temps[slot]
            topks[i] = self._topks[slot]
            seeds[i] = self._seeds[slot]
        for req, slot, start, clen in work:
            # CoW discipline: chunk writes start AFTER any shared
            # prefix, so they can only hit privately-held pages.  The
            # guard (on in tests) turns a violation into a loud raise
            # instead of silent cross-request contamination.
            if self.cache.pages_shared:
                self.cache.ensure_writable(slot, start, clen)
            if self.cache.cow_guard:
                self.cache.assert_writable(slot, start, clen)
        try:
            with self._tr.span("serve_prefill"), \
                    self._dispatching(f"prefill[{bb}x{cb}]"):
                k, v, toks, oks = self._prefill_fn(
                    self.params, self.cache.k, self.cache.v,
                    self._dev_put(prompts), self._dev_put(p_lens),
                    self._dev_put(starts), self._dev_put(chunk_lens),
                    self._dev_put(tables), self._dev_put(temps),
                    self._dev_put(topks), self._dev_put(seeds))
                self.cache.update(k, v)
                toks = np.asarray(toks)
                oks = np.asarray(oks)
        except Exception as e:
            if not self.watchdog:
                raise
            now = self._now()
            self.watchdog_trips += 1
            self._m_watchdog.inc()
            why = (f"batched prefill raised {type(e).__name__}: {e}")
            warnings.warn(f"decode watchdog: {why} — quarantined")
            for req, slot, start, clen in work:
                self._rt.event(req.rid, "watchdog_trip",
                               engine=self.instance,
                               why="prefill_raise")
                self._fl.incident("watchdog", rid=req.rid,
                                  extra={"engine": self.instance,
                                         "why": why})
                self._finalize_active(req, "error", now)
            return 0
        now = self._now()
        for i, (req, slot, start, clen) in enumerate(work):
            self.prefill_chunks += 1
            if self.watchdog and not bool(oks[i]):
                self.watchdog_trips += 1
                self._m_watchdog.inc()
                warnings.warn(
                    f"decode watchdog: non-finite prefill logits for "
                    f"request {req.rid} — quarantined")
                self._rt.event(req.rid, "watchdog_trip",
                               engine=self.instance,
                               why="nonfinite_prefill")
                self._fl.incident(
                    "watchdog", rid=req.rid,
                    extra={"engine": self.instance,
                           "why": "non-finite prefill logits"})
                self._finalize_active(req, "error", now)
                continue
            if start + clen < int(req.prompt.size):
                # mid-prompt: park until the next iteration's chunk —
                # decode interleaves in the meantime
                self._prefilling[slot]["start"] = start + clen
                self._rt.event(req.rid, "prefill_chunk",
                               engine=self.instance, slot=slot,
                               start=start, tokens=clen)
                continue
            self._prefilling.pop(slot, None)
            self._prefill_order.remove(slot)
            self.cache.positions[slot] = int(req.prompt.size)
            if self._draft is not None and (
                    self._spec_min_accept is None
                    or self._spec_accept_ewma >= self._spec_min_accept):
                # gate closed -> skip the draft-side prefill dispatch:
                # the lane stays at pos 0 and the catchup arithmetic in
                # _step_speculative feeds prompt + stream through the
                # draft's bulk-catchup program if a probe ever reopens
                # speculation, so a junk draft costs nothing per
                # admission while gated off
                self._draft.admit(slot, req.prompt)
            if self.prefix_cache is not None:
                self.prefix_cache.intern(req.prompt, slot)
            self.prefills += 1
            self._m_prefill_iters.inc()
            self._rt.event(req.rid, "prefill_end", engine=self.instance,
                           slot=slot, ok=True)
            tok = int(toks[i])
            forced = req.next_replay()
            if forced is not None:
                tok = forced
                self._last_tokens[slot] = tok
                self._absorb_replay(req, tok)
            else:
                self._last_tokens[slot] = tok
                self._emit(req, tok, now)
                produced += 1
            self._maybe_retire(req, tok, now)
        return produced

    def step(self):
        """One scheduler iteration: expire/admit/prefill, then one fused
        decode step for everything in flight.  Returns the number of
        tokens produced."""
        produced = 0
        self._expire(self._now())
        if self._paged:
            produced += self._prefill_paged()
            if self._spec_k and self._spec_gate():
                return produced + self._step_speculative()
            return produced + self._step_decode()
        # 1) admission: prefill up to the budget into free slots
        for req, slot in self.scheduler.admit():
            req.t_admit = self._now()
            self._rt.event(req.rid, "admitted", engine=self.instance,
                           slot=slot)
            padded, _ = pad_prompts([req.prompt],
                                    pad_to=self.max_prompt_len)
            self._rt.event(req.rid, "prefill_start",
                           engine=self.instance, slot=slot,
                           prompt_len=int(req.prompt.size))
            try:
                with self._tr.span("serve_prefill"), \
                        self._dispatching("prefill"):
                    k, v, tok, ok = self._prefill_fn(
                        self.params, self.cache.k, self.cache.v,
                        jnp.asarray(padded), req.prompt.size, slot,
                        self._next_key())
                    self.cache.update(k, v)
                    self.cache.positions[slot] = req.prompt.size
                    tok = int(np.asarray(tok))
                    ok = bool(np.asarray(ok))
            except Exception as e:
                if not self.watchdog:
                    raise
                self.watchdog_trips += 1
                self._m_watchdog.inc()
                why = (f"prefill of request {req.rid} raised "
                       f"{type(e).__name__}: {e}")
                warnings.warn(
                    f"decode watchdog: {why} — quarantined")
                self._rt.event(req.rid, "watchdog_trip",
                               engine=self.instance, why="prefill_raise")
                self._fl.incident("watchdog", rid=req.rid,
                                  extra={"engine": self.instance,
                                         "why": why})
                self._finalize_active(req, "error", self._now())
                continue
            self.prefills += 1
            self._m_prefill_iters.inc()
            now = self._now()
            self._rt.event(req.rid, "prefill_end", engine=self.instance,
                           slot=slot, ok=bool(ok))
            if self.watchdog and not ok:
                self.watchdog_trips += 1
                self._m_watchdog.inc()
                warnings.warn(
                    f"decode watchdog: non-finite prefill logits for "
                    f"request {req.rid} — quarantined")
                self._rt.event(req.rid, "watchdog_trip",
                               engine=self.instance,
                               why="nonfinite_prefill")
                self._fl.incident(
                    "watchdog", rid=req.rid,
                    extra={"engine": self.instance,
                           "why": "non-finite prefill logits"})
                self._finalize_active(req, "error", now)
                continue
            forced = req.next_replay()
            if forced is not None:
                # failover replay: the first generated token is already
                # known (and was already delivered) — force it instead
                # of emitting.  For a greedy request the computed ``tok``
                # equals ``forced`` (same executable, same prompt); for
                # sampled requests the sibling's key stream differs and
                # forcing is what keeps the stream consistent.
                tok = forced
                self._last_tokens[slot] = tok
                self._absorb_replay(req, tok)
            else:
                self._last_tokens[slot] = tok
                self._emit(req, tok, now)
                produced += 1
            self._maybe_retire(req, tok, now)
        return produced + self._step_decode()

    def _step_decode(self):
        """One fused decode iteration over every active slot (shared by
        the slot and paged paths; the paged call swaps the PRNG key for
        block-table + per-slot sampling operands and skips slots whose
        prompt is still mid-chunked-prefill)."""
        produced = 0
        live = len(self.scheduler.running)
        if live:
            self.peak_active = max(self.peak_active, live)
            self.peak_live_tokens = max(self.peak_live_tokens,
                                        int(self.cache.positions.sum()))
        slots = self.scheduler.active_slots()
        if self._paged:
            # mid-prefill slots hold pages but have no decodable token
            # yet — decode proceeds AROUND them (that's the chunked
            # interleaving), their lanes masked to the sentinel page
            slots = [s for s in slots if s not in self._prefilling]
        if slots:
            active = np.zeros(self.cache.n_slots, bool)
            active[slots] = True
            # the active mask only changes at request boundaries; reuse
            # the device copy across the (long) decode runs in between
            akey = active.tobytes()
            if self._dev_active[0] != akey:
                self._dev_active = (akey, self._dev_put(active))
            dev_active = self._dev_active[1]
            occ = len(slots) / self.cache.n_slots
            self.occupancy.append(occ)
            self._m_occ.set(occ)
            if self._paged and (self.cache.pages_shared
                                or self.cache.cow_guard):
                for s in slots:
                    pos = int(self.cache.positions[s])
                    if self.cache.pages_shared:
                        self.cache.ensure_writable(s, pos, 1)
                    if self.cache.cow_guard:
                        self.cache.assert_writable(s, pos, 1)
            try:
                with self._tr.span("serve_decode"), \
                        self._dispatching("step"):
                    # _last_tokens is mutated in place per emitted token,
                    # so upload a SNAPSHOT: on the CPU backend
                    # jnp.asarray may alias the host buffer / defer the
                    # copy, and the post-dispatch mutation raced the
                    # pending read (nondeterministic streams — the
                    # tier-1 serving flake)
                    if self._paged:
                        if self._dev_sampling is None:
                            self._dev_sampling = (
                                self._dev_put(self._temps.copy()),
                                self._dev_put(self._topks.copy()),
                                self._dev_put(self._seeds.copy()))
                        temps, topks, seeds = self._dev_sampling
                        k, v, nxt, slot_ok = self._step_fn(
                            self.params, self.cache.k, self.cache.v,
                            self._dev_put(self._last_tokens.copy()),
                            self.cache.device_positions(),
                            self.cache.device_block_tables(),
                            dev_active, temps, topks, seeds)
                    else:
                        k, v, nxt, slot_ok = self._step_fn(
                            self.params, self.cache.k, self.cache.v,
                            jnp.asarray(self._last_tokens.copy()),
                            self.cache.device_positions(),
                            dev_active, self._next_key())
                    self.cache.update(k, v)
                    self.cache.advance(slots)
                    # materialize INSIDE the span: this is where the
                    # host actually waits for the decode iteration
                    nxt = np.asarray(nxt)
                    slot_ok = np.asarray(slot_ok)
            except Exception as e:
                if not self.watchdog:
                    raise
                self._quarantine_all(
                    f"decode step raised {type(e).__name__}: {e}",
                    self._now())
                return produced
            self.decode_steps += 1
            self._m_decode_iters.inc()
            now = self._now()
            for slot in slots:
                req = self.scheduler.running[slot]
                if self.watchdog and not slot_ok[slot]:
                    # quarantine: only THIS slot is poisoned (slots
                    # attend their own cache rows only); the bad token
                    # is never emitted, the slot is reclaimed, and the
                    # other streams stay bitwise identical
                    self.watchdog_trips += 1
                    self._m_watchdog.inc()
                    warnings.warn(
                        f"decode watchdog: non-finite logits in slot "
                        f"{slot} (request {req.rid}) — quarantined")
                    self._rt.event(req.rid, "watchdog_trip",
                                   engine=self.instance, slot=slot,
                                   why="nonfinite_decode")
                    self._fl.incident(
                        "watchdog", rid=req.rid,
                        extra={"engine": self.instance, "slot": slot,
                               "why": "non-finite decode logits"})
                    self._finalize_active(req, "error", now)
                    continue
                forced = req.next_replay()
                if forced is not None:
                    # teacher-forced replay step: the cache row written
                    # by this iteration is a function of the FED token,
                    # so forcing the known token rebuilds the exact KV
                    # state of the original run
                    tok = forced
                    self._last_tokens[slot] = tok
                    self._absorb_replay(req, tok)
                    # ONE timeline event per iteration per request —
                    # slot + running token count, never per-token spam
                    self._rt.event(req.rid, "decode_iter",
                                   engine=self.instance, slot=slot,
                                   tokens=len(req.tokens), replayed=True)
                    self._maybe_retire(req, tok, now)
                    continue
                tok = int(nxt[slot])
                self._last_tokens[slot] = tok
                self._emit(req, tok, now)
                produced += 1
                self._rt.event(req.rid, "decode_iter",
                               engine=self.instance, slot=slot,
                               tokens=len(req.tokens))
                self._maybe_retire(req, tok, now)
        return self._leak_sweep(produced)

    def _leak_sweep(self, produced):
        """Leak sweep (end of every decode iteration): a slot owned by
        nobody can never be retired through the request path — reclaim
        it so the pool cannot starve (cheap: one int comparison in the
        healthy case)."""
        if (self.watchdog
                and self.cache.n_active != len(self.scheduler.running)):
            reclaimed = self.scheduler.reconcile()
            if reclaimed:
                self.slot_leaks_reclaimed += reclaimed
                self._m_leaks.inc(reclaimed)
                warnings.warn(
                    f"slot reconcile: reclaimed {reclaimed} leaked KV "
                    "slot(s)")
        return produced

    def _spec_gate(self):
        """Adaptive speculation gate: True -> run the verify window
        this iteration.  With no threshold configured speculation is
        unconditional; otherwise fall back to plain decode while the
        accepted-tokens-per-iteration EWMA sags below it, re-probing
        every ``spec_probe_every`` iterations so recovered acceptance
        re-enables speculation.  The fallback runs the SAME shared
        step executable as the non-speculative twin, so the floor is
        plain-decode throughput minus probe overhead — a slope, never
        a cliff."""
        if self._spec_min_accept is None:
            return True
        if self._spec_accept_ewma >= self._spec_min_accept:
            self._spec_since_probe = 0
            return True
        self._spec_since_probe += 1
        if self._spec_since_probe >= self._spec_probe_every:
            self._spec_since_probe = 0
            return True
        return False

    def _step_speculative(self):
        """One speculative iteration: the draft proposes ``spec_k``
        candidates per slot, ONE fused verify step teacher-forces the
        whole ``[S, W]`` window (W = spec_k + 1, the PR 6 replay path
        widened), and the host commits the accepted prefix — bitwise
        the tokens the plain decode loop would have emitted, in fewer
        dispatches.  Rejected rows need no device rollback: they sit
        beyond the committed position, exactly the stale rows the
        ``col <= position`` mask never attends, and the next write at
        those positions overwrites them (``kv_cache.advance_by``).
        Failover replay slots spend their known continuation as window
        candidates first, so replay accepts at full width and stays
        bit-exact mid-speculation."""
        produced = 0
        live = len(self.scheduler.running)
        if live:
            self.peak_active = max(self.peak_active, live)
            self.peak_live_tokens = max(self.peak_live_tokens,
                                        int(self.cache.positions.sum()))
        slots = [s for s in self.scheduler.active_slots()
                 if s not in self._prefilling]
        if not slots:
            return self._leak_sweep(produced)
        kk = self._spec_k
        window = kk + 1
        n = self.cache.n_slots
        active = np.zeros(n, bool)
        active[slots] = True
        akey = active.tobytes()
        if self._dev_active[0] != akey:
            self._dev_active = (akey, self._dev_put(active))
        dev_active = self._dev_active[1]
        occ = len(slots) / n
        self.occupancy.append(occ)
        self._m_occ.set(occ)
        if self._dev_sampling is None:
            self._dev_sampling = (self._dev_put(self._temps.copy()),
                                  self._dev_put(self._topks.copy()),
                                  self._dev_put(self._seeds.copy()))
        temps, topks, seeds = self._dev_sampling
        # window candidates: replay remainder first (failover — the
        # stream continuation is KNOWN and accepts by construction),
        # then draft proposals
        rems = {}
        need_draft = False
        for s in slots:
            req = self.scheduler.running[s]
            rem = ([] if req.replay is None else
                   [int(t) for t in req.replay[
                       req._replay_pos:req._replay_pos + kk]])
            rems[s] = rem
            if len(rem) < kk:
                need_draft = True
        props = None
        try:
            if self._draft is not None:
                work = []
                for s in slots:
                    req = self.scheduler.running[s]
                    dp = int(self._draft.pos[s])
                    p = int(req.prompt.size)
                    if dp < p:
                        cat = ([int(t) for t in req.prompt[dp:]]
                               + list(req.tokens))
                    else:
                        cat = list(req.tokens[dp - p:])
                    work.append((s, cat))
                with self._dispatching("draft"):
                    props = self._draft.propose(work, temps, topks, seeds)
            elif need_draft:
                with self._dispatching("draft"):
                    props = np.asarray(self._draft_fn(
                        self.params, self.cache.k, self.cache.v,
                        self._dev_put(self._last_tokens.copy()),
                        self.cache.device_positions(),
                        self.cache.device_block_tables(),
                        temps, topks, seeds))
        except Exception as e:
            if not self.watchdog:
                raise
            self._quarantine_all(
                f"speculative draft raised {type(e).__name__}: {e}",
                self._now())
            return produced
        toks = np.zeros((n, window), np.int32)
        toks[:, 0] = self._last_tokens
        for s in slots:
            cand = list(rems[s])
            if props is not None:
                cand += [int(props[s, i]) for i in range(len(cand), kk)]
                d = kk - len(rems[s])
                if d > 0:
                    self.spec_proposed += d
                    self._m_spec_proposed.inc(d)
            else:
                cand += [0] * (kk - len(cand))
            toks[s, 1:] = cand
            pos = int(self.cache.positions[s])
            if self.cache.pages_shared:
                self.cache.ensure_writable(s, pos, window)
            if self.cache.cow_guard:
                self.cache.assert_writable(s, pos, window)
        try:
            with self._tr.span("serve_decode"), \
                    self._dispatching("verify"):
                k, v, picks, oks = self._verify_fn(
                    self.params, self.cache.k, self.cache.v,
                    self._dev_put(toks), self.cache.device_positions(),
                    self.cache.device_block_tables(), dev_active,
                    temps, topks, seeds)
                self.cache.update(k, v)
                picks = np.asarray(picks)
                oks = np.asarray(oks)
        except Exception as e:
            if not self.watchdog:
                raise
            self._quarantine_all(
                f"speculative verify raised {type(e).__name__}: {e}",
                self._now())
            return produced
        self.decode_steps += 1
        self.spec_steps += 1
        self._m_decode_iters.inc()
        now = self._now()
        total_m = 0
        for s in slots:
            req = self.scheduler.running[s]
            r = len(rems[s])
            m = 0
            finished = False
            for j in range(window):
                if self.watchdog and not oks[s, j]:
                    self.watchdog_trips += 1
                    self._m_watchdog.inc()
                    warnings.warn(
                        f"decode watchdog: non-finite logits in slot "
                        f"{s} (request {req.rid}) — quarantined")
                    self._rt.event(req.rid, "watchdog_trip",
                                   engine=self.instance, slot=s,
                                   why="nonfinite_decode")
                    self._fl.incident(
                        "watchdog", rid=req.rid,
                        extra={"engine": self.instance, "slot": s,
                               "why": "non-finite decode logits"})
                    self._finalize_active(req, "error", now)
                    finished = True
                    break
                forced = req.next_replay()
                if forced is not None:
                    tok = int(forced)
                    self._last_tokens[s] = tok
                    self._absorb_replay(req, tok)
                else:
                    tok = int(picks[s, j])
                    self._last_tokens[s] = tok
                    self._emit(req, tok, now)
                    produced += 1
                m += 1
                done_eos = (req.eos_id is not None
                            and tok == req.eos_id)
                if done_eos or len(req.tokens) >= req.max_new:
                    self._finalize_active(
                        req, "eos" if done_eos else "max_new", now)
                    finished = True
                    break
                # the chain rule: window step j+1 fed candidate
                # toks[s, j+1]; its pick is the stream continuation iff
                # that candidate IS the token just committed
                if j + 1 < window and int(toks[s, j + 1]) == tok:
                    if j >= r:      # a draft-origin candidate survived
                        self.spec_accepted += 1
                        self._m_spec_accepted.inc()
                    continue
                break
            total_m += m
            if not finished:
                self.cache.advance_by(s, m)
                self._rt.event(req.rid, "decode_iter",
                               engine=self.instance, slot=s,
                               tokens=len(req.tokens), spec=m)
        mean_m = total_m / len(slots)
        self._spec_accept_ewma += 0.25 * (mean_m
                                          - self._spec_accept_ewma)
        return self._leak_sweep(produced)

    def run(self, max_iterations=None):
        """Step until queue and slots drain; returns iterations used."""
        it = 0
        while not self.scheduler.idle:
            if max_iterations is not None and it >= max_iterations:
                raise RuntimeError(
                    f"engine did not drain in {max_iterations} iterations")
            self.step()
            it += 1
        return it

    def generate_many(self, prompts, max_new, eos_id=None):
        """Synchronous batch API: submit all, drain, return each
        request's generated ids (prompt excluded)."""
        reqs = [self.submit(p, max_new, eos_id=eos_id) for p in prompts]
        # worst case every request runs alone to max_len
        self.run(max_iterations=(len(reqs) + 1) * (self.max_len + 2))
        return [r.result() for r in reqs]

    def stream(self, prompt, max_new, eos_id=None, ttl=None):
        """Generator API: yields tokens as the engine produces them
        (pumping the engine between yields; other in-flight requests
        advance too)."""
        req = self.submit(prompt, max_new, eos_id=eos_id, ttl=ttl)
        emitted = 0
        guard = (self.max_len + 2) * (len(self.scheduler.queue)
                                      + self.cache.n_slots + 1)
        it = 0
        while emitted < len(req.tokens) or not req.finished:
            if emitted < len(req.tokens):
                emitted += 1
                yield req.tokens[emitted - 1]
                continue
            if it >= guard:
                raise RuntimeError("stream did not make progress")
            self.step()
            it += 1

    def reset_stats(self):
        """Clear per-request records and step counters (NOT the trace
        counters — retraces after a warmup are exactly what the
        compile-once guard must still see)."""
        self.records = []
        self.occupancy = []
        self.decode_steps = 0
        self.prefills = 0
        self.prefill_chunks = 0
        self.peak_active = 0
        self.peak_live_tokens = 0
        self.cancellations = 0
        self.expirations = 0
        self.watchdog_trips = 0
        self.slot_leaks_reclaimed = 0
        self.streams_detached = 0
        self.replayed_tokens = 0
        self.spec_steps = 0
        self.spec_proposed = 0
        self.spec_accepted = 0

    # -- reporting ---------------------------------------------------------
    def stats(self):
        occ = float(np.mean(self.occupancy)) if self.occupancy else 0.0
        out = {"n_slots": self.cache.n_slots,
                "mean_occupancy": round(occ, 4),
                "decode_steps": self.decode_steps,
                "prefills": self.prefills,
                "prefill_chunks": self.prefill_chunks,
                "peak_active": self.peak_active,
                "peak_live_tokens": self.peak_live_tokens,
                "requests_finished": len(self.records),
                "slot_allocs": self.cache.alloc_count,
                "slot_frees": self.cache.free_count,
                "rejections": self.scheduler.rejected,
                "queue_depth_peak": self.scheduler.queue_depth_peak,
                "cancellations": self.cancellations,
                "expirations": self.expirations,
                "watchdog_trips": self.watchdog_trips,
                "slot_leaks_reclaimed": self.slot_leaks_reclaimed,
                "streams_detached": self.streams_detached,
                "replayed_tokens": self.replayed_tokens,
                "trace_counts": self.trace_counts}
        if self._paged:
            out["pages"] = self.cache.occupancy()
        if self._spec_k:
            prop = self.spec_proposed
            out["spec"] = {
                "k": self._spec_k,
                "draft": ("model" if self._draft is not None
                          else f"self[{self._draft_layers}]"),
                "steps": self.spec_steps,
                "proposed": prop,
                "accepted": self.spec_accepted,
                "acceptance_rate": (round(self.spec_accepted / prop, 4)
                                    if prop else 0.0),
                "accepted_per_step_ewma": round(
                    self._spec_accept_ewma, 4)}
        if self.prefix_cache is not None:
            out["prefix"] = self.prefix_cache.stats()
        if self.mesh is not None:
            out["mesh"] = {
                "tp": self._tp,
                "devices": list(_shd.device_ids(self.mesh)),
                "kv_per_chip_bytes": _shd.per_chip_bytes(
                    (self.cache.k, self.cache.v)),
                "param_per_chip_bytes": _shd.per_chip_bytes(self.params)}
        return out
