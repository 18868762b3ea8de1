"""Continuous-batching inference serving over the KV-cache decoders.

Slot-pooled K/V cache — dense per-slot spans (``SlotKVCache``) or a
paged pool with per-slot block tables, batched + chunked prefill, and
per-request sampling operands (``PagedKVCache``, ``paged=True`` on the
engine; docs/SERVING.md walks the page math) — plus an
iteration-level FIFO scheduler
with bounded-queue admission control (scheduler.py) + slot-batched
model adapters (adapters.py) + the engine tying them together with
per-request deadlines, cancellation, and a decode watchdog (engine.py).
Above the single engine sits the FLEET layer (fleet.py + health.py): N
supervised engine replicas behind a latency-aware router with health
state machines, circuit-broken quarantine, failover of in-flight
requests (bitwise-identical greedy streams via teacher-forced replay),
and supervised restarts over the shared compile-once program cache.
``tests/test_chaos_stages.py`` injects every serving fault and holds one
engine to surviving it, then kills, wedges and rolls whole replicas and
holds the fleet to losing nothing.

Engines scale past one chip with TENSOR-PARALLEL serving (sharding.py,
docs/SHARDING.md): ``InferenceEngine(..., paged=True, mesh=
serving_mesh(tp))`` shards block weights on their output dims and the
KV page pool over kv_heads, with activations gathered back to
replicated before every cross-shard reduction — so the sharded engine
is a token-stream-bitwise twin of the single-chip one, and
``EngineFleet(tp_size=N)`` pins one replica per contiguous N-device
sub-mesh with failover replay landing bit-exactly on a sharded sibling.

The paged engine's raw-speed multiplier is SPECULATIVE DECODING
(speculative.py): a cheap draft — truncated-layer self-draft or an
injectable small model — proposes ``spec_k`` tokens per iteration and
ONE fused verify step teacher-forces the whole window, committing the
accepted prefix bitwise-identically to the non-speculative twin (the
replay path widened to ``[S, k+1]``; rejected rows roll back by
host-side position bookkeeping alone).  On the same page refcounts,
PREFIX CACHING (prefix_cache.py) interns finished prompts' page-aligned
prefixes and shares them into later admissions — shared system prompts
skip prefill, guarded read-only with copy-on-write forking.

A second production workload rides the same lifecycle: the embedding
subpackage (embedding/) serves batched sparse-feature lookups + CTR
scoring through the identical Scheduler — a HET-style device hot-row
cache over the PS table tier, packed-lookup scoring, and
``EngineFleet(engine_factory=EmbeddingServer)`` for cluster routing.

The fleet also moves LIVE state between replicas (kv_transfer.py): a
mid-decode request's refcounted KV pages — raw float32 rows or the
quantized pool's codes + scales — serialize into a CRC32-framed blob
that splices into a sibling's pool and continues the stream BITWISE
where it left off (paged sampling keys fold only the per-request seed
and consumed count).  Four robustness paths ride the wire:
prefill→decode handoff in role-split fleets (``EngineFleet(roles=)``),
page-level failover after a crash, SLO-driven decode rebalancing
(``fleet.rebalance``), and migrate-then-drain scale-down
(``drain(migrate=True)``); the quarantined replica's prefix cache is
re-interned on a sibling the same way.  Any transfer failure — torn or
corrupt frame, geometry drift, a full receiver — raises
:class:`~.kv_transfer.TransferError` and the fleet falls back to
teacher-forced replay, so migration is strictly no worse than the
PR 12 failover oracle.

Above the fleet sits the SLO control plane (control.py): a declared
:class:`~.control.SLO` plus a :class:`~.control.FleetController` that
autoscales replicas, sheds provably-infeasible work at admission with a
typed :class:`~.control.SLOReject`, and walks a staged brownout ladder
under sustained violation.
"""

from .kv_cache import PagedKVCache, QuantizedKVPool, SlotKVCache
from .scheduler import (EngineOverloaded, Request, Scheduler,
                        FINISH_REASONS, SHED_POLICIES, TERMINAL_OK)
from .adapters import (LlamaSlotAdapter, GPTSlotAdapter, adapter_for)
from .engine import InferenceEngine
from .speculative import ModelDraft, SelfDraft
from .prefix_cache import PrefixCache
from .sharding import (KV_POOL_SPEC, kv_sharding, param_pspecs,
                       param_shardings, per_chip_bytes, serving_mesh,
                       shard_params, validate_tp)
from .health import (CircuitBreaker, ReplicaHealth, HEALTH_STATES,
                     HEALTH_STATE_CODES)
from .fleet import EngineFleet, FleetRequest, FleetUnavailable
from .kv_transfer import (TransferError, blob_info, can_migrate,
                          install_prefix_cache, resume_request,
                          snapshot_prefix_cache, snapshot_request)
from .control import (CostModel, DEGRADE_LEVELS, FleetController, SLO,
                      SLOReject)
from .embedding import (BatchSlotPool, DeviceHotRowCache, EmbedRequest,
                        EmbeddingServer, EMBED_BUCKETS)

__all__ = ["PagedKVCache", "QuantizedKVPool", "SlotKVCache",
           "Request", "Scheduler",
           "EngineOverloaded",
           "FINISH_REASONS", "SHED_POLICIES", "TERMINAL_OK",
           "LlamaSlotAdapter", "GPTSlotAdapter", "adapter_for",
           "InferenceEngine", "ModelDraft", "SelfDraft", "PrefixCache",
           "CircuitBreaker", "ReplicaHealth",
           "HEALTH_STATES", "HEALTH_STATE_CODES", "EngineFleet",
           "FleetRequest", "FleetUnavailable", "TransferError",
           "blob_info", "can_migrate", "install_prefix_cache",
           "resume_request", "snapshot_prefix_cache",
           "snapshot_request", "CostModel",
           "DEGRADE_LEVELS", "FleetController", "SLO", "SLOReject",
           "BatchSlotPool", "DeviceHotRowCache", "EmbedRequest",
           "EmbeddingServer", "EMBED_BUCKETS", "KV_POOL_SPEC",
           "kv_sharding", "param_pspecs", "param_shardings",
           "per_chip_bytes", "serving_mesh", "shard_params",
           "validate_tp"]
