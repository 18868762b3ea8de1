"""Tiered embedding serving (HET-style) behind the serving lifecycle.

Three tiers (PAPER.md's HET hot-embedding cache, re-hosted for TPU
serving):

1. **cold** — the host-RAM full table (`ps.EmbeddingTable` /
   `ps.CacheSparseTable`, optionally behind the PS RPC path);
2. **hot**  — :class:`DeviceHotRowCache`: a preallocated
   ``[cache_rows, dim]`` HBM array + host-side id→slot index with
   LFU/LRU admission and a bounded-staleness contract (a row may be
   served at most ``staleness_bound`` host-table updates stale before a
   forced refresh; bound 0 ⇒ bitwise parity with the host table),
   filled by BATCHED scatter, never per-row transfers;
3. **score** — one jitted program per server taking densified id
   batches through the ``ops/pallas/sparse_densify.py`` packed-lookup
   path into the ``models/ctr.py`` (WDL) dense layers.

:class:`EmbeddingServer` serves batched sparse-feature lookups + CTR
scoring through the SAME ``Scheduler`` lifecycle as LLM requests:
bounded-queue admission (typed ``EngineOverloaded``), deadlines/TTL,
``cancel()``, an in-graph finiteness sentinel, telemetry instruments,
and ``EngineFleet`` routing/failover (``engine_factory=
EmbeddingServer``) all work unchanged for microsecond-scale embedding
traffic.
"""

from .hot_cache import DeviceHotRowCache, EMBED_BUCKETS
from .server import BatchSlotPool, EmbedRequest, EmbeddingServer

__all__ = ["DeviceHotRowCache", "EmbeddingServer", "EmbedRequest",
           "BatchSlotPool", "EMBED_BUCKETS"]
