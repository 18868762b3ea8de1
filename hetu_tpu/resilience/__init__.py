"""Resilient training runtime: step guard, rolling checkpoints, fault
injection, and the shared retry policy.

The reference Hetu assumes a healthy cluster — a NaN step, a torn
checkpoint, or a preempted host kills the run and loses everything
since the last manual save.  This package makes the executor-level
training loop survive those, at near-zero steady-state cost:

* :class:`StepGuard` (guard.py) — a non-finite sentinel FUSED into the
  jitted step plus a loss-spike watchdog, with ``skip`` / ``rollback``
  / ``abort`` policies;
* :class:`RollingCheckpointManager` (checkpointer.py) — atomic
  (tmp + ``os.replace``) keep-last-K checkpoints with a CRC manifest,
  a ``restore_latest`` that skips torn files, and a SIGTERM preemption
  hook that flushes a final checkpoint so a killed run resumes bitwise;
* :mod:`faults` — deterministic, seed-driven fault injection (NaN
  batches, dataloader errors, silent prefetch-producer death, PS RPC
  delay/drop, torn files, simulated preemption) backing the tests
  (``tests/test_chaos_stages.py`` runs one stage a fault class);
* :func:`retry` (retry.py) — the one backoff/jitter/deadline retry
  policy shared by the PS transport and dataset fetch paths;
* :class:`ElasticTrainer` (elastic.py) — the capacity-change
  supervisor: on chip loss or preemption it re-plans the parallel
  geometry over the survivors and resumes from a resharded rolling
  checkpoint (same-DP recoveries are bitwise vs an uninterrupted run).
"""

from __future__ import annotations

from ..graph.checkpoint import CheckpointError, GeometryMismatch
from .retry import retry
from .guard import GuardTripped, StepGuard
from .checkpointer import RollingCheckpointManager
from . import faults
from .faults import (DeviceLost, FaultInjector, InjectedFault,
                     PrefetcherKilled)
from .elastic import ElasticTrainer

__all__ = [
    "CheckpointError", "DeviceLost", "ElasticTrainer", "FaultInjector",
    "GeometryMismatch", "GuardTripped", "InjectedFault",
    "PrefetcherKilled", "RollingCheckpointManager", "StepGuard", "faults",
    "retry",
]
