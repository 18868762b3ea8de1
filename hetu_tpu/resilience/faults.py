"""Deterministic, seed-driven fault injection for tests and the chaos
bench.

Every injector here is a thin, composable wrapper that makes ONE
specific failure happen at a KNOWN place, reproducibly:

* batch corruption   — ``nan_stream`` / ``corrupt_batch`` poison float
  leaves of the k-th batch (what the StepGuard's fused sentinel must
  catch);
* iterator failure   — ``raising_stream`` raises ``InjectedFault`` from
  the dataloader iterator (the prefetcher's err channel must carry it
  to the consumer);
* producer death     — ``killer_stream`` raises ``PrefetcherKilled``
  (``SystemExit``) INSIDE the prefetch producer thread: it escapes the
  producer's ``except Exception`` and threading swallows it silently,
  so the thread dies with no sentinel on the queue — the honest
  simulation of a segfaulted/OOM-killed worker, which the consumer's
  liveness check must surface within one step;
* PS RPC faults      — ``delay_rpc`` stalls calls, ``drop_rpc`` closes
  the client's pooled sockets mid-conversation so the transport's
  reconnect+retransmit (and the server's dedup cache) must absorb it;
* torn files         — ``tear_file`` truncates a checkpoint the way a
  killed writer would have (only possible pre-atomic-write; the
  restore path must skip it);
* preemption         — ``simulate_preemption`` raises SIGTERM in the
  current process, exercising the checkpoint manager's flush hook.

``FaultInjector`` adds seed-driven *placement*: the same seed always
injects at the same steps, so a chaos run is replayable.
"""

from __future__ import annotations

import os
import signal
import time

import numpy as np


class InjectedFault(RuntimeError):
    """An error deliberately raised by a fault injector."""


class DeviceLost(RuntimeError):
    """A device backing the executor's mesh dropped out mid-run.  On a
    real pod a dead chip surfaces exactly like this: the NEXT dispatch
    (a collective touching the chip) fails — there is no callback.
    Carries the lost device so a supervisor (``resilience/elastic``)
    can compute the surviving set."""

    def __init__(self, device=None):
        super().__init__(f"device lost: {device}")
        self.device = device


# Kills a prefetch producer thread SILENTLY when raised from the wrapped
# source iterator: SystemExit escapes the producer's `except Exception`
# and threading discards it with no traceback, so no error sentinel is
# enqueued — the consumer sees only a dead thread, like after a real
# worker crash.  Must be EXACTLY SystemExit (an alias, not a subclass):
# threading.excepthook silences only the exact class.
PrefetcherKilled = SystemExit


# -- batch corruption ------------------------------------------------------

def corrupt_batch(batch, keys=None, value=np.nan):
    """Return a copy of ``batch`` (dict / tuple / array) with float
    leaves poisoned by ``value`` in element 0.  Integer leaves (ids)
    are left alone — NaN has no integer encoding, and real corruption
    enters through the float path (labels, dense features, activations).
    ``keys`` restricts which dict leaves are hit."""
    def _poison(arr):
        arr = np.array(arr, copy=True)
        if np.issubdtype(arr.dtype, np.floating) and arr.size:
            arr.reshape(-1)[0] = value
        return arr

    if isinstance(batch, dict):
        return {k: (_poison(v) if keys is None or k in keys
                    or getattr(k, "name", None) in (keys or ()) else v)
                for k, v in batch.items()}
    if isinstance(batch, (tuple, list)):
        return type(batch)(_poison(v) for v in batch)
    return _poison(batch)


def nan_stream(iterator, at, keys=None, value=np.nan):
    """Yield ``iterator``'s batches, poisoning the ones at 0-based
    indices in ``at`` (an int or a collection of ints)."""
    steps = {int(at)} if np.isscalar(at) else {int(a) for a in at}
    for i, batch in enumerate(iterator):
        yield corrupt_batch(batch, keys, value) if i in steps else batch


def raising_stream(iterator, at, exc=None):
    """Yield batches until index ``at``, then raise (default
    :class:`InjectedFault`) — a dataloader that dies mid-epoch."""
    for i, batch in enumerate(iterator):
        if i == int(at):
            raise exc if exc is not None else InjectedFault(
                f"injected dataloader failure at batch {at}")
        yield batch


def killer_stream(iterator, at):
    """Yield batches until index ``at``, then kill the consuming thread
    silently (see :class:`PrefetcherKilled`)."""
    for i, batch in enumerate(iterator):
        if i == int(at):
            raise PrefetcherKilled(
                f"injected producer death at batch {at}")
        yield batch


# -- PS RPC faults ---------------------------------------------------------

def delay_rpc(table, seconds, calls=1):
    """Stall the next ``calls`` RPCs of a ``RemoteTable`` by ``seconds``
    (a congested or GC-pausing server).  Returns an undo callable."""
    orig = table._call
    state = {"left": int(calls)}

    def wrapped(header, *arrays, **kw):
        if state["left"] > 0:
            state["left"] -= 1
            time.sleep(float(seconds))
        return orig(header, *arrays, **kw)

    table._call = wrapped
    return lambda: setattr(table, "_call", orig)


def drop_rpc(table, calls=1):
    """Close the client's pooled sockets immediately before each of the
    next ``calls`` RPCs: the request dies mid-wire and the transport's
    reconnect + retransmit path (with the server's dedup cache for
    non-idempotent verbs) must absorb it.  Returns an undo callable."""
    orig = table._call
    state = {"left": int(calls)}

    def wrapped(header, *arrays, **kw):
        if state["left"] > 0:
            state["left"] -= 1
            for c in table._pool:
                sock = c.sock
                if sock is not None:
                    try:
                        sock.close()
                    except OSError:
                        pass    # socket already dead — the goal anyway
        return orig(header, *arrays, **kw)

    table._call = wrapped
    return lambda: setattr(table, "_call", orig)


# -- serving faults --------------------------------------------------------
# The serving-engine counterparts of the training-path faults: each one
# makes a production failure of the continuous-batching engine happen at
# a KNOWN place (tests/test_chaos_stages.py and tests/test_serving_
# robustness.py drive them).

def poison_slot_kv(engine, slot, value=np.nan):
    """Poison one slot's K/V cache rows with ``value`` — a corrupted HBM
    row / overflowed activation deposited into the pooled cache.  The
    next decode step's logits for THAT slot (and only that slot — slots
    attend their own rows) go non-finite, which is exactly what the
    engine's in-graph watchdog sentinel must flag."""
    import jax.numpy as jnp

    slot = int(slot)
    engine.cache.k = engine.cache.k.at[slot].set(value)
    engine.cache.v = engine.cache.v.at[slot].set(value)
    return slot


def raising_engine_step(engine, at, exc=None):
    """Make the engine's ``at``-th decode-step CALL (0-based, counted
    from now) raise (default :class:`InjectedFault`) BEFORE dispatch —
    a poisoned executable / runtime failure the host sees as an
    exception, not a sentinel.  Returns an undo callable."""
    orig = engine._step_fn
    state = {"n": 0}

    def wrapped(*args, **kw):
        n = state["n"]
        state["n"] += 1
        if n == int(at):
            raise exc if exc is not None else InjectedFault(
                f"injected decode-step failure at call {at}")
        return orig(*args, **kw)

    engine._step_fn = wrapped
    return lambda: setattr(engine, "_step_fn", orig)


def leak_slot(engine):
    """Allocate a KV slot that NO request owns — the accounting leak a
    crashed request path leaves behind.  Without the engine's reconcile
    sweep the slot never returns to the pool and admission eventually
    starves; with it, the sweep frees the orphan within one iteration.
    Returns the leaked slot id (None if the pool is already full)."""
    return engine.cache.alloc(owner="__injected_leak__")


def stalling_consumer(seconds, collect=None, fail_after=None):
    """A stream callback that STALLS ``seconds`` on every delivery (a
    slow/blocked client holding the decode loop hostage) and, when
    ``fail_after`` is set, raises :class:`InjectedFault` from the
    ``fail_after``-th call onward (a disconnected client).  ``collect``
    (a list) receives the tokens that were delivered."""
    state = {"n": 0}

    def cb(tok, req):
        state["n"] += 1
        if collect is not None:
            collect.append(int(tok))
        if fail_after is not None and state["n"] > int(fail_after):
            raise InjectedFault(
                f"injected consumer failure at delivery {state['n']}")
        if seconds:
            time.sleep(float(seconds))

    return cb


# -- embedding-serving faults ----------------------------------------------
# The tiered embedding path's failure classes (serving/embedding/):
# where a slot fault poisons one LLM stream, these attack the HOT-ROW
# CACHE contract — rows going stale under it, and admission churn
# defeating it.

def stale_rows(table, keys, value=1.0):
    """Apply an update to ``keys`` on the HOST embedding tier, bumping
    their row versions — every device-cached copy of those rows is now
    stale, and a staleness-bounded cache must refresh them within its
    bound (bound 0: on the very next lookup).  Accepts a
    ``ps.EmbeddingTable`` (push) or ``ps.CacheSparseTable`` (update
    through the HET cache, then flushed so the backing table moves
    too).  Returns the updated keys."""
    keys = np.asarray(keys).reshape(-1).astype(np.int64)
    dim = table.dim
    grads = np.full((keys.size, dim), float(value), np.float32)
    if hasattr(table, "embedding_update"):       # CacheSparseTable
        table.embedding_update(keys, grads).result()
        table.flush()
    else:
        table.push(keys, grads)
    return keys


def thrash_cache(cache, n_keys, seed=0, lo=0, hi=None):
    """Flood a :class:`~hetu_tpu.serving.embedding.DeviceHotRowCache`
    with one-shot COLD keys — the adversarial anti-Zipf workload that
    defeats LFU/LRU admission and forces eviction churn (every flood
    key is a miss, and each one evicts a resident row once the cache is
    full).  Keys are drawn seeded from ``[lo, hi)`` (``hi`` defaults to
    10x the cache so floods barely repeat) in batches the cache can
    hold.  Returns the number of evictions the flood caused."""
    rng = np.random.default_rng(seed)
    hi = int(hi) if hi is not None else lo + 10 * cache.cache_rows
    ev0 = cache.evictions
    batch = max(1, cache.cache_rows // 2)
    keys = rng.integers(int(lo), hi, int(n_keys))
    for i in range(0, keys.size, batch):
        cache.lookup_slots(keys[i:i + batch])
    return cache.evictions - ev0


# -- fleet faults ----------------------------------------------------------
# Replica-level failures for the fleet layer (tests/test_chaos_stages.py
# and tests/test_fleet.py): where the serving faults above hit
# one slot/consumer, these take out a WHOLE engine — the blast radius
# the EngineFleet's quarantine/failover/restart machinery must contain.

def crash_engine(engine, at=0, exc=None):
    """Make the engine's ``at``-th ``step()`` CALL (0-based, counted
    from now) raise OUTSIDE the watchdog's try blocks — the engine-loop
    bug / runtime abort that kills the whole engine, not one slot.  The
    fleet driver sees the exception escape ``step()`` and quarantines
    the replica.  Returns an undo callable."""
    orig = engine.step
    state = {"n": 0}

    def wrapped(*args, **kw):
        n = state["n"]
        state["n"] += 1
        if n == int(at):
            raise exc if exc is not None else InjectedFault(
                f"injected engine crash at step call {at}")
        return orig(*args, **kw)

    engine.step = wrapped
    return lambda: setattr(engine, "step", orig)


def wedge_engine(engine, seconds, at=0):
    """Make the engine's ``at``-th decode-step call STALL ``seconds``
    before dispatch — a hung device call / deadlocked runtime.  The
    driver thread is stuck inside ``step()``, so the replica's
    heartbeat goes stale and the fleet supervisor must quarantine it
    from OUTSIDE (it cannot get the lock).  Bounded, so the zombie
    daemon thread eventually exits.  Returns an undo callable."""
    orig = engine._step_fn
    state = {"n": 0}

    def wrapped(*args, **kw):
        n = state["n"]
        state["n"] += 1
        if n == int(at):
            time.sleep(float(seconds))
        return orig(*args, **kw)

    engine._step_fn = wrapped
    return lambda: setattr(engine, "_step_fn", orig)


def slow_engine(engine, seconds):
    """Make EVERY decode-step call of this engine take an extra
    ``seconds`` — the straggler replica (thermal throttling, a noisy
    neighbor).  Not a fault the health machine trips on; the fleet's
    latency-aware dispatch must simply learn to route around it.
    Returns an undo callable."""
    orig = engine._step_fn

    def wrapped(*args, **kw):
        time.sleep(float(seconds))
        return orig(*args, **kw)

    engine._step_fn = wrapped
    return lambda: setattr(engine, "_step_fn", orig)


# -- KV transfer faults (serving/kv_transfer.py wire) -----------------------
# The fleet routes every migration blob through ``fleet.transfer_filter``
# when one is set; these injectors compose with whatever filter was
# already installed and return an undo callable like everything above.

def _wrap_transfer(fleet, fn):
    prev = fleet.transfer_filter

    def filt(blob):
        if prev is not None:
            blob = prev(blob)
            if blob is None:
                return None
        return fn(blob)

    fleet.transfer_filter = filt
    return lambda: setattr(fleet, "transfer_filter", prev)


def drop_transfer(fleet, at=0):
    """Make the fleet's ``at``-th KV migration transfer (0-based,
    counted from now) vanish in flight — the network ate it.  The
    receiver never sees bytes; the fleet must fall back to
    teacher-forced replay with zero stream divergence."""
    state = {"n": 0}

    def fn(blob):
        n = state["n"]
        state["n"] += 1
        return None if n == int(at) else blob

    return _wrap_transfer(fleet, fn)


def corrupt_transfer(fleet, at=0):
    """Flip one byte in the middle of the ``at``-th migration blob —
    bit rot in transit.  The CRC32 frame walk on the receiver must
    reject it loudly (TransferError), leaving both pools untouched."""
    state = {"n": 0}

    def fn(blob):
        n = state["n"]
        state["n"] += 1
        if n != int(at):
            return blob
        b = bytearray(blob)
        b[len(b) // 2] ^= 0xFF
        return bytes(b)

    return _wrap_transfer(fleet, fn)


#: keep enough bytes that the magic survives — the failure under test
#: is a TORN FRAME, not a non-blob
_TRANSFER_MAGIC = b"HTKV1"


def tear_transfer(fleet, at=0, frac=0.5):
    """Truncate the ``at``-th migration blob to ``frac`` of its bytes —
    the sender died mid-write.  The receiver's frame walk must reject
    the torn frame, never a partial splice."""
    state = {"n": 0}

    def fn(blob):
        n = state["n"]
        state["n"] += 1
        if n != int(at):
            return blob
        return blob[:max(len(_TRANSFER_MAGIC),
                         int(len(blob) * float(frac)))]

    return _wrap_transfer(fleet, fn)


# -- files & process -------------------------------------------------------

def tear_file(path, frac=0.5, keep_bytes=None):
    """Truncate ``path`` the way a killed non-atomic writer would have:
    keep the first ``keep_bytes`` (or ``frac`` of the file)."""
    size = os.path.getsize(path)
    keep = int(size * float(frac)) if keep_bytes is None else int(keep_bytes)
    with open(path, "r+b") as f:
        f.truncate(max(0, min(keep, size)))
    return path


def simulate_preemption(sig=signal.SIGTERM):
    """Deliver the pod scheduler's preemption notice to THIS process
    (synchronously, in the main thread)."""
    signal.raise_signal(sig)


# -- capacity loss ---------------------------------------------------------

def lose_device(executor, device=None):
    """Simulate losing one device of the executor's mesh: the NEXT
    dispatch of EVERY subgraph raises :class:`DeviceLost` (how a dead
    chip actually surfaces — a failed collective, not a notification),
    and the device is appended to ``executor.lost_devices`` so a
    supervisor can compute the surviving set.  Defaults to the mesh's
    last device.  Returns an undo callable (a supervisor that rebuilds
    the executor never needs it; a test that wants the "chip back"
    does)."""
    mesh = getattr(executor, "mesh", None)
    if device is None:
        if mesh is not None:
            device = list(mesh.devices.flat)[-1]
        else:
            import jax
            device = jax.devices()[-1]
    lost = getattr(executor, "lost_devices", None)
    if lost is None:
        lost = []
        executor.lost_devices = lost
    lost.append(device)
    orig = {}
    for name, sub in executor.subexecutor.items():
        orig[name] = sub.run

        def _raiser(*a, _d=device, **kw):
            raise DeviceLost(_d)
        sub.run = _raiser

    def undo():
        for name, sub in executor.subexecutor.items():
            if name in orig:
                sub.run = orig[name]
        lost = getattr(executor, "lost_devices", None)
        if lost is not None and device in lost:
            lost.remove(device)
    return undo


def preempt_during_save(mgr, sig=signal.SIGTERM, frac=0.5,
                        deliver=None):
    """Arm the NEXT ``mgr.save`` to be preempted MID-FLUSH: what lands
    on disk is exactly the wreckage a SIGTERM inside the write window
    leaves — a torn payload under the final checkpoint name (pickle
    mode) or a complete-looking shard directory with one truncated
    file and no manifest entry (sharded mode: one host of the pod
    never finished), the preemption notice is delivered, and the save
    raises :class:`InjectedFault` instead of returning.  The contract
    under test: ``restore_latest`` must still ADOPT the previous good
    checkpoint — the torn flush fails verification (the existing
    torn-manifest path) and falls over.

    ``deliver`` controls the actual SIGTERM: ``None`` (default) raises
    it only when a non-default handler is installed (a bare test
    process must not be killed); ``True``/``False`` force it.  One-
    shot; returns an undo callable that disarms an unfired injector."""
    orig = mgr.save
    prev_last = mgr.last_saved_step

    def _armed_save(executor, step=None):
        mgr.save = orig                      # one-shot: disarm first so a
        prev_handler = signal.getsignal(sig)  # chained flush hook still works
        if mgr.sharded:
            import shutil
            path = orig(executor, step=step)
            step_no = mgr.last_saved_step
            fname = os.path.basename(path)
            # rewind the manifest to before this save (the kill landed
            # before the manifest write) and tear the largest shard
            # file — a host that never finished its part
            entries = [e for e in mgr._read_manifest()
                       if e.get("file") != fname]
            mgr._write_manifest(entries)
            files = [os.path.join(dp, fn)
                     for dp, _dn, fns in os.walk(path) for fn in fns]
            data = [f for f in files if os.path.getsize(f) > 0]
            if data:
                tear_file(max(data, key=os.path.getsize), frac=frac)
            mgr.last_saved_step = prev_last
        else:
            import pickle as _pickle
            state = executor.state_dict()
            step_no = (int(state.get("global_step", 0))
                       if step is None else int(step))
            blob = _pickle.dumps(state,
                                 protocol=_pickle.HIGHEST_PROTOCOL)
            fname = f"{mgr.prefix}-{step_no:010d}.pkl"
            with open(os.path.join(mgr.directory, fname), "wb") as f:
                f.write(blob[:max(1, int(len(blob) * float(frac)))])
        want = deliver
        if want is None:
            want = (callable(prev_handler)
                    and prev_handler not in (signal.SIG_IGN,
                                             signal.SIG_DFL))
        if want:
            signal.raise_signal(sig)
        raise InjectedFault(
            f"preempted during checkpoint flush (step {step_no})")

    mgr.save = _armed_save

    def undo():
        if mgr.save is _armed_save:
            mgr.save = orig
    return undo


# -- seeded placement ------------------------------------------------------

class FaultInjector:
    """Seed-driven fault placement: the same seed plans the same faults
    at the same steps, so chaos runs replay exactly."""

    def __init__(self, seed=0):
        self.seed = int(seed)
        self.rng = np.random.default_rng(self.seed)

    def pick_steps(self, n_steps, n_faults=1, low=1):
        """``n_faults`` distinct 0-based step indices in
        ``[low, n_steps)``, sorted (deterministic per seed)."""
        lo, hi = int(low), int(n_steps)
        if hi - lo < int(n_faults):
            raise ValueError(
                f"cannot place {n_faults} faults in [{lo}, {hi})")
        picks = self.rng.choice(np.arange(lo, hi), size=int(n_faults),
                                replace=False)
        return sorted(int(p) for p in picks)

    # stream wrappers bound to this injector's plan
    def nan_batches(self, iterator, n_steps, n_faults=1, keys=None):
        at = self.pick_steps(n_steps, n_faults)
        return at, nan_stream(iterator, at, keys=keys)

    def kill_producer(self, iterator, n_steps):
        (at,) = self.pick_steps(n_steps, 1)
        return at, killer_stream(iterator, at)

    def raise_in_loader(self, iterator, n_steps, exc=None):
        (at,) = self.pick_steps(n_steps, 1)
        return at, raising_stream(iterator, at, exc=exc)
