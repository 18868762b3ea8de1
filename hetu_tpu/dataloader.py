"""Data pipeline: prefetching, DP-aware batch feeding as graph nodes.

Reference: /root/reference/python/hetu/dataloader.py — `Dataloader` (:125)
slices the dataset by dp_rank/dp_nrank and prefetches batches through
multiprocess queues; `DataloaderOp` (:289) is a graph node whose value the
executor pulls per step (per named subgraph: 'default'/'train'/'validate').

TPU redesign: feeding is host-side (no kernels involved).  Plain batch
slicing runs on a background *thread* + bounded queue — numpy slicing
releases the GIL and the XLA step fully overlaps it; the queue depth plays
the role of the reference's batch_num prefetch window.  A Python
``transform`` (augmentation, tokenization) is GIL-BOUND, so
``num_workers>0`` switches to the reference's architecture (worker
processes + shared memory, dataloader.py:125): the dataset is published
once into a SharedMemory block, workers apply the transform and write
batches into a fixed ring of shared-memory slots (slot i%S guarded by an
empty/filled semaphore pair), and the consumer drains the ring in batch
order — deterministic regardless of worker timing.  `DataloaderOp`
follows the executor's placeholder-autofill protocol (same hook as
ps/embedding.PSRowsOp): the executor asks the node for the next batch
instead of requiring a feed.
"""

from __future__ import annotations

import queue
import threading

import numpy as np

from .graph.node import PlaceholderOp


def block_diffusion_noise(ids, block, mask_id, rng, eps=1e-3):
    """The noising step of block diffusion's data path (SDAR, arXiv:2510.06303;
    the linear schedule of MDLM and LLaDA) on a host batch ``ids [B, L]``: for
    each sequence and block of ``block`` tokens a level ``t ~ U[eps, 1]``, for
    each token a draw ``m ~ Bernoulli(t of its block)``, and a masked token
    becomes ``mask_id``.  Returns ``(input_ids [B, 2L], labels [B, L], weights
    [B, L] f32)``: the clean copy and then the noised one, a masked position's
    token (-1 elsewhere) and ``1 / t`` of a position's block, the weight the
    masked-diffusion bound gives its cross-entropy.  ``rng`` is a
    ``numpy.random.Generator``: the same state gives the same batch.  Counts
    the positions it masked and kept in
    ``hetu_diffusion_positions_total{state}``."""
    from . import telemetry
    ids = np.asarray(ids)
    B, L = ids.shape
    assert L % block == 0, (L, block)
    t = rng.uniform(eps, 1.0, (B, L // block))
    level = np.repeat(t, block, axis=1)
    masked = rng.random((B, L)) < level
    noised = np.where(masked, np.asarray(mask_id, ids.dtype), ids)
    counts = telemetry.get_registry().counter(
        "hetu_diffusion_positions_total",
        "Noised positions block_diffusion_noise made, by state (masked: "
        "replaced by the mask token and labelled; kept: left as they were)",
        labels=("state",))
    n_masked = int(masked.sum())
    counts.labels(state="masked").inc(n_masked)
    counts.labels(state="kept").inc(B * L - n_masked)
    return (np.concatenate([ids, noised], axis=1),
            np.where(masked, ids, -1).astype(ids.dtype),
            (1.0 / level).astype(np.float32))


def _mp_worker(worker_id, num_workers, start, stop, data_shm_name,
               data_shape, data_dtype, out_shm_name, out_shape, out_dtype,
               slots, empty_sems, filled_sems, batch_size, num_batches,
               shuffle, seed, transform):
    """Worker process body: handles batches i with i % num_workers ==
    worker_id, writing each into ring slot i % slots.  ``start`` shifts
    the global counter so a fast-forwarded stream (skip_to_step) resumes
    mid-epoch without replaying skipped batches."""
    from multiprocessing import shared_memory
    data_shm = shared_memory.SharedMemory(name=data_shm_name)
    out_shm = shared_memory.SharedMemory(name=out_shm_name)
    try:
        data = np.ndarray(data_shape, dtype=data_dtype, buffer=data_shm.buf)
        ring = np.ndarray((slots,) + out_shape, dtype=out_dtype,
                          buffer=out_shm.buf)
        # GLOBAL batch counter g (continuous across epochs): the consumer
        # drains slot g % slots in g order, so the slot index must come
        # from g, not the within-epoch index — the within-epoch form
        # collides as soon as num_batches % slots != 0.  The first g this
        # worker owns at/after ``start`` keeps the g % W == worker shard
        # assignment identical to a never-skipped run.
        g = start + ((worker_id - start) % num_workers)
        order, order_epoch = None, -1
        while not stop.is_set():
            epoch, i = divmod(g, num_batches)
            if epoch != order_epoch:
                # every worker derives the SAME per-epoch order from the
                # seed, so index-sharding keeps global order deterministic
                order = (np.random.default_rng((seed, epoch))
                         .permutation(data_shape[0])
                         if shuffle else np.arange(data_shape[0]))
                order_epoch = epoch
            sel = order[i * batch_size:(i + 1) * batch_size]
            batch = data[sel]
            if transform is not None:
                batch = np.asarray(transform(batch), dtype=out_dtype)
            slot = g % slots
            while not stop.is_set():
                if empty_sems[slot].acquire(timeout=0.1):
                    break
            else:
                return
            ring[slot] = batch
            filled_sems[slot].release()
            g += num_workers
    finally:
        data_shm.close()
        out_shm.close()


class _MPEngine:
    """Worker processes + shared-memory ring (reference dataloader.py:125
    multiprocess queues, rebuilt on SharedMemory instead of pickled Queue
    traffic — one copy out of the ring per batch, zero per-batch pickling)."""

    def __init__(self, data, batch_size, num_batches, shuffle, seed,
                 num_workers, prefetch, transform, start=0):
        import multiprocessing as mp
        from multiprocessing import shared_memory
        # spawn: never fork a process that may hold a live XLA client
        self._mp = mp.get_context("spawn")
        self.num_batches = num_batches
        if data.shape[0] < num_batches * batch_size:
            # a ragged tail batch can't share the fixed-shape ring slots
            # (and XLA would retrace on it anyway)
            raise ValueError(
                "num_workers > 0 requires drop_last=True (ragged final "
                f"batch: {data.shape[0]} rows, batch {batch_size})")
        # ring slots: >= the worker fan-out (a worker blocking on a slot
        # must not deadlock the ring) AND a MULTIPLE of num_workers — the
        # consumer's slot-(g % slots) discipline assumes slot s is always
        # refilled by the same worker ((g + slots) % W == g % W); with an
        # indivisible slot count a fast worker could steal a slot one
        # epoch ahead and the consumer would read the wrong batch
        slots = max(2 * num_workers, int(prefetch))
        slots += (-slots) % num_workers
        probe = data[:batch_size]
        if transform is not None:
            probe = np.asarray(transform(probe))
        self._out_shape = probe.shape
        self._out_dtype = probe.dtype
        self._data_shm = shared_memory.SharedMemory(
            create=True, size=data.nbytes)
        np.ndarray(data.shape, data.dtype,
                   buffer=self._data_shm.buf)[...] = data
        self._out_shm = shared_memory.SharedMemory(
            create=True, size=int(np.prod((slots,) + probe.shape)
                                  * probe.dtype.itemsize))
        self._ring = np.ndarray((slots,) + probe.shape, probe.dtype,
                                buffer=self._out_shm.buf)
        self._slots = slots
        self._stop = self._mp.Event()
        self._empty = [self._mp.Semaphore(1) for _ in range(slots)]
        self._filled = [self._mp.Semaphore(0) for _ in range(slots)]
        self._procs = [
            self._mp.Process(
                target=_mp_worker,
                args=(w, num_workers, int(start), self._stop,
                      self._data_shm.name, data.shape, data.dtype,
                      self._out_shm.name, probe.shape, probe.dtype, slots,
                      self._empty, self._filled, batch_size, num_batches,
                      shuffle, seed, transform),
                daemon=True)
            for w in range(num_workers)]
        for p in self._procs:
            p.start()
        self._cursor = int(start)

    def next_batch(self):
        slot = self._cursor % self._slots
        self._filled[slot].acquire()
        batch = self._ring[slot].copy()
        self._empty[slot].release()
        self._cursor += 1
        return batch

    def stop(self):
        self._stop.set()
        for p in self._procs:
            p.join(timeout=2.0)
            if p.is_alive():
                p.terminate()
        for shm in (self._data_shm, self._out_shm):
            try:
                shm.close()
                shm.unlink()
            except FileNotFoundError:
                pass


class Dataloader:
    """Batched, optionally shuffled, DP-sliced iterator with prefetch.

    ``raw_data``: numpy array [N, ...].  ``dp_rank``/``dp_nrank`` shard the
    dataset like the reference (each data-parallel worker sees its slice).
    ``drop_last`` keeps shapes static for XLA (the reference re-plans on
    shape change; we default to dropping the ragged tail and only retrace
    when the user opts into it).
    """

    def __init__(self, raw_data, batch_size, shuffle=False, drop_last=True,
                 dp_rank=0, dp_nrank=1, seed=0, prefetch=2, name="data",
                 device_prefetch=False, dtype=None, transform=None,
                 num_workers=0, sharding=None):
        data = np.asarray(raw_data)
        if dp_nrank > 1:
            # contiguous equal shards; tail dropped so every rank agrees
            per = data.shape[0] // dp_nrank
            data = data[dp_rank * per:(dp_rank + 1) * per]
        self.data = data
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.name = name
        # device_prefetch: the producer thread uploads each batch with
        # jax.device_put as soon as it's sliced, so the host->device copy
        # overlaps the previous step instead of landing on the critical
        # path (a synchronous per-step upload is PCIe time the chip
        # spends idle).
        # ``sharding``: the committed layout for the batch (a
        # jax.sharding.Sharding) — under a dp/tp mesh the upload lands
        # sharded exactly as the compiled step's in_shardings expect,
        # instead of single-device + GSPMD reshard.
        self.device_prefetch = device_prefetch
        self.sharding = sharding
        self.dtype = dtype
        # transform: per-batch augmentation/tokenization callable.  Pure
        # Python transforms are GIL-bound — pair with num_workers>0 to
        # run them in worker processes (reference dataloader.py:125);
        # must be picklable (module-level function) in that case.
        self.transform = transform
        self.num_workers = int(num_workers)
        self._seed = seed + dp_rank
        self._prefetch = prefetch
        self._queue = queue.Queue(maxsize=prefetch)
        self._thread = None
        self._engine = None
        self._stop = threading.Event()
        self._start_batch = 0
        if self.num_batches == 0:
            raise ValueError(
                f"dataloader '{name}': shard of {data.shape[0]} rows "
                f"(dp_rank {dp_rank}/{dp_nrank}) yields no "
                f"batches of size {batch_size}")

    @property
    def num_batches(self):
        n = self.data.shape[0]
        return n // self.batch_size if self.drop_last else \
            (n + self.batch_size - 1) // self.batch_size

    # reference API names ---------------------------------------------------
    def get_batch_num(self, name=None):
        return self.num_batches

    def _epoch_perm(self, epoch):
        # keyed by (seed, epoch) — the exact stream the MP workers use, so
        # thread and process engines yield identical batch sequences
        return (np.random.default_rng((self._seed, epoch))
                .permutation(self.data.shape[0])
                if self.shuffle else np.arange(self.data.shape[0]))

    def skip_to_step(self, k):
        """Fast-forward the stream to global batch ``k`` in O(1) — the
        elastic trainer's resume hook: batch k of a skipped stream is
        bitwise the batch k an uninterrupted run would have produced,
        because every batch is a pure function of (seed, k) via the
        per-epoch permutation.  Must be called before the stream starts
        (no replaying a live queue)."""
        if self._thread is not None or self._engine is not None:
            raise RuntimeError(
                f"dataloader '{self.name}': skip_to_step({k}) after the "
                "stream started — position the stream before the first "
                "next_batch()/start()")
        if k < 0:
            raise ValueError(f"skip_to_step: k must be >= 0, got {k}")
        self._start_batch = int(k)
        return self

    def _producer(self):
        epoch, start_i = divmod(self._start_batch, self.num_batches)
        while not self._stop.is_set():
            order = self._epoch_perm(epoch)
            epoch += 1
            for i in range(start_i, self.num_batches):
                if self._stop.is_set():
                    return
                sel = order[i * self.batch_size:(i + 1) * self.batch_size]
                batch = self.data[sel]
                if self.transform is not None:
                    batch = np.asarray(self.transform(batch))
                if self.device_prefetch:
                    batch = self._to_device(batch)
                while not self._stop.is_set():
                    try:
                        self._queue.put(batch, timeout=0.1)
                        break
                    except queue.Full:
                        continue
            start_i = 0

    def start(self):
        if self.num_workers > 0:
            if self._engine is None:
                self._engine = _MPEngine(
                    self.data, self.batch_size, self.num_batches,
                    self.shuffle, self._seed, self.num_workers,
                    self._prefetch, self.transform,
                    start=self._start_batch)
            return self
        if self._thread is None:
            self._thread = threading.Thread(target=self._producer,
                                            daemon=True)
            self._thread.start()
        return self

    def _to_device(self, batch):
        import jax
        import jax.numpy as jnp
        batch = jnp.asarray(batch, dtype=self.dtype)
        if self.sharding is not None:
            return jax.device_put(batch, self.sharding)
        return jax.device_put(batch)

    def next_batch(self):
        self.start()
        if self._engine is not None:
            batch = self._engine.next_batch()
            if self.device_prefetch:
                batch = self._to_device(batch)
            return batch
        return self._queue.get()

    def stop(self):
        self._stop.set()
        if self._engine is not None:
            self._engine.stop()
            self._engine = None

    @property
    def batch_shape(self):
        """[batch, ...] shape AFTER the transform (what the graph sees)."""
        base = (self.batch_size,) + self.data.shape[1:]
        if self.transform is None:
            return base
        return np.asarray(
            self.transform(self.data[:self.batch_size])).shape

    def __iter__(self):
        """Single-epoch iteration without the prefetch machinery (eval
        loops); honors a prior :meth:`skip_to_step` by yielding the
        remainder of the positioned epoch."""
        epoch, start_i = divmod(self._start_batch, self.num_batches)
        order = self._epoch_perm(epoch)
        for i in range(start_i, self.num_batches):
            sel = order[i * self.batch_size:(i + 1) * self.batch_size]
            batch = self.data[sel]
            if self.transform is not None:
                batch = np.asarray(self.transform(batch))
            yield batch


class DataloaderOp(PlaceholderOp):
    """Graph node auto-fed from a Dataloader (reference DataloaderOp :289).

    ``dataloaders``: either one Dataloader or {subgraph_name: Dataloader}
    (the reference keys batch streams by named subexecutor: train/validate).
    The executor recognizes the ``auto_feed`` hook and pulls the next batch
    when the user did not feed the node explicitly.
    """

    __slots__ = ("dataloaders",)

    def __init__(self, dataloaders, dtype=np.float32, name=None):
        if not isinstance(dataloaders, dict):
            dataloaders = {"default": dataloaders}
        self.dataloaders = dataloaders
        some = next(iter(dataloaders.values()))
        super().__init__(name or f"dataloader_{some.name}",
                         shape=tuple(some.batch_shape), dtype=dtype)

    def auto_feed(self, subgraph_name):
        dl = self.dataloaders.get(subgraph_name)
        if dl is None:
            dl = self.dataloaders.get("default")
        if dl is None:
            raise ValueError(
                f"DataloaderOp {self.name} has no stream for subgraph "
                f"'{subgraph_name}' (streams: {list(self.dataloaders)})")
        return dl.next_batch()


def dataloader_op(dataloaders, dtype=np.float32, name=None):
    return DataloaderOp(dataloaders, dtype=dtype, name=name)
