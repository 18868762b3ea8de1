"""Executor: named subgraphs compiled to jitted XLA programs.

Reference: /root/reference/python/hetu/gpu_ops/executor.py — `Executor` holds
named subgraphs (train/validate/...) each run by a `SubExecutor` that topo
sorts, infers shapes, plans memory, and dispatches kernels per node per step.

TPU redesign: each named subgraph becomes ONE jitted pure function
``(params, opt_state, feeds, key) -> (outputs, new_params, new_opt_state)``.
XLA replaces the per-node dispatch loop, the stream/event machinery
(executor.py:351-380, :1227-1246), the memory planner (memory_pool.py — XLA's
buffer assignment does arena reuse), and shape inference (shapes specialize at
trace time; a new feed shape simply triggers a retrace, mirroring the
reference's re-plan on shape change at executor.py:938-1051).

Distribution hooks: when a `mesh` (parallel/mesh.py) is attached, parameter
and feed shardings are derived from node `dist_state` annotations and passed
to jit as in_shardings — GSPMD then inserts the collectives the reference
materialized by hand in its graph-rewrite pass (context.py:1469).
"""

from __future__ import annotations

import functools
import time
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from .node import (Op, PlaceholderOp, VariableOp, find_topo_sort,
                   graph_variables, named_scope)
from .trace import TraceContext, evaluate
from .. import telemetry as _telemetry
from ..telemetry.steps import StepWatch


def _changed_state_only(step_fn):
    """``step_fn`` returning, of params and optimiser state, only the
    entries it replaced (found by identity while tracing: an untouched
    entry is the argument itself)."""
    def changed(params, opt_state, feeds, base_key, step):
        vals, new_params, new_opt_state, step = step_fn(
            params, opt_state, feeds, base_key, step)
        return (vals,
                {k: v for k, v in new_params.items()
                 if v is not params.get(k)},
                {k: v for k, v in new_opt_state.items()
                 if v is not opt_state.get(k)},
                step)
    return changed


def _as_traced(value, dtype):
    """A host feed as a numpy array in the dtype the step program was
    traced with: the placeholder's, else the value's own, canonicalised as
    ``jnp.asarray`` would (``float64`` becomes ``float32`` unless x64 is
    on), so the cast happens once, on the host, and nothing retraces."""
    value = np.asarray(value)
    return value.astype(jax.dtypes.canonicalize_dtype(
        value.dtype if dtype is None else dtype), copy=False)


class SubExecutor:
    """One named subgraph compiled into a single jitted step function."""

    def __init__(self, name, eval_nodes, executor):
        self.name = name
        self.eval_nodes = list(eval_nodes)
        self.executor = executor
        self.topo = find_topo_sort(self.eval_nodes)
        self.placeholders = [n for n in self.topo
                             if isinstance(n, PlaceholderOp)]
        self.variables = [n for n in self.topo if isinstance(n, VariableOp)]
        self.opt_ops = [n for n in self.topo if n.is_stateful
                        and hasattr(n, "init_state")]
        # train/eval mode: training iff the subgraph optimizes or explicitly
        # differentiates, unless the subgraph name marks it as evaluation
        # (reference: inference flag on SubExecutor, executor.py:733).
        has_grads = any(hasattr(n, "_compute_with_env") for n in self.topo)
        self.training = executor.config.get(
            "training",
            (len(self.opt_ops) > 0 or has_grads)
            and name not in ("validate", "inference", "eval"))
        # PS-backed embeddings (ps/embedding.py PSRowsOp): gathered rows
        # enter as feeds; their grads leave as hidden outputs pushed to the
        # host store after the step (reference hybrid comm_mode, where
        # embedding params bypass the dense path via PS push/pull).
        self.ps_rows = [p for p in self.placeholders
                        if hasattr(p, "ps_embedding")]
        self._ps_grad_nodes = []
        if self.training and self.ps_rows:
            losses = [op.loss for op in self.opt_ops
                      if getattr(op, "loss", None) is not None]
            if losses:
                from .autodiff import gradients
                # PS rows may feed any optimized loss: differentiate their
                # sum (total sensitivity) so no server update is dropped
                total = losses[0]
                for extra in losses[1:]:
                    total = total + extra
                self._ps_grad_nodes = gradients(total, self.ps_rows)
        self._all_eval = self.eval_nodes + self._ps_grad_nodes
        if self._ps_grad_nodes:
            self.topo = find_topo_sort(self._all_eval)
        self._ps_pending = []
        self._jitted = None
        self._multi_jitted = None   # lazily-built run_steps program
        self._numerics_layers = None  # set by _build when a monitor rides
        self._numerics_sample = 1     # in-graph stats sampling cadence
        self._jitted_stats = None     # stats-bearing twin (sampled mode)
        # fast-path cache for steady-state training loops: the first
        # slow-path run() caches the feed pytree STRUCTURE — key set,
        # canonical names, declared dtypes, which placeholders are
        # dataloader-fed — so subsequent steps skip the per-call feed
        # validation/cast/dataloader-resolution walk and only swap leaf
        # buffers.  Keyed on structure, not dict identity: a prefetcher
        # handing over a fresh dict of device batches every step stays
        # on the fast path (in-place value swaps in one dict do too).
        self._fast_feed = None
        # monitor variables: non-trainable in-graph counters (e.g. the
        # BERT MLM bucket-overflow total) polled host-side every
        # monitor_interval steps — works on every platform, unlike host
        # callbacks (VERDICT r3 item 7)
        self._monitor_vars = [v for v in self.variables
                              if getattr(v, "monitor", None) is not None]
        self._monitor_interval = int(
            executor.config.get("monitor_interval", 200))
        self._runs = 0  # per-subgraph step count (monitor poll schedule)
        # runtime telemetry (telemetry/): instruments are near-free
        # no-ops until telemetry.enable() — the step path carries them
        # unconditionally (cost pinned by tests/test_telemetry.py)
        reg = _telemetry.get_registry()
        self._m_steps = reg.counter(
            "hetu_executor_steps_total",
            "Executor steps dispatched (run() calls + run_steps inner "
            "steps)", labels=("subgraph",)).labels(subgraph=name)
        self._m_step_time = reg.histogram(
            "hetu_executor_step_seconds",
            "Wall time of one run() or run_steps() call, the duration of "
            "its root span "
            "(feed prep + dispatch + guard check, and the fetch when the "
            "caller asks for numpy values; without it device completion "
            "is asynchronous)",
            labels=("subgraph",)).labels(subgraph=name)
        self._m_h2d_bytes = reg.counter(
            "hetu_executor_h2d_bytes_total",
            "Bytes of feeds that arrived as host arrays and were "
            "uploaded by the executor (size after the cast to the "
            "placeholder's dtype)",
            labels=("subgraph",)).labels(subgraph=name)
        uploads = reg.counter(
            "hetu_executor_feed_uploads_total",
            "Feeds that arrived as host arrays, counted where the "
            "executor uploads them, all of a step's in one device_put: "
            "sharded under the step's in_shardings on the mesh, default "
            "on the default device (no mesh)",
            labels=("subgraph", "placed"))
        self._m_uploads = {placed: uploads.labels(subgraph=name,
                                                  placed=placed)
                           for placed in ("sharded", "default")}
        self._feed_sh = None   # the step's feed in_shardings (_build)
        self._m_multi = reg.counter(
            "hetu_executor_run_steps_calls_total",
            "run_steps() multi-step dispatches",
            labels=("subgraph",)).labels(subgraph=name)
        self._m_retrace = reg.counter(
            "hetu_executor_retraces_total",
            "Step-program (re)traces — >1 per subgraph after warmup "
            "means a shape/dtype change recompiled the step",
            labels=("subgraph",)).labels(subgraph=name)
        self._m_donates = reg.gauge(
            "hetu_executor_donates_state",
            "1 when the subgraph's step program was built to take params "
            "and optimiser state as donated arguments (updated in place, "
            "no fresh output buffers a step), 0 when it leaves them alive",
            labels=("subgraph",)).labels(subgraph=name)
        self._m_stalls = reg.counter(
            "hetu_executor_step_stalls_total",
            "run roots that closed as a stall (telemetry/steps.py: over "
            "the running median by a quarter of it and by 50 ms, or "
            "carrying an XLA event once the median stands), by the phase "
            "with the largest excess over its own median (h2d, dispatch, "
            "fetch, run_self) and the cause (xla, paging, runq, host_cpu, "
            "blocked)", labels=("subgraph", "phase", "cause"))
        self._m_excess = reg.counter(
            "hetu_executor_step_excess_seconds_total",
            "Seconds of run roots over the running median of the last 64 "
            "that carried no XLA event, every root",
            labels=("subgraph",)).labels(subgraph=name)
        self._watch = StepWatch()
        self._tr = _telemetry.get_tracer()

    def ps_synchronize(self):
        """Wait for all in-flight PS pushes (call before reading tables
        directly or checkpointing the host store)."""
        first_error = None
        for f in self._ps_pending:
            try:
                f.result()
            except Exception as e:   # drain everything, report once
                if first_error is None:
                    first_error = e
        self._ps_pending.clear()
        for p in self.ps_rows:
            p.ps_embedding.synchronize()
        if first_error is not None:
            raise first_error

    def _should_donate(self):
        """Whether the step program takes params and optimiser state as
        donated arguments (argnums 0, 1 beside the step counter's 4).

        Rule: a training subgraph donates; an evaluation subgraph never
        does (its caller goes on reading the weights it was given).
        ``Executor(..., donate_params=True/False)`` overrides the rule for
        training subgraphs.

        A step that returns its state in fresh buffers holds the host in
        PJRT's output allocation before every launch, 20-35 us a MB, with
        the device idle; donation lets the program update the state in
        place.  Its price is on the device: XLA stages the update fusions
        in scoped memory and copies each updated parameter back.  Measured
        on a v5e, donation off -> on (PERF.md, PR 25):

        * BERT-base, AdamW, bf16 over f32 masters, batch 64 x 512, loss
          fetched every step (620 leaves, 1.32 GB): one chip 134.7 k ->
          153.2 k tokens/s (+13.7%), step program 207.6 -> 208.8 ms;
          DataParallel(4) 482.7 k -> 562.6 k (+16.6%), step program
          219.1 -> 221.0 ms, XLA's peak a chip 15.31 -> 15.68 GB.
        * W&D, Adam, batch 128, 337,000 rows x 16 packed (34 leaves,
          68 MB): 296 -> 833 steps/s with no fetch (the host runs
          ahead), 264 -> 623 with the loss fetched every step; device
          time a step 326 -> 369 us in both.

        No measured model loses by wall clock, and state that fills more
        of HBM or a lazy-sparse table (whose scatter needs the alias to
        stay rowwise) only gains more, so the rule has no threshold.
        """
        if not self.training:
            return False
        cfg = self.executor.config.get("donate_params", "auto")
        return cfg == "auto" or bool(cfg)

    def _donate_argnums(self):
        """``donate_argnums`` of a step program about to be built, and
        the gauge that says which it was."""
        donates = self._should_donate()
        self._m_donates.set(int(donates))
        return (0, 1, 4) if donates else (4,)

    def _build(self):
        placeholders = self.placeholders
        eval_nodes = self._all_eval
        topo = self.topo
        training = self.training
        mesh = self.executor.mesh
        compute_dtype = self.executor.compute_dtype
        # resilience.StepGuard: traced INTO the step when attached, so
        # the sentinel reductions fuse with the updates they check
        guard = self.executor.config.get("step_guard")
        guard_losses = ([op.loss for op in self.opt_ops
                         if getattr(op, "loss", None) is not None]
                        if guard is not None else [])
        # telemetry.NumericsMonitor: like the guard sentinel, the
        # per-layer stats vector is traced INTO the step when a monitor
        # is attached, so each L2 reduce fuses with the grad/update
        # computation that produced the tensor.  The layer spec is
        # static (optimizer var lists, keyed by profiling.layer_of), so
        # the row order is fixed before any trace runs.
        numerics_groups = None
        numerics_sample = 1
        self._numerics_layers = None
        self._numerics_sample = 1
        if (self.executor.config.get("numerics") is not None
                and self.training and self.opt_ops):
            numerics_sample = max(1, int(getattr(
                self.executor.config["numerics"], "sample_every", 1)))
            self._numerics_sample = numerics_sample
            from ..telemetry.profiling import layer_of
            groups = {}
            for op in self.opt_ops:
                if not hasattr(op, "var_list"):
                    continue
                for var, gnode in zip(op.var_list,
                                      op.inputs[:len(op.var_list)]):
                    groups.setdefault(layer_of(var.name), []).append(
                        (var, gnode, None))
                for var, sites in getattr(op, "sparse", None) or []:
                    groups.setdefault(layer_of(var.name), []).append(
                        (var, None, sites))
            if groups:
                numerics_groups = list(groups.items())
                self._numerics_layers = tuple(groups)

        def cast(x):
            if compute_dtype is not None and jnp.issubdtype(
                    x.dtype, jnp.floating):
                return x.astype(compute_dtype)
            return x

        # skip the per-step key derivation entirely when nothing in the
        # subgraph draws random bits (dropout/noise ops) — the threefry
        # fold_in is small but pure overhead on RNG-free models (W&D,
        # ResNet eval, ...)
        needs_rng = any(getattr(n, "needs_rng", False) for n in topo)

        def step_fn(params, opt_state, feeds, base_key, step,
                    _stats="cond"):
            # host-side retrace witness: runs at TRACE time only, so the
            # counter ticks once per compiled program variant.
            # ``_stats`` is a python-level mode bound per program
            # variant (functools.partial below, never traced): None
            # emits no stats outputs (byte-identical to an unmonitored
            # step), "full" emits the row unconditionally, "cond"
            # emits it under the in-graph sample_every lax.cond
            # (run_steps' amortized path).
            self._m_retrace.inc()
            # the per-step key derives INSIDE the program from a
            # device-resident step counter — an eager fold_in per run()
            # would dispatch a separate device op each step (tens of us of
            # launch overhead, which small models would feel)
            key = (jax.random.fold_in(base_key, step) if needs_rng
                   else base_key)
            # mixed precision: forward/backward run in compute_dtype while
            # optimizers update the full-precision masters (the standard
            # TPU bf16-compute / f32-master-weights policy).
            ctx = TraceContext(key=key, training=training, mesh=mesh,
                               cp_impl=self.executor.config.get(
                                   "cp_impl", "ring"),
                               master_params=(params if compute_dtype
                                              is not None else None))
            ctx.opt_state = opt_state
            bindings = {}
            with named_scope("hetu_param_cast"):
                for v in self.variables:
                    bindings[v] = cast(params[v.name])
            for p in placeholders:
                bindings[p] = cast(feeds[p.name])
            vals, env = evaluate(eval_nodes, bindings, ctx, topo=topo)
            new_params = dict(params)
            for var, val in ctx.updates.items():
                new_params[var.name] = val.astype(params[var.name].dtype)
            new_opt_state = dict(opt_state)
            new_opt_state.update(ctx.new_opt_state)
            nstats = None
            if numerics_groups is not None and _stats is not None:
                # fused per-layer stats: sums of squares of the grad,
                # the ATTEMPTED update delta (pre skip-select, so a
                # poisoned step shows its non-finite norms even when
                # the guard discards it), and the current params — one
                # [n_layers, 3] f32 row block per step.  Sqrt happens
                # host-side; NaN/inf propagate through the sums, so a
                # non-finite row IS the per-layer finite flag.
                def _sumsq(x):
                    x = x.astype(jnp.float32)
                    return jnp.sum(x * x)

                def _nstats():
                    rows = []
                    for _layer, entries in numerics_groups:
                        gsq = jnp.float32(0)
                        usq = jnp.float32(0)
                        psq = jnp.float32(0)
                        for var, gnode, sites in entries:
                            old = params[var.name]
                            psq = psq + _sumsq(old)
                            new = ctx.updates.get(var)
                            if new is not None:
                                usq = usq + _sumsq(
                                    new.astype(jnp.float32)
                                    - old.astype(jnp.float32))
                            if gnode is not None and gnode in env:
                                gsq = gsq + _sumsq(env[gnode])
                            for rnode, _ids in (sites or ()):
                                if rnode in env:
                                    # sparse tables: L2 over the batch's
                                    # touched row grads (dense rows are
                                    # 0)
                                    gsq = gsq + _sumsq(env[rnode])
                        rows.append(jnp.stack([gsq, usq, psq]))
                    return jnp.stack(rows)

                if _stats == "cond" and numerics_sample > 1:
                    # sampled cadence inside run_steps' fori_loop: the
                    # reductions run only on every sample_every-th
                    # inner step (real control flow, not a select);
                    # the loop carry keeps the latest SAMPLED row, so
                    # the zeros filler is never surfaced.  The single-
                    # step run() path never pays even the cond — it
                    # switches between the plain and "full" compiled
                    # programs host-side on the same cadence.
                    nstats = jax.lax.cond(
                        (step % jnp.uint32(numerics_sample)) == 0,
                        _nstats,
                        lambda: jnp.zeros((len(numerics_groups), 3),
                                          jnp.float32))
                else:
                    nstats = _nstats()
            if guard is not None:
                # fused guard sentinel: one scalar conjunction over loss
                # finiteness and every parameter update written this step
                # (optimizer slots are poisoned iff the param is, so
                # checking params covers both at half the reads).  The
                # loss sum doubles as the host-side spike signal.
                gloss = jnp.float32(0)
                seen = False
                for lnode in guard_losses:
                    if lnode in env:
                        gloss = gloss + jnp.sum(env[lnode]).astype(
                            jnp.float32)
                        seen = True
                if not seen:
                    # eval-only subgraph: guard its floating outputs
                    for v in vals:
                        if v is not None and jnp.issubdtype(
                                jnp.result_type(v), jnp.floating):
                            gloss = gloss + jnp.sum(v).astype(jnp.float32)
                gfin = jnp.isfinite(gloss)
                for var, val in ctx.updates.items():
                    if jnp.issubdtype(jnp.result_type(
                            new_params[var.name]), jnp.floating):
                        gfin = jnp.logical_and(
                            gfin, jnp.all(jnp.isfinite(
                                new_params[var.name])))
                if guard.policy == "skip":
                    # discard the poisoned update IN-GRAPH: params and
                    # opt-state roll forward only on a clean sentinel, so
                    # a NaN step can never corrupt persistent state
                    for var in ctx.updates:
                        new_params[var.name] = jnp.where(
                            gfin, new_params[var.name], params[var.name])
                    for k in ctx.new_opt_state:
                        new_opt_state[k] = jax.tree_util.tree_map(
                            lambda nv, ov: jnp.where(gfin, nv, ov),
                            new_opt_state[k], opt_state[k])
            # hidden trailing outputs, strip order (last-first in
            # _dispatch): [.., nstats][gfin, gloss]
            if nstats is not None:
                vals = list(vals) + [nstats]
            if guard is not None:
                vals = list(vals) + [gfin, gloss]
            return vals, new_params, new_opt_state, step + 1

        self._step_fn = step_fn   # run_steps builds its scan over this
        donate = self._donate_argnums()
        # single-step program variants: on a sampled cadence the
        # steady-state program carries NO stats (the stats reductions
        # would otherwise pin the pre-update params live across the
        # update — a cond can't help, its operand liveness is static —
        # costing a params copy per step); the "full" twin runs only
        # on every sample_every-th dispatch.
        single = step_fn
        stats_fn = None
        if numerics_groups is not None:
            if numerics_sample == 1:
                single = functools.partial(step_fn, _stats="full")
            else:
                single = functools.partial(step_fn, _stats=None)
                stats_fn = functools.partial(step_fn, _stats="full")
        in_shardings = self.executor._input_shardings(self)
        #: (params', optimiser state's) shardings under a mesh, else None
        self._state_sh = None
        # where ``_upload`` puts the step's host feeds: the feed entry of
        # the in_shardings the program is jitted with, None with no mesh
        self._feed_sh = None if in_shardings is None else in_shardings[2]
        self._jitted_stats = None
        # A program that does not take its state donated would return
        # every leaf it only read as a fresh copy (jit does not forward an
        # input to an output): a second whole state on the device for the
        # length of the call, which a state that fills half of HBM cannot
        # afford (OLMoE's validate program: 7.5 GB of state, PR 26).  It
        # returns the leaves it changed and ``_dispatch`` merges them
        # (under a mesh their shardings are left to the compiler, since
        # out_shardings are fixed before the trace says which leaves change,
        # and ``_dispatch`` puts them where the next call takes them).
        self._returns_changed_only = donate == (4,)
        if self._returns_changed_only:
            single = _changed_state_only(single)
            if stats_fn is not None:
                stats_fn = _changed_state_only(stats_fn)
        if in_shardings is not None:
            self.executor._commit_state(in_shardings)
            # pin updated params/opt-state to their INPUT shardings: with
            # interior reshard constraints in the program, GSPMD may
            # otherwise emit new param values in a different layout,
            # which would mismatch the next call's in_shardings (and
            # defeat donation aliasing).  Eval outputs gather replicated
            # (reference reduceMean/gatherPredict, executor.py:680).
            from ..parallel.mesh import replicated
            rep = replicated(self.executor.mesh)
            param_sh, opt_sh, _, _, _ = in_shardings
            self._state_sh = (param_sh, opt_sh)
            out_shardings = ((rep, None, None, rep)
                             if self._returns_changed_only
                             else (rep, param_sh, opt_sh, rep))
            self._jitted = jax.jit(single, donate_argnums=donate,
                                   in_shardings=in_shardings,
                                   out_shardings=out_shardings)
            if stats_fn is not None:
                self._jitted_stats = jax.jit(
                    stats_fn, donate_argnums=donate,
                    in_shardings=in_shardings,
                    out_shardings=out_shardings)
        else:
            self._jitted = jax.jit(single, donate_argnums=donate)
            if stats_fn is not None:
                self._jitted_stats = jax.jit(stats_fn,
                                             donate_argnums=donate)

    def _fast_resolve(self, feed_dict):
        """Steady-state dispatch: swap leaf buffers into the cached feed
        structure.  Returns the canonical feeds dict, or None (disarming
        the cache) when the structure or value classes changed — a
        wrong-dtype device array must not silently retrace a new program
        variant, and numpy leaves still need the slow path's cast."""
        pairs, autos = self._fast_feed
        if len(feed_dict or {}) != len(pairs):
            self._fast_feed = None
            return None
        feeds = {}
        for key, name, want in pairs:
            v = feed_dict.get(key)
            if not isinstance(v, jax.Array) or (
                    want is not None and v.dtype != want):
                self._fast_feed = None
                return None
            feeds[name] = v
        host = {}
        for p, want in autos:
            # dataloader-fed: a device-prefetched batch in the declared
            # dtype passes straight through (no host round-trip); host
            # batches get the one cast and upload the slow path would do
            v = p.auto_feed(self.name)
            if not isinstance(v, jax.Array):
                host[p.name] = _as_traced(v, want)
            elif want is not None and v.dtype != want:
                v = jnp.asarray(v, dtype=want)
            feeds[p.name] = v
        feeds.update(self._upload(host))
        return feeds

    def _upload(self, host):
        """The step's host feeds (numpy, already in the dtypes the program
        was traced with) onto the device in ONE ``device_put``, each where
        the jitted step's in_shardings wants it: under a mesh the call
        then receives committed arrays that already have its input
        shardings and moves nothing; an uncommitted array on one device
        would be fetched back, cut up and uploaded again INSIDE the call,
        a host round trip a feed with every device waiting.  With no mesh
        the place is the default device."""
        if not host:
            return host
        shardings, placed = None, "default"
        if self._feed_sh is not None:
            shardings, placed = {k: self._feed_sh[k] for k in host}, "sharded"
        self._m_uploads[placed].inc(len(host))
        self._m_h2d_bytes.inc(sum(v.nbytes for v in host.values()))
        return jax.device_put(host, shardings)

    def _rooted(self, impl, *args):
        """``impl(*args)`` under the ``run`` root of one call: every phase
        span below is its child and carries its key, ``<subgraph>:<global
        step>``; in a ``jax.profiler`` capture it is a step marker.  The
        root carries the thread's OS account and whatever XLA did beneath
        it (``telemetry/tracing.py``); when it closes, its wall time goes
        to the step histogram and to the subgraph's :class:`StepWatch`,
        which names a stalled step."""
        step = self.executor._global_step
        root = self._tr.span("run", key=f"{self.name}:{step}", step=step,
                             account=True)
        try:
            with root:
                return impl(*args)
        finally:
            if root.kids is not None:   # not a tracer switched off since
                self._close_root(root)

    def _close_root(self, root):
        self._m_step_time.observe(root.dur)
        excess, stall = self._watch.close(root.dur, root.kids, root.extra)
        if excess:
            self._m_excess.inc(excess)
        if stall is not None:
            self._m_stalls.labels(subgraph=self.name, phase=stall["phase"],
                                  cause=stall["cause"]).inc()
            _telemetry.get_flight().record(
                {"type": "step_stall", "subgraph": self.name,
                 "key": root.key, "start_s": root.start,
                 "wall_s": root.dur, **stall, "account": root.extra})

    def run(self, feed_dict=None, convert_to_numpy_ret_vals=False):
        if not self._tr.enabled:
            return self._run_impl(feed_dict, convert_to_numpy_ret_vals)
        try:
            return self._rooted(self._run_impl, feed_dict,
                                convert_to_numpy_ret_vals)
        finally:
            self._m_steps.inc()

    def _run_impl(self, feed_dict, convert_to_numpy_ret_vals):
        if self._jitted is None:
            # "compile" span: program construction alone (the graph walk
            # and the jit wrapper, milliseconds).  XLA's trace, lowering
            # and compile (or cache read) happen inside the first
            # dispatch; jax.monitoring reports them and they land in this
            # root's ``extra`` as xla_* (telemetry/tracing.py), from where
            # the goodput ledger adds them to its compile bucket.
            with self._tr.span("compile"):
                self._build()
        feeds, ps_ids = self._feeds(feed_dict)
        return self._dispatch(self.executor, feeds, ps_ids,
                              convert_to_numpy_ret_vals)

    def _feeds(self, feed_dict):
        """``(feeds, ps_ids)`` of one call, by the cached structure where
        it is armed and still fits, else by the full walk."""
        # "h2d" phase: everything between entry and the jitted call —
        # feed canonicalization, casts, uploads, PS row gathers
        with self._tr.span("h2d"):
            feeds = (self._fast_resolve(feed_dict)
                     if self._fast_feed is not None else None)
            if feeds is not None:
                return feeds, None
            return self._slow_feeds(feed_dict)

    def _slow_feeds(self, feed_dict):
        """Full per-call feed canonicalization walk; returns
        ``(feeds, ps_ids)`` and may arm the fast path for next step."""
        feeds = {}
        feed_dict = feed_dict or {}
        for node, value in feed_dict.items():
            name = node.name if isinstance(node, Op) else node
            feeds[name] = value
        # dataloader nodes: pull the next prefetched batch for any node the
        # user didn't feed explicitly (reference DataloaderOp streams)
        auto_names = set()
        for p in self.placeholders:
            if p.name not in feeds and hasattr(p, "auto_feed"):
                feeds[p.name] = p.auto_feed(self.name)
                auto_names.add(p.name)
        # PS embeddings: issue ASYNC row gathers through each table's
        # worker thread (ordered after the previous step's async grad
        # push), then resolve after the rest of feed prep — so host
        # store/cache traffic overlaps the still-running previous device
        # step (reference SparsePull prefetch path,
        # ParameterServerCommunicate.py:40-56 + executor.py:1541-1567)
        ps_ids = {}
        ps_futs = {}
        for p in self.ps_rows:
            ids_name = p.ids_node.name
            if ids_name not in feeds:
                raise ValueError(
                    f"PS embedding {p.name} needs ids feed '{ids_name}'")
            ids_val = np.asarray(feeds[ids_name])
            if p.inv_node is not None:
                # unique-feed: gather only the batch's unique rows (bucket-
                # padded with -1, which the store reads as zeros and drops
                # on push) and feed the gather indices alongside
                from ..ps.embedding import _bucket
                uniq, inv = np.unique(ids_val, return_inverse=True)
                keys = np.full(_bucket(uniq.size), -1, np.int64)
                keys[:uniq.size] = uniq
                feeds[p.inv_node.name] = inv.reshape(
                    ids_val.shape).astype(np.int32)
                ps_ids[p.name] = keys
                ps_futs[p.name] = p.ps_embedding.lookup_async(keys)
            else:
                ps_ids[p.name] = ids_val
                ps_futs[p.name] = p.ps_embedding.lookup_async(ids_val)
        for p in self.ps_rows:
            rows = ps_futs[p.name].result()
            if p.inv_node is not None:
                feeds[p.name] = rows
            else:
                ids_val = ps_ids[p.name]
                # shape follows the FED ids (a new batch size just
                # retraces, per the executor's shape contract above)
                feeds[p.name] = rows.reshape(
                    ids_val.shape + (p.ps_embedding.embedding_dim,))
        missing = [p.name for p in self.placeholders if p.name not in feeds]
        if missing:
            raise ValueError(f"missing feeds for placeholders: {missing}")
        # drop feeds that aren't placeholders of THIS subgraph (e.g. ids
        # consumed only by the PS lookup above): extra keys would change the
        # jit pytree and break against in_shardings
        names = {p.name for p in self.placeholders}
        feeds = {k: v for k, v in feeds.items() if k in names}
        # cast feeds to declared dtypes (reference DataloaderOp feeds
        # float32): host arrays on the host, then up in one call
        all_device = True
        dtypes = {}
        host = {}
        for p in self.placeholders:
            v = feeds[p.name]
            want = np.dtype(p.dtype) if p.dtype is not None else None
            dtypes[p.name] = want
            if not isinstance(v, jax.Array):
                if p.name not in auto_names:
                    all_device = False
                host[p.name] = _as_traced(v, want)
            elif want is not None and v.dtype != want:
                # wrong-dtype DEVICE array: cast (device-side) instead of
                # silently retracing a second program variant
                if p.name not in auto_names:
                    all_device = False
                feeds[p.name] = v.astype(want)
        feeds.update(self._upload(host))
        self._arm_fast(feed_dict, feeds, names, dtypes, auto_names,
                       all_device)
        return feeds, ps_ids

    def _arm_fast(self, feed_dict, feeds, names, dtypes, auto_names,
                  all_device):
        """Cache the feed pytree structure so the NEXT step skips the
        canonicalization walk.  Armed when every user-fed leaf is a
        device array in its declared dtype (dataloader-fed leaves are
        resolved per step regardless) and nothing host-interactive (PS
        rows, extra keys) is involved."""
        if not all_device or self.ps_rows:
            return
        pairs = []
        for key in feed_dict:
            name = key.name if isinstance(key, Op) else key
            if name not in names or name in auto_names:
                return      # extra key or shadowing a dataloader node
            pairs.append((key, name, dtypes.get(name)))
        if len({nm for _, nm, _ in pairs}) != len(pairs):
            return          # two keys canonicalize to one placeholder
        if len(pairs) + len(auto_names) != len(feeds):
            return
        autos = [(p, dtypes[p.name]) for p in self.placeholders
                 if p.name in auto_names]
        self._fast_feed = (pairs, autos)

    def _dispatch(self, ex, feeds, ps_ids, convert_to_numpy_ret_vals):
        if ex._step_arr is None:
            ex._step_arr = ex._step_counter()
        # numerics cadence for the step about to run (counter value
        # ex._global_step): off-cadence steps run the plain program —
        # zero stats cost, not even a cond — the sampled ones run the
        # stats-bearing twin
        has_stats = self._numerics_layers is not None and (
            self._numerics_sample == 1
            or ex._global_step % self._numerics_sample == 0)
        fn = (self._jitted_stats
              if has_stats and self._jitted_stats is not None
              else self._jitted)
        ex._global_step += 1
        # "dispatch" phase: the jitted call itself — asynchronous on
        # accelerators, so time spent HERE past the enqueue cost is
        # runtime back-pressure (in-flight queue full ≈ device-bound)
        with self._tr.span("dispatch"):
            vals, new_params, new_opt_state, ex._step_arr = fn(
                ex.params, ex.opt_state, feeds, ex._base_key,
                ex._step_arr)
        if self._returns_changed_only:
            if self._state_sh is not None:
                new_params, new_opt_state = (
                    {k: jax.device_put(v, sh[k]) for k, v in new.items()}
                    for new, sh in zip((new_params, new_opt_state),
                                       self._state_sh))
            new_params = {**ex.params, **new_params}
            new_opt_state = {**ex.opt_state, **new_opt_state}
        ex.params = new_params
        ex.opt_state = new_opt_state
        # guard sentinel scalars ride as the two trailing hidden outputs
        guard = ex.config.get("step_guard")
        guard_out = None
        if guard is not None:
            guard_out, vals = vals[-2:], vals[:-2]
        # the per-layer numerics stats block rides just before them
        # (only on the stats-bearing program — off-cadence dispatches
        # emit no row at all)
        nstats_out = None
        if has_stats:
            nstats_out, vals = vals[-1], vals[:-1]
        # poll monitor counters after this SUBGRAPH's first step and
        # every interval of ITS runs (a global-step schedule can
        # permanently miss a subgraph under alternating train/validate);
        # np.asarray syncs on a scalar — negligible at the interval.
        # Executor.check_monitors() is the final flush.
        self._runs += 1
        if self._monitor_vars and (
                self._runs == 1
                or self._runs % self._monitor_interval == 0):
            self.check_monitors()
        # push PS-embedding grads ASYNC: the device array goes straight to
        # the table's worker thread, which blocks on the device→host copy
        # there — run() returns without waiting for the step, so the push
        # (and the next step's lookups, queued behind it) hide under
        # device compute.  push-then-lookup ordering per table keeps the
        # consistency mode intact; pull_bound/push_bound staleness applies
        # as before inside the cache.
        if self._ps_grad_nodes:
            n_user = len(self.eval_nodes)
            for p, gval in zip(self.ps_rows, vals[n_user:]):
                # start the device→host copy NOW, non-blocking; by the
                # time the table worker materializes the array the bytes
                # are (mostly) already on the host
                try:
                    gval.copy_to_host_async()
                except AttributeError:
                    pass
                fut = p.ps_embedding.push_grad_async(
                    ps_ids[p.name], gval, deduped=p.inv_node is not None)
                self._ps_pending.append(fut)
            # surface worker-thread errors, keep the list bounded
            done = [f for f in self._ps_pending if f.done()]
            for f in done:
                f.result()
                self._ps_pending.remove(f)
            vals = vals[:n_user]
        if nstats_out is not None:
            # BEFORE the guard check, so a trip this step can attribute
            # its culprit layer from the freshly queued stats row
            with self._tr.span("numerics"):
                ex.config["numerics"].on_step(
                    ex, self._numerics_layers, ex._global_step,
                    nstats_out)
        if guard_out is not None:
            # after PS pushes so a rollback can't orphan in-flight grads;
            # may restore executor state or raise GuardTripped (abort)
            with self._tr.span("guard_check"):
                guard.on_step(ex, guard_out[0], guard_out[1])
        return self._fetch(vals) if convert_to_numpy_ret_vals else vals

    def _fetch(self, vals):
        """``fetch`` phase: the step's one synchronisation point — the
        wait for the device and the copy of the results to the host.
        Where a step hands out several values, every copy is asked for
        before the first is waited for, so the copies follow the step's
        program back to back and the host sleeps once, not once a value
        with the device idle between.  One value is simply waited for: a
        copy asked for ahead of the program's end is one more hand-over
        between the runtime's threads, which several values repay and one
        does not."""
        with self._tr.span("fetch"):
            ahead = [v for v in vals if hasattr(v, "copy_to_host_async")]
            if len(ahead) > 1:
                for v in ahead:
                    v.copy_to_host_async()
            return [None if v is None else np.asarray(v) for v in vals]

    def run_steps(self, feed_dict, n, convert_to_numpy_ret_vals=False):
        """Run ``n`` consecutive training steps on the SAME feeds in ONE
        device dispatch: an in-graph ``lax.fori_loop`` over the step
        function, returning the LAST step's values.

        Per-step host dispatch costs tens of us of launch overhead — for
        small models that rivals the step itself, so this amortizes it
        n-fold.  The
        device-resident step counter keeps per-step RNG identical to n
        ``run()`` calls; checkpoint state advances the same way.
        Requires pure device-side feeds (no PS embeddings / dataloader
        placeholders — those interact with the host every step).
        Sharded executors work: the fori_loop program carries the same
        param/opt-state/feed shardings as the single-step program, so
        GSPMD re-inserts the identical collectives inside the loop
        body."""
        if n < 1:
            raise ValueError(f"run_steps needs n >= 1, got {n}")
        if not self._tr.enabled:
            return self._run_steps_impl(feed_dict, n,
                                        convert_to_numpy_ret_vals)
        return self._rooted(self._run_steps_impl, feed_dict, n,
                            convert_to_numpy_ret_vals)

    def _run_steps_impl(self, feed_dict, n, convert_to_numpy_ret_vals):
        if self._jitted is None:
            with self._tr.span("compile"):
                self._build()
        if self.ps_rows:
            raise ValueError("run_steps: PS-embedding subgraphs interact "
                             "with the host store every step; use run()")
        if any(hasattr(p, "auto_feed") for p in self.placeholders):
            raise ValueError("run_steps: dataloader placeholders pull a "
                             "new batch per step; use run()")
        ex = self.executor
        feeds, _ = self._feeds(feed_dict)
        if self._multi_jitted is None:
            step_fn = self._step_fn
            donate = self._donate_argnums()
            # guard state at build time matches _build's: attach/detach
            # invalidate both compiled programs together
            guarded = ex.config.get("step_guard") is not None
            nlayers = len(self._numerics_layers or ())
            nsample = self._numerics_sample if nlayers else 1
            # the stats block rides before the two guard scalars
            stats_idx = -3 if guarded else -1

            def multi_fn(params, opt_state, feeds, base_key, step,
                         n_steps):
                # per-inner-step guard-trip accounting: the sentinel of
                # every inner step accumulates into a carried counter,
                # so trips are EXACT across the fori_loop instead of
                # detected only at the call boundary (ROADMAP item).
                # vals[-2] is the step's fused gfin sentinel.  The
                # numerics carry does the same per LAYER: an int32
                # [n_layers] count of inner steps whose stats row went
                # non-finite.  On the sampled cadence the latest
                # SAMPLED row is carried too, so the window's newest
                # real stats come back whichever inner step they
                # belong to (zeros filler rows are never surfaced).
                def nf_of(vals, nf):
                    row_ok = jnp.isfinite(
                        jnp.sum(vals[stats_idx], axis=1))
                    return nf + jnp.where(row_ok, 0, 1).astype(jnp.int32)

                def advance(carry):
                    params, opt_state, step, trips, nf, nrow = carry
                    prev = step
                    vals, params, opt_state, step = step_fn(
                        params, opt_state, feeds, base_key, step)
                    if guarded:
                        trips = trips + jnp.where(vals[-2], 0, 1).astype(
                            jnp.int32)
                    if nlayers:
                        nf = nf_of(vals, nf)
                        if nsample > 1:
                            nrow = jnp.where(
                                (prev % jnp.uint32(nsample)) == 0,
                                vals[stats_idx], nrow)
                    return vals, (params, opt_state, step, trips, nf,
                                  nrow)

                carry = (params, opt_state, step, jnp.int32(0),
                         jnp.zeros((nlayers,), jnp.int32),
                         jnp.zeros((nlayers, 3), jnp.float32))
                carry = jax.lax.fori_loop(
                    0, n_steps - 1,
                    lambda _, c: advance(c)[1], carry)
                # last step outside the loop so its values are returned
                vals, carry = advance(carry)
                params, opt_state, step, trips, nf, nrow = carry
                if nlayers and nsample > 1:
                    vals = list(vals)
                    vals[stats_idx] = nrow
                return vals, params, opt_state, step, trips, nf

            in_sh = ex._input_shardings(self)
            if in_sh is not None:
                # mirror _build: pin the carried params/opt-state to
                # their INPUT shardings so iteration i+1 of the loop —
                # and the next run_steps call — sees the layout its
                # executable expects; n_steps rides replicated
                from ..parallel.mesh import replicated
                rep = replicated(ex.mesh)
                param_sh, opt_sh = in_sh[0], in_sh[1]
                self._multi_jitted = jax.jit(
                    multi_fn, donate_argnums=donate,
                    in_shardings=in_sh + (rep,),
                    out_shardings=(rep, param_sh, opt_sh, rep, rep, rep))
            else:
                self._multi_jitted = jax.jit(multi_fn,
                                             donate_argnums=donate)
        if ex._step_arr is None:
            ex._step_arr = ex._step_counter()
        ex._global_step += n
        with self._tr.span("dispatch"):
            (vals, ex.params, ex.opt_state, ex._step_arr,
             trips_arr, nf_arr) = self._multi_jitted(
                ex.params, ex.opt_state, feeds, ex._base_key,
                ex._step_arr, jnp.int32(n))
        self._m_steps.inc(n)
        self._m_multi.inc()
        guard = ex.config.get("step_guard")
        guard_out = None
        if guard is not None:
            guard_out, vals = vals[-2:], vals[:-2]
        if self._numerics_layers is not None:
            # the returned stats cover the FINAL inner step (latest
            # SAMPLED inner step on the sampled cadence); the carried
            # [n_layers] counter attributes every inner step's
            # non-finite rows exactly (mirroring inner_trips).  A
            # window too short to contain a sampled step delivers
            # nothing — the filler row carries no information.
            nstats_out, vals = vals[-1], vals[:-1]
            ns = self._numerics_sample
            s0 = ex._global_step - n
            if ns == 1 or ((s0 + n - 1) // ns) * ns >= s0:
                with self._tr.span("numerics"):
                    ex.config["numerics"].on_step(
                        ex, self._numerics_layers, ex._global_step,
                        nstats_out, n=n, inner_nf=nf_arr)
        if guard_out is not None:
            # the returned sentinel covers the FINAL inner step; the
            # carried counter reports every inner step's trip exactly
            # (the 'skip' policy's in-graph select still protects every
            # inner step; rollback/abort act at the call boundary)
            with self._tr.span("guard_check"):
                guard.on_step(ex, guard_out[0], guard_out[1], n=n,
                              inner_trips=trips_arr)
        self._runs += n
        if self._monitor_vars:
            self.check_monitors()
        return self._fetch(vals) if convert_to_numpy_ret_vals else vals

    def check_monitors(self):
        """Warn on any tripped monitor counter (MLM overflow etc.)."""
        import warnings
        for v in self._monitor_vars:
            msg = v.monitor(float(np.asarray(self.executor.params[v.name])))
            if msg:
                warnings.warn(msg)

    def profile(self, feed_dict=None, repeats=10):
        """Wall-clock a compiled step (reference SubExecutor.profile)."""
        self.run(feed_dict)  # compile
        start = time.perf_counter()
        for _ in range(repeats):
            out = self.run(feed_dict)
        jax.block_until_ready([o for o in out if o is not None])
        return (time.perf_counter() - start) / repeats

    def _abstract_args(self, feed_dict=None):
        """The jitted step's argument tree as ShapeDtypeStructs.  Feed
        shapes come from ``feed_dict`` values when given, else from the
        placeholders' declared shapes."""
        ex = self.executor

        def abstract(a):
            return jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a))

        fed = {}
        if feed_dict:
            for node, value in feed_dict.items():
                name = node.name if isinstance(node, Op) else node
                fed[name] = value
        feeds = {}
        for p in self.placeholders:
            if p.name in fed:
                feeds[p.name] = jax.ShapeDtypeStruct(
                    jnp.shape(fed[p.name]), p.dtype)
            else:
                assert p.shape is not None, \
                    f"cost_analysis needs a feed or declared shape for " \
                    f"{p.name}"
                feeds[p.name] = jax.ShapeDtypeStruct(tuple(p.shape),
                                                     p.dtype)
        return (jax.tree_util.tree_map(abstract, ex.params),
                jax.tree_util.tree_map(abstract, ex.opt_state),
                feeds,
                jax.ShapeDtypeStruct((), ex._base_key.dtype),
                jax.ShapeDtypeStruct((), jnp.uint32))

    def lower_compiled(self, feed_dict=None):
        """The compiled (AOT) step program for analysis.  Pure: no step
        executes, no state mutates; XLA reuses its compilation cache, so
        after the first ``run()`` this costs a lowering only."""
        if self._jitted is None:
            self._build()
        return self._jitted.lower(*self._abstract_args(feed_dict)).compile()

    def cost_analysis(self, feed_dict=None):
        """XLA's static cost model for the compiled step (flops, HBM
        bytes accessed, ...) — the single-program analogue of the
        reference's per-op timer_subexecutor breakdown: XLA has already
        fused across op boundaries, so costs are whole-program.

        Pure analysis: no step executes, no state mutates.  Returns the
        version-normalized dict (see ``platform.compiled_cost_analysis``).
        """
        from ..platform import compiled_cost_analysis
        return compiled_cost_analysis(self.lower_compiled(feed_dict))

    def memory_analysis(self, feed_dict=None):
        """XLA's memory ledger for the compiled step (argument/output/
        temp bytes), version-normalized to a plain dict — the workspace
        side of the HBM accounting in ``telemetry.profiling``."""
        from ..platform import compiled_memory_analysis
        return compiled_memory_analysis(self.lower_compiled(feed_dict))


def _tree_nbytes(tree):
    """Total bytes of every array leaf in a pytree (0 for scalars and
    non-array leaves)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        total += int(getattr(leaf, "nbytes", 0) or 0)
    return total


class Executor:
    """Multi-subgraph session (reference executor.py:430).

    ``eval_node_dict`` may be a list (single anonymous subgraph) or a dict
    {name: eval_node_list}.  ``dist_strategy`` (parallel/strategies) annotates
    the graph with shardings before compilation; ``mesh`` selects the device
    mesh.  ``seed`` drives variable init and per-step RNG (dropout replay on
    checkpoint resume is preserved by saving the step counter, like the
    reference's seed+seqnum scheme in random.py).
    """

    def __init__(self, eval_node_dict, ctx=None, seed=0, mesh=None,
                 dist_strategy=None, comm_mode=None, compute_dtype=None,
                 **kwargs):
        if isinstance(eval_node_dict, (list, tuple)):
            eval_node_dict = {"default": list(eval_node_dict)}
        # set-up's other large part beside the first steps: the graph
        # walk, parameter initialisation and upload, under one accounted
        # root that also takes the XLA phases of the initialisers
        with _telemetry.get_tracer().span(
                "executor_init", key="+".join(eval_node_dict),
                account=True):
            self._init(eval_node_dict, ctx, seed, mesh, dist_strategy,
                       comm_mode, compute_dtype, kwargs)

    def _init(self, eval_node_dict, ctx, seed, mesh, dist_strategy,
              comm_mode, compute_dtype, kwargs):
        self.eval_node_dict = {k: list(v) for k, v in eval_node_dict.items()}
        self.mesh = mesh
        self.comm_mode = comm_mode
        self.compute_dtype = (jnp.dtype(compute_dtype)
                              if compute_dtype is not None else None)
        self.config = kwargs

        all_nodes = [n for lst in self.eval_node_dict.values() for n in lst]
        # reference comm_mode semantics (executor.py:278-306):
        #   'AllReduce' — dense grads allreduced across data-parallel
        #     replicas; here that's the DataParallel strategy (GSPMD emits
        #     the psum over the dp axis).
        #   'PS'/'Hybrid' — embedding tables live behind the parameter
        #     store (ps.PSEmbedding feeds/pushes rows); dense params stay
        #     on-device.  Selecting the mode without any PS-backed table
        #     in the graph is almost certainly a mistake — flag it.
        if comm_mode is not None:
            mode = str(comm_mode).lower()
            if mode == "allreduce":
                if dist_strategy is None and mesh is None:
                    from ..parallel.strategies import DataParallel
                    dist_strategy = DataParallel(ndev=len(jax.devices()))
            elif mode in ("ps", "hybrid"):
                has_ps = any(hasattr(n, "ps_embedding")
                             for n in find_topo_sort(all_nodes))
                if not has_ps:
                    import warnings
                    warnings.warn(
                        f"comm_mode={comm_mode!r} but no PSEmbedding-backed "
                        "table reaches this executor; dense parameters "
                        "always train on-device (use ps.PSEmbedding for "
                        "host-store tables)")
                if mode == "hybrid" and dist_strategy is None \
                        and mesh is None and len(jax.devices()) > 1:
                    from ..parallel.strategies import DataParallel
                    dist_strategy = DataParallel(ndev=len(jax.devices()))
            else:
                raise ValueError(f"unknown comm_mode {comm_mode!r}")
        if dist_strategy is not None:
            dist_strategy.annotate(all_nodes)
            if mesh is None and getattr(dist_strategy, "mesh", None) is not None:
                self.mesh = dist_strategy.mesh
        self.all_topo = find_topo_sort(all_nodes)
        self.variables = [n for n in self.all_topo if isinstance(n, VariableOp)]
        by_name = {}
        for v in self.variables:
            if by_name.setdefault(v.name, v) is not v:
                raise ValueError(
                    f"two distinct variables named {v.name!r} reach this "
                    "executor; give the models distinct `name=`s or build "
                    "them under separate `ht.name_scope()`s")

        # rng_impl="rbg" maps dropout/noise ops onto the TPU's hardware RNG
        # (threefry, the default, burns real FLOPs generating bits —
        # measurable on dropout-heavy training; rbg is the TPU-native
        # choice when bit-exact cross-platform replay isn't required)
        self._base_key = jax.random.key(seed,
                                        impl=kwargs.get("rng_impl", None))
        self._global_step = 0
        self._step_arr = None  # device-resident step counter (lazy)
        self.params = {}
        init_key = jax.random.fold_in(self._base_key, 0x5EED)
        for v in self.variables:
            # fold in the NAME, not the global op id: op ids count every
            # node any earlier code in the process built, so two
            # same-seed executors would init differently depending on
            # what ran before them (ADVICE r5 — the torch-parity gate
            # was suite-order-dependent).  Names are unique per executor
            # (checked above) and stable across processes.
            salt = np.uint32(zlib.crc32(v.name.encode("utf-8")))
            self.params[v.name] = self._place(
                v, v.initializer(jax.random.fold_in(init_key, salt),
                                 v.shape, jnp.dtype(v.dtype)))

        self.opt_state = {}
        self._opt_ops = {}  # name -> op, in graph (construction) order
        for n in self.all_topo:
            if n.is_stateful and hasattr(n, "init_state"):
                self.opt_state[n.name] = n.init_state(self.params)
                self._opt_ops[n.name] = n

        # HBM accounting: register the two big live pools this executor
        # owns with the process-wide ledger (telemetry.profiling).  The
        # ledger always tracks — the hetu_hbm_bytes{pool=} gauge only
        # moves once telemetry is enabled — and close() releases both.
        led = _telemetry.get_hbm_ledger()
        tag = f"executor:{id(self):x}"
        self._hbm_handles = [
            led.alloc("params", _tree_nbytes(self.params),
                      owner=f"{tag}:params"),
            led.alloc("opt_state", _tree_nbytes(self.opt_state),
                      owner=f"{tag}:opt_state")]

        if "pipeline" in self.config:
            # graph-driven pipeline over inhomogeneous stages (raw_ctx /
            # `with ht.stage(i)` annotations), reference context.py:1430
            from ..parallel.graph_pipeline import PipelineSubExecutor
            self.subexecutor = {
                name: PipelineSubExecutor(name, nodes, self)
                for name, nodes in self.eval_node_dict.items()}
        else:
            self.subexecutor = {name: SubExecutor(name, nodes, self)
                                for name, nodes in self.eval_node_dict.items()}
        # resilience.StepGuard passed as Executor(..., step_guard=guard):
        # bind it so policy actions (rollback/abort) can reach this state
        if self.config.get("step_guard") is not None:
            self.config["step_guard"]._bind(self)
        # telemetry.NumericsMonitor passed as Executor(..., numerics=mon):
        # bind so escalation can find the guard through this executor
        if self.config.get("numerics") is not None:
            self.config["numerics"]._executor = self

    # -- sharding hooks (filled in by parallel layer) ----------------------
    def _place(self, var, value):
        if self.mesh is not None and var.dist_state is not None:
            from ..parallel.mesh import to_named_sharding
            placed = jax.device_put(value, to_named_sharding(
                self.mesh, var.dist_state))
            # ``value`` lies whole on one device until the copy is done, and
            # the initial values of the next variables are already being
            # made there: a model no device holds whole would pile up on it
            # (2.1 G parameters: 8.5 GB beside the shards; PERF.md, PR 72)
            return jax.block_until_ready(placed)
        return value

    def _commit_state(self, shardings):
        """Put params, optimizer state and RNG key on the mesh as the
        compiled step takes and returns them.  State still on the one
        device it was initialised on has another abstract type than the
        mesh-placed state the first step returns, and jit would trace and
        compile the whole step a second time at step two."""
        param_sh, opt_sh, _, rep, _ = shardings
        for name, sh in param_sh.items():
            self.params[name] = jax.device_put(self.params[name], sh)
        self.opt_state = jax.device_put(self.opt_state, opt_sh)
        self._base_key = jax.device_put(self._base_key, rep)

    def _step_counter(self):
        """The device-resident step counter, placed like the one the
        step returns (see :meth:`_commit_state`)."""
        step = jnp.uint32(self._global_step)
        if self.mesh is None:
            return step
        from ..parallel.mesh import replicated
        return jax.device_put(step, replicated(self.mesh))

    def _input_shardings(self, subexec):
        if self.mesh is None:
            return None
        from ..parallel.mesh import to_named_sharding, replicated
        param_sh = {}
        for v in subexec.variables:
            if v.dist_state is not None:
                param_sh[v.name] = to_named_sharding(self.mesh, v.dist_state)
            else:
                param_sh[v.name] = replicated(self.mesh)
        feed_sh = {}
        for p in subexec.placeholders:
            if p.dist_state is not None:
                feed_sh[p.name] = to_named_sharding(self.mesh, p.dist_state)
            else:
                feed_sh[p.name] = replicated(self.mesh)
        opt_sh = jax.tree_util.tree_map(
            lambda _: replicated(self.mesh), self.opt_state)
        # parameter-sharded optimizer slots follow their parameter
        for opname, state in self.opt_state.items():
            if opname in opt_sh and "slots" in state:
                for vname in state["slots"]:
                    if vname in param_sh:
                        opt_sh[opname]["slots"][vname] = jax.tree_util.tree_map(
                            lambda _: param_sh[vname], state["slots"][vname])
        return (param_sh, opt_sh, feed_sh, replicated(self.mesh),
                replicated(self.mesh))

    # -- reference-compatible API -----------------------------------------
    def run(self, name_or_feed=None, feed_dict=None,
            convert_to_numpy_ret_vals=False, **kwargs):
        if isinstance(name_or_feed, str):
            name = name_or_feed
        else:
            name = next(iter(self.subexecutor))
            if feed_dict is None:
                feed_dict = name_or_feed
        return self.subexecutor[name].run(
            feed_dict=feed_dict,
            convert_to_numpy_ret_vals=convert_to_numpy_ret_vals)

    def run_steps(self, name, feed_dict, n,
                  convert_to_numpy_ret_vals=False):
        """Run ``n`` steps of subgraph ``name`` on the same feeds in ONE
        device dispatch (see SubExecutor.run_steps)."""
        return self.subexecutor[name].run_steps(
            feed_dict, n,
            convert_to_numpy_ret_vals=convert_to_numpy_ret_vals)

    def ps_synchronize(self):
        """Drain in-flight PS embedding traffic across all subgraphs
        (reference worker barriers before SaveParam, executor.py:589)."""
        for sub in self.subexecutor.values():
            if hasattr(sub, "ps_synchronize"):
                sub.ps_synchronize()

    def close(self):
        """Release this executor's HBM-ledger entries (params/opt_state
        pools).  Idempotent; the arrays themselves stay valid and are
        reclaimed by ordinary GC — this only ends the accounting."""
        for h in getattr(self, "_hbm_handles", ()):
            h.free()
        self._hbm_handles = []

    def profile(self, name=None, feed_dict=None, repeats=10,
                trace_dir=None):
        """Wall-clock ``repeats`` compiled steps of subgraph ``name``
        (reference Executor.profile, executor.py:501).

        With ``trace_dir``, the timed steps run under
        ``jax.profiler.trace`` and per-op aggregates (the
        timer_subexecutor.logOut role) are written to
        ``<trace_dir>/op_aggregates.json`` — see hetu_tpu/timeline.py.
        Returns ``(avg_seconds_per_step, aggregates_or_None)`` —
        always a pair, so callers passing trace_dir conditionally
        don't have to switch on the return shape."""
        if name is None:
            name = next(iter(self.subexecutor))
        sub = self.subexecutor[name]
        if trace_dir is None:
            return sub.profile(feed_dict, repeats=repeats), None
        # compile + warm OUTSIDE the capture — and BLOCK, so no async
        # warmup work leaks in: the aggregates cover exactly `repeats`
        # steps (matching meta)
        out = sub.run(feed_dict)
        jax.block_until_ready([o for o in out if o is not None])
        with jax.profiler.trace(trace_dir):
            start = time.perf_counter()
            for _ in range(repeats):
                out = sub.run(feed_dict)
            jax.block_until_ready([o for o in out if o is not None])
            dt = (time.perf_counter() - start) / repeats
        from ..timeline import write_aggregates
        aggs = write_aggregates(trace_dir, extra={
            "subgraph": name, "repeats": repeats,
            "avg_step_seconds": dt})
        return dt, aggs

    def check_monitors(self):
        """Final flush of monitor counters across all subgraphs (also
        called from state_dict so a run that checkpoints before the next
        poll interval still surfaces tripped counters)."""
        for sub in self.subexecutor.values():
            if hasattr(sub, "check_monitors"):
                sub.check_monitors()

    # -- checkpoint (reference executor.py:558-670) ------------------------
    def state_dict(self):
        self.check_monitors()
        host = jax.tree_util.tree_map(np.asarray, self.params)
        opt = jax.tree_util.tree_map(np.asarray, self.opt_state)
        # kept outside opt_state so the jitted step never sees string
        # leaves; load_state_dict uses it to pair optimizer instances by
        # construction order + class instead of by sorted-name luck
        meta = {name: {"class": type(op.optimizer).__name__, "order": i}
                for i, (name, op) in enumerate(self._opt_ops.items())
                if hasattr(op, "optimizer")}
        return {"params": host, "opt_state": opt, "opt_meta": meta,
                # machine-checkable layout tag: 4-D conv kernels are
                # HWIO (TPU-native).  Without it, an OIHW-era checkpoint
                # whose kernel dims are all equal (e.g. a 3x3 conv with
                # 3->3 channels) would load silently transposed — the
                # shape guard in load_state_dict can't see those.
                "format": {"conv_layout": "HWIO", "version": 1},
                "global_step": self._global_step,
                "base_key": np.asarray(jax.random.key_data(self._base_key))}

    def save(self, path):
        # atomic: tmp in the same directory + os.replace, so a kill
        # mid-save (preemption!) never destroys the previous checkpoint
        from .checkpoint import atomic_pickle
        atomic_pickle(self.state_dict(), path)

    def load(self, path):
        # read_checkpoint turns garbage/truncated/stale files into a
        # CheckpointError naming the path, not an opaque unpickle crash
        from .checkpoint import read_checkpoint
        self.load_state_dict(read_checkpoint(path))

    def load_state_dict(self, state):
        from .checkpoint import validate_state
        validate_state(state, source="state_dict payload")
        fmt = state.get("format")
        layout = (fmt or {}).get("conv_layout")
        if layout not in (None, "HWIO"):
            raise ValueError(
                f"checkpoint declares conv_layout={layout!r}; this "
                "executor expects HWIO kernels — convert with "
                "Conv2d.load_oihw (see MIGRATION.md)")
        if fmt is None and any(
                np.ndim(v) == 4 for v in state["params"].values()):
            import warnings
            warnings.warn(
                "checkpoint predates the conv-layout tag: 4-D kernels "
                "are assumed HWIO; an OIHW-era checkpoint whose kernel "
                "dims are all equal cannot be shape-detected — if this "
                "is one, convert with Conv2d.load_oihw (MIGRATION.md)",
                stacklevel=2)
        var_by_name = {v.name: v for v in self.variables}
        extra = sorted(set(state["params"]) - set(var_by_name))
        absent = sorted(set(var_by_name) - set(state["params"]))
        if extra or absent:
            # loading only the intersection is legitimate (fine-tuning a
            # new head) but must never be SILENT: a "restored" run that
            # actually re-initialized half its params diverges quietly.
            # Classic cause: rebuilding the same model outside
            # ht.name_scope(), which suffixes every name with _1.
            import warnings
            warnings.warn(
                f"partial restore: {len(absent)} graph param(s) not in "
                f"the checkpoint (keep their init: {absent[:4]}...), "
                f"{len(extra)} checkpoint param(s) unused "
                f"({extra[:4]}...) — if a full restore was intended, "
                "check that the model was rebuilt under the same "
                "ht.name_scope()", stacklevel=2)
        for name, value in state["params"].items():
            if name in var_by_name:
                v = var_by_name[name]
                value = jnp.asarray(value)
                if v.shape is not None and tuple(value.shape) != tuple(
                        v.shape):
                    hint = ""
                    if value.ndim == 4 and tuple(value.shape) == (
                            v.shape[3], v.shape[2], v.shape[0], v.shape[1]):
                        hint = (" — this looks like an OIHW conv kernel; "
                                "layers.Conv2d stores HWIO (TPU-native); "
                                "convert with Conv2d.load_oihw")
                    raise ValueError(
                        f"checkpoint param {name!r} has shape "
                        f"{tuple(value.shape)} but the graph expects "
                        f"{tuple(v.shape)}{hint}")
                self.params[name] = self._place(v, value)
        saved_opt = state["opt_state"]
        if (set(saved_opt) != set(self.opt_state)
                and len(saved_opt) == len(self.opt_state)):
            # optimizer-op names carry a process-wide counter (a second
            # optimizer instance in the same process gets `optimizer_2`);
            # remap by construction order.  Slot variable-name sets alone
            # can't disambiguate two optimizers over the same variables
            # (same vars under different hyperparams), so also pair by the
            # checkpoint's recorded construction order + class when
            # available, and refuse a pairing order can't resolve.
            meta = state.get("opt_meta")
            if meta is not None and set(meta) == set(saved_opt):
                # construction order on BOTH sides
                sv_order = sorted(saved_opt, key=lambda n: meta[n]["order"])
                cur_order = list(self._opt_ops)
            else:
                # legacy checkpoint: pair sorted-vs-sorted (the old
                # behavior — consistent on both sides, unlike zipping
                # construction order against sorted names, which
                # mispairs once 'optimizer_10' sorts before
                # 'optimizer_2')
                sv_order = sorted(saved_opt)
                cur_order = sorted(self.opt_state)
                slot_sets = [frozenset(s.get("slots", {}))
                             for s in self.opt_state.values()]
                if len(set(slot_sets)) != len(slot_sets):
                    raise ValueError(
                        "checkpoint has no optimizer construction-order "
                        "metadata and this graph has multiple optimizers "
                        "over identical variable sets — the pairing is "
                        "ambiguous; re-save the checkpoint with this "
                        "version or load opt state manually")
            remap = {}
            for cur_name, sv_name in zip(cur_order, sv_order):
                cur, sv = self.opt_state[cur_name], saved_opt[sv_name]
                if set(cur.get("slots", {})) != set(sv.get("slots", {})):
                    raise ValueError(
                        f"checkpoint optimizer state {sv_name!r} does not "
                        f"match this graph's {cur_name!r} (different "
                        "variable sets)")
                if meta is not None and sv_name in meta:
                    cur_op = self._opt_ops[cur_name]
                    cur_cls = type(getattr(cur_op, "optimizer",
                                           cur_op)).__name__
                    if meta[sv_name]["class"] != cur_cls:
                        raise ValueError(
                            f"checkpoint optimizer {sv_name!r} is a "
                            f"{meta[sv_name]['class']} but this graph's "
                            f"{cur_name!r} is a {cur_cls}")
                remap[cur_name] = sv
            saved_opt = remap
        self.opt_state = jax.tree_util.tree_map(jnp.asarray, saved_opt)
        self._global_step = state["global_step"]
        self._step_arr = None  # re-materializes from _global_step
        self._base_key = jax.random.wrap_key_data(
            jnp.asarray(state["base_key"]),
            impl=self.config.get("rng_impl", None))

    def get_params(self):
        return dict(self.params)
