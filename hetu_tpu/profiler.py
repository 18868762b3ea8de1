"""Profiling + cost simulation (reference: python/hetu/profiler.py —
`HetuProfiler` :55 per-op replay timing with synthetic inputs and zipf key
sampling for embedding ops; `NCCLProfiler` :390 collective micro-benchmarks;
`HetuSimulator` :609 cached per-op times feeding the auto-parallel
searchers).

TPU redesign: per-op replay compiles each node's compute as its own jitted
function on synthetic inputs (XLA owns streams, so CUDA-event timing becomes
wall-clock around block_until_ready); whole-step timing wraps the compiled
step.  The simulator combines measured per-op times (cached on disk keyed by
op type + shapes, like /tmp/hetu_cached_exetime.bin) with an analytic
roofline + collective model so searchers can score sharding choices without
running them.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from .graph.node import Op, PlaceholderOp, VariableOp, find_topo_sort
from .graph.trace import TraceContext


def _sync(out):
    """End a timing window by copying a result to the host: the copy
    cannot start before the program that writes it has ended, so this
    waits exactly as ``block_until_ready`` would."""
    np.asarray(jax.tree_util.tree_leaves(out)[0])


# ---------------------------------------------------------------------------
# shape inference over the graph


def shape_map(eval_nodes, feed_shapes=None):
    """{node: ShapeDtypeStruct} for every node, via per-op jax.eval_shape.

    ``feed_shapes``: optional {placeholder_name: shape} overriding declared
    shapes (the reference re-infers on feed-shape change, executor.py:938).
    """
    feed_shapes = feed_shapes or {}
    ctx = TraceContext(key=jax.random.key(0), training=False)
    shapes = {}
    for node in find_topo_sort(eval_nodes):
        if isinstance(node, PlaceholderOp):
            shape = feed_shapes.get(node.name, node.shape)
            assert shape is not None, f"{node.name} has no shape"
            shapes[node] = jax.ShapeDtypeStruct(tuple(shape), node.dtype)
        elif isinstance(node, VariableOp):
            shapes[node] = jax.ShapeDtypeStruct(tuple(node.shape),
                                                node.dtype)
        elif hasattr(node, "_compute_with_env"):
            shapes[node] = None  # stateful/bundle nodes: skip
        else:
            ins = [shapes[i] for i in node.inputs]
            if any(s is None for s in ins):
                shapes[node] = None
                continue
            try:
                shapes[node] = jax.eval_shape(
                    lambda *xs: node._compute(list(xs), ctx), *ins)
            except Exception:
                shapes[node] = None
    return shapes


# ---------------------------------------------------------------------------
# FLOP / byte estimation (drives the analytic cost model)


def op_kind(node):
    """Semantic op name: SimpleOps carry op_kind; class name otherwise."""
    return getattr(node, "op_kind", type(node).__name__).lower()


def estimate_flops(node, shapes):
    """Rough FLOPs of one op given the shape map (0 for unknown/cheap)."""
    out = shapes.get(node)
    tname = op_kind(node)
    ins = [shapes.get(i) for i in node.inputs]
    if out is None:
        return 0.0
    n_out = float(np.prod(out.shape)) if out.shape else 1.0
    if "matmul" in tname or "linear" in tname:
        if ins and ins[0] is not None:
            k = float(ins[0].shape[-1])
            return 2.0 * n_out * k
        return 2.0 * n_out
    if "conv" in tname and ins and len(ins) > 1 and ins[1] is not None:
        w = ins[1].shape
        if "hwio" in tname:          # (Kh, Kw, I, O): per-output-element
            k = float(np.prod(w[:2])) * w[2]   # Kh*Kw*I MACs
        else:                        # OIHW: drop the O dim
            k = float(np.prod(w[1:]))
        return 2.0 * n_out * k
    if "attention" in tname and ins and ins[0] is not None:
        b, h, s, d = ins[0].shape
        return 4.0 * b * h * s * s * d
    return n_out  # elementwise-ish


def tensor_bytes(struct):
    if struct is None:
        return 0
    return int(np.prod(struct.shape)) * struct.dtype.itemsize


# ---------------------------------------------------------------------------
# per-op replay profiler


def _synth_input(struct, rng, zipf_vocab=None):
    if np.issubdtype(struct.dtype, np.integer):
        hi = zipf_vocab or 1000
        # zipf-distributed keys for embedding realism (reference
        # profiler.py:143-165 uses zipf samplers for sparse ops)
        vals = np.minimum(rng.zipf(1.5, size=struct.shape), hi) - 1
        return jnp.asarray(vals, struct.dtype)
    return jnp.asarray(rng.standard_normal(struct.shape), struct.dtype)


class HetuProfiler:
    """Per-op replay timing (reference HetuProfiler.profile_all)."""

    def __init__(self, eval_nodes, feed_shapes=None, seed=0):
        self.eval_nodes = list(eval_nodes)
        self.shapes = shape_map(self.eval_nodes, feed_shapes)
        self.rng = np.random.default_rng(seed)

    def profile_op(self, node, repeats=5, warmup=1):
        """Compile node._compute alone and wall-clock it."""
        if (isinstance(node, (PlaceholderOp, VariableOp))
                or hasattr(node, "_compute_with_env")):
            return 0.0
        ins = [self.shapes.get(i) for i in node.inputs]
        if any(s is None for s in ins) or self.shapes.get(node) is None:
            return 0.0
        ctx = TraceContext(key=jax.random.key(0), training=False)
        fn = jax.jit(lambda *xs: node._compute(list(xs), ctx))
        args = [_synth_input(s, self.rng) for s in ins]
        try:
            for _ in range(warmup):
                out = fn(*args)
            _sync(out)
            t0 = time.perf_counter()
            for _ in range(repeats):
                out = fn(*args)
            _sync(out)
            return (time.perf_counter() - t0) / repeats
        except Exception:
            return 0.0

    def profile_all(self, repeats=5):
        """{node_name: seconds} over all computable nodes."""
        out = {}
        for node in find_topo_sort(self.eval_nodes):
            dt = self.profile_op(node, repeats=repeats)
            if dt > 0:
                out[node.name] = dt
        return out


class CommProfiler:
    """Collective micro-benchmarks over the current devices (reference
    NCCLProfiler :390 — allreduce/sendrecv sweeps feeding cost models)."""

    def __init__(self, mesh=None):
        self.mesh = mesh

    def bench_collective(self, kind="psum", nbytes=1 << 20, axis=None,
                         repeats=5):
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        import jax.numpy as jnp
        mesh = self.mesh
        if mesh is None:
            return 0.0
        axis = axis or mesh.axis_names[0]
        n = mesh.shape[axis]
        elems = max(nbytes // 4, n)
        elems -= elems % n
        x = jnp.ones((elems,), jnp.float32)

        def body(v):
            if kind == "psum":
                return jax.lax.psum(v, axis)
            if kind == "all_gather":
                return jax.lax.all_gather(v, axis, tiled=True)
            if kind == "ppermute":
                return jax.lax.ppermute(
                    v, axis, [(i, (i + 1) % n) for i in range(n)])
            raise ValueError(kind)

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P(axis),
                               out_specs=P(axis) if kind == "ppermute"
                               else (P() if kind == "psum" else P())))
        out = fn(x)
        _sync(out)
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(x)
        _sync(out)
        return (time.perf_counter() - t0) / repeats


# ---------------------------------------------------------------------------
# simulator / cost model


class HetuSimulator:
    """Cost model for auto-parallel search (reference HetuSimulator :609).

    Combines: (a) measured per-op times cached on disk; (b) an analytic
    roofline (flops/peak, bytes/bandwidth) fallback; (c) a linear collective
    model time = latency + bytes/bandwidth scaled by the standard ring
    factor (k-1)/k over the participating axis size.
    """

    # conservative single-chip defaults; calibrate() overwrites from
    # measurement. Units: flops/s, bytes/s, seconds.
    peak_flops = 2e14          # bf16 MXU order of magnitude
    hbm_bw = 8e11
    ici_bw = 4.5e10            # per-link ICI, one direction
    ici_latency = 1e-6
    dcn_bw = 2.5e9
    dcn_latency = 2.5e-5

    def __init__(self, cache_path=None):
        self.cache_path = cache_path or os.path.join(
            os.path.expanduser("~"), ".hetu_tpu_exetime.json")
        self._cache = {}
        if os.path.exists(self.cache_path):
            try:
                with open(self.cache_path) as f:
                    self._cache = json.load(f)
            except Exception:
                self._cache = {}

    # -- measured-time cache ----------------------------------------------
    @staticmethod
    def _op_key(node, shapes):
        ins = [tuple(shapes[i].shape) if shapes.get(i) is not None else None
               for i in node.inputs]
        return f"{op_kind(node)}:{ins}"

    def record(self, eval_nodes, feed_shapes=None, repeats=5):
        prof = HetuProfiler(eval_nodes, feed_shapes)
        for node in find_topo_sort(eval_nodes):
            key = self._op_key(node, prof.shapes)
            if key not in self._cache:
                dt = prof.profile_op(node, repeats=repeats)
                if dt > 0:
                    self._cache[key] = dt
        self.save()
        return self._cache

    def save(self):
        try:
            with open(self.cache_path, "w") as f:
                json.dump(self._cache, f)
        except Exception:
            pass

    # -- analytic pieces ----------------------------------------------------
    def op_time(self, node, shapes, shard_factor=1.0):
        """Estimated seconds for one op with its work divided shard_factor
        ways (measured if cached, else roofline)."""
        key = self._op_key(node, shapes)
        if key in self._cache:
            return self._cache[key] / shard_factor
        flops = estimate_flops(node, shapes) / shard_factor
        bytes_moved = (sum(tensor_bytes(shapes.get(i))
                           for i in node.inputs)
                       + tensor_bytes(shapes.get(node))) / shard_factor
        return max(flops / self.peak_flops, bytes_moved / self.hbm_bw)

    def collective_time(self, nbytes, axis_size, kind="all_reduce",
                        over="ici"):
        if axis_size <= 1:
            return 0.0
        bw = self.ici_bw if over == "ici" else self.dcn_bw
        lat = self.ici_latency if over == "ici" else self.dcn_latency
        k = axis_size
        factor = {"all_reduce": 2.0 * (k - 1) / k,
                  "all_gather": (k - 1) / k,
                  "reduce_scatter": (k - 1) / k,
                  "all_to_all": (k - 1) / k,
                  "p2p": 1.0}[kind]
        return lat * (k - 1) + factor * nbytes / bw

    def graph_time(self, eval_nodes, shapes=None, shard_factors=None):
        """Sum of per-op estimates (the searchers add comm terms)."""
        shapes = shapes or shape_map(eval_nodes)
        shard_factors = shard_factors or {}
        total = 0.0
        for node in find_topo_sort(eval_nodes):
            if isinstance(node, (PlaceholderOp, VariableOp)):
                continue
            total += self.op_time(node, shapes,
                                  shard_factors.get(node, 1.0))
        return total

    def calibrate(self, size=2048, repeats=3):
        """Measure actual matmul throughput to scale the roofline."""
        x = jnp.ones((size, size), jnp.bfloat16)
        fn = jax.jit(lambda a: a @ a)
        _sync(fn(x))
        t0 = time.perf_counter()
        for _ in range(repeats):
            out = fn(x)
        _sync(out)
        dt = (time.perf_counter() - t0) / repeats
        self.peak_flops = 2.0 * size ** 3 / dt
        return self.peak_flops
