"""Calibration: measured ``LayerProfile``s from live evidence.

Two paths feed the search, both measured:

1. :func:`calibrate_hp_layers` — the HP-layer path: time each
   distinct layer spec's compiled **fwd+bwd** on the
   live backend (``value_and_grad``, so the cost model's ``bwd = 2 ×
   fwd`` convention is calibrated against what will actually run), read
   activation memory from the XLA temp-bytes slope over two batch
   sizes, and measure ICI bandwidth with the collective micro-bench.

2. :func:`calibrate_from_profiler` — the generic path for any program
   already captured + observed by the
   :class:`~hetu_tpu.telemetry.profiling.ProgramProfiler`: the observed
   window's measured step time is attributed over layers by XLA flops
   fraction (``ProgramProfiler.calibration``), and parameter bytes come
   from the live params grouped by
   :func:`~hetu_tpu.telemetry.profiling.layer_of`.

Both serialize through :func:`calibrate_and_save` as the versioned
galvatron profile artifact (atomic write, schema-validated load) so a
plan can always answer "what evidence was this searched on?".
"""

from __future__ import annotations

import warnings

import numpy as np

from ..galvatron.search import (LayerProfile, measure_ici_gbps,
                                save_profile)

#: ici_gbps used when the backend cannot measure one (single device):
#: matches the GalvatronSearch default so single-chip plans stay
#: comparable with hand-driven searches
DEFAULT_ICI_GBPS = 100.0

#: fwd+bwd is modeled as 3x the forward pass (CostModel: bwd = 2*fwd),
#: so a measured fwd+bwd time calibrates compute_ms at 1/3
FWD_BWD_FACTOR = 3.0


def calibrate_hp_layers(specs, batch=2, seq=64, reps=5, devices=None):
    """Measured :class:`LayerProfile` per HP layer spec.

    Like :func:`~hetu_tpu.galvatron.search.profile_hp_layers` but timed
    on the compiled **fwd+bwd** (``value_and_grad``) — the thing a
    train step actually runs — so the profile calibrates the cost
    model's whole compute term, not just the forward.  One timing per
    distinct spec type; same-typed layers share it (the reference's
    ``layertype_*`` entries).  Returns ``(layers, meta)``."""
    import time
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from ..galvatron.config import HybridParallelConfig
    from ..galvatron.runtime import LayerShardings
    from ..platform import compiled_memory_analysis

    dev = (devices or jax.devices())[0]
    mesh = Mesh(np.asarray([dev]), ("m0",))
    cfg = HybridParallelConfig(pp_deg=1, tp_sizes=[1], dp_types=[0],
                               world=1)
    sh = LayerShardings(mesh, cfg, 0)
    by_type = {}
    out = []
    for spec in specs:
        key = (type(spec).__name__, spec.hidden,
               getattr(spec, "ffn", None), getattr(spec, "heads", None))
        if key not in by_type:
            params = jax.device_put(spec.init(jax.random.PRNGKey(0)), dev)
            x = jax.device_put(
                jax.random.normal(jax.random.PRNGKey(1),
                                  (batch, seq, spec.hidden), spec.dtype),
                dev)
            vg = jax.jit(jax.value_and_grad(
                lambda p, xx: jnp.sum(spec.apply(p, xx, sh))))
            l, g = vg(params, x)
            np.asarray(l)                       # compile + real sync
            t0 = time.perf_counter()
            for _ in range(reps):
                l, g = vg(params, x)
            np.asarray(l)
            ms = (time.perf_counter() - t0) / reps * 1e3
            param_bytes = sum(v.size * v.dtype.itemsize
                              for v in jax.tree_util.tree_leaves(params))
            act_bytes = seq * spec.hidden * jnp.dtype(spec.dtype).itemsize
            act_mem = None
            try:
                def temp_at(b):
                    xb = jax.ShapeDtypeStruct((b, seq, spec.hidden),
                                              spec.dtype)
                    ma = compiled_memory_analysis(
                        vg.lower(params, xb).compile())
                    return float(ma.get("temp_size_in_bytes", 0) or 0)
                t1, t2 = temp_at(batch), temp_at(2 * batch)
                if t2 > t1 > 0:
                    act_mem = max(act_bytes, (t2 - t1) / batch)
            except Exception as e:
                # memory model falls back to analytic act_bytes
                warnings.warn(
                    f"calibrate: temp-bytes slope unavailable for "
                    f"{key[0]} ({type(e).__name__}: {e}); using "
                    f"analytic activation bytes")
            by_type[key] = LayerProfile(
                ms / FWD_BWD_FACTOR / batch, param_bytes, act_bytes,
                act_mem_bytes=act_mem)
        out.append(by_type[key])
    meta = {"source": "hp_layers", "platform": jax.default_backend(),
            "batch": int(batch), "seq": int(seq), "reps": int(reps),
            "timing": "fwd_bwd/3", "n_layers": len(out),
            "layer_types": sorted({type(s).__name__ for s in specs})}
    return out, meta


def calibrate_from_profiler(profiler, name, batch_size, params=None,
                            act_bytes_by_layer=None, layer_order=None):
    """Measured :class:`LayerProfile`s from an already-profiled program.

    ``profiler.calibration(name)`` attributes the observed window's
    measured step time over layers by flops fraction; an executed train
    step is fwd+bwd+update, so per-sample ``compute_ms`` divides by the
    fwd+bwd factor and ``batch_size``.  ``params`` (name -> array)
    supplies per-layer parameter bytes via the telemetry layer grouping;
    ``act_bytes_by_layer`` overrides the boundary-activation bytes per
    sample (default: the layer's attributed memory traffic per sample —
    an upper bound, conservative for the comm terms).  ``layer_order``
    fixes the emitted order (default: attribution order, heaviest
    first).  Returns ``(layers, meta)``."""
    from ..telemetry.profiling import layer_of

    rows = profiler.calibration(name)
    by_layer = {r["layer"]: r for r in rows}
    param_bytes = {}
    if params:
        for pname, v in params.items():
            lname = layer_of(pname)
            param_bytes[lname] = param_bytes.get(lname, 0) + int(
                getattr(v, "nbytes", 0) or
                np.asarray(v).size * np.asarray(v).dtype.itemsize)
    order = list(layer_order) if layer_order is not None else \
        [r["layer"] for r in rows]
    out = []
    for lname in order:
        r = by_layer.get(lname)
        if r is None:
            raise KeyError(
                f"layer {lname!r} not in {name!r}'s attribution table "
                f"({sorted(by_layer)})")
        if act_bytes_by_layer and lname in act_bytes_by_layer:
            act = float(act_bytes_by_layer[lname])
        else:
            act = float(r["bytes"]) / max(1, batch_size)
        out.append(LayerProfile(
            r["ms"] / FWD_BWD_FACTOR / max(1, batch_size),
            param_bytes.get(lname, 0.0), act))
    meta = {"source": "profiler", "program": str(name),
            "batch": int(batch_size), "timing": "observed_window/3",
            "n_layers": len(out), "layers": order}
    return out, meta


def measured_ici_gbps(mesh=None):
    """ICI bandwidth for the profile artifact: measured when the mesh
    has >= 2 devices, the search default otherwise.  Returns
    ``(ici_gbps, measured: bool)``."""
    ici = None
    try:
        ici = measure_ici_gbps(mesh=mesh)
    except Exception:
        ici = None
    if ici is None:
        return DEFAULT_ICI_GBPS, False
    return float(ici), True


def calibrate_and_save(path, specs, batch=2, seq=64, reps=5,
                       devices=None, mesh=None):
    """The whole calibration pass: measured
    HP-layer profiles + measured ICI bandwidth, written as the
    versioned profile artifact.  Returns ``(layers, ici_gbps, meta)``
    (the artifact is at ``path``)."""
    layers, meta = calibrate_hp_layers(specs, batch=batch, seq=seq,
                                       reps=reps, devices=devices)
    ici, measured = measured_ici_gbps(mesh=mesh)
    meta["ici_measured"] = bool(measured)
    save_profile(path, layers, ici_gbps=ici, meta=meta)
    return layers, ici, meta
