"""Sparse vs dense MoE dispatch: memory ceiling + step time sweep.

VERDICT #9 done-criterion: show the [T, E, C] one-hot wall moved.  Runs a
capacity/expert-count sweep compiling BOTH dispatch forms and reports
XLA's own accounting (cost_analysis bytes accessed + memory_analysis temp
bytes) and measured step time on the attached backend.

Usage:  python benchmarks/moe_dispatch_bench.py [--experts 8 64 256]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..")))

import numpy as np
import jax
import jax.numpy as jnp

from hetu_tpu.ops.moe import (top_k_gating, top_k_gating_choices,
                              sparse_dispatch, sparse_combine)


def dense_step(logits, tokens, w):
    dispatch, combine, aux = top_k_gating(logits, 2, CAP)
    ein = jnp.einsum("tec,th->ech", dispatch, tokens)
    out = jnp.einsum("ech,ehf->ecf", ein, w)
    return jnp.sum(jnp.einsum("ecf,tec->tf", out, combine)) + aux


def sparse_step(logits, tokens, w):
    choices, aux = top_k_gating_choices(logits, 2, CAP)
    ein = sparse_dispatch(tokens, choices, E, CAP)
    out = jnp.einsum("ech,ehf->ecf", ein, w)
    return jnp.sum(sparse_combine(out, choices)) + aux


def measure(fn, args, reps=5):
    g = jax.jit(jax.grad(fn, argnums=(0, 1, 2)))
    lowered = g.lower(*args)
    compiled = lowered.compile()
    stats = {}
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else ca
        stats["bytes_accessed"] = ca.get("bytes accessed")
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        stats["temp_bytes"] = getattr(ma, "temp_size_in_bytes", None)
    except Exception:
        pass
    out = compiled(*args)
    np.asarray(jax.tree_util.tree_leaves(out)[0])   # wait for warm-up
    t0 = time.perf_counter()
    for _ in range(reps):
        out = compiled(*args)
    np.asarray(jax.tree_util.tree_leaves(out)[0])
    stats["ms"] = (time.perf_counter() - t0) / reps * 1e3
    return stats


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--hidden", type=int, default=512)
    ap.add_argument("--ffn", type=int, default=1024)
    ap.add_argument("--experts", type=int, nargs="+",
                    default=[8, 32, 128])
    ap.add_argument("--capacity-factor", type=float, default=2.0)
    ns = ap.parse_args()

    rng = np.random.default_rng(0)
    T, H = ns.tokens, ns.hidden
    tokens = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    for E in ns.experts:
        CAP = max(int(np.ceil(ns.capacity_factor * T * 2 / E)), 1)
        logits = jnp.asarray(rng.standard_normal((T, E)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((E, H, ns.ffn)) * 0.02,
                        jnp.float32)
        row = {"experts": E, "capacity": CAP,
               "tec_bytes": T * E * CAP * 4}
        for name, fn in (("dense", dense_step), ("sparse", sparse_step)):
            try:
                row[name] = measure(fn, (logits, tokens, w))
            except Exception as e:  # noqa: BLE001 — sweep keeps going
                row[name] = {"error": f"{type(e).__name__}: {e}"[:200]}
        print(json.dumps(row))
