"""Lazy sparse vs dense in-graph embedding optimizer sweep.

The reference keeps sparse optimizer kernels (src/ops/OptimizersSparse.cu)
so a step touches only the looked-up rows; the dense path reads/writes the
full [V, H] table plus every optimizer moment each step.  This sweep
compiles an Adam embedding-update step BOTH ways at growing vocab sizes.

The headline metric is MEASURED step time: dense grows linearly with V
while lazy stays flat at the touched-row working set (measured on CPU
XLA, V=10k -> 1M: dense 1.2 -> 98 ms/step, lazy ~1.5-2.0 ms/step, 50x at
Criteo-and-beyond scale).  cost_analysis bytes are reported too but
over-count the lazy path: XLA's static model charges a scatter its whole
table operand even though the donated in-place update only writes the
touched rows.

Usage:  JAX_PLATFORMS=cpu python benchmarks/sparse_opt_bench.py
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

import hetu_tpu as ht


def build_step(V, D, B, F, sparse):
    ids = ht.placeholder_op(f"ids_{V}_{int(sparse)}", (B, F),
                            dtype=np.int32)
    y = ht.placeholder_op(f"y_{V}_{int(sparse)}", (B, F, D))
    table = ht.Variable(f"table_{V}_{int(sparse)}", shape=(V, D),
                        initializer=ht.init.normal(0.0, 0.01))
    e = ht.embedding_lookup_op(table, ids)
    loss = ht.reduce_mean_op(ht.pow_op(e - y, exponent=2.0))
    opt = ht.AdamOptimizer(0.01)
    train = opt.minimize(loss, sparse_vars=[table] if sparse else ())
    return ht.Executor({"train": [loss, train]}), ids, y


def measure(V, D, B, F, sparse, steps=10):
    ex, ids, y = build_step(V, D, B, F, sparse)
    rng = np.random.default_rng(0)
    feed = {ids: rng.integers(0, V, (B, F)).astype(np.int32),
            y: rng.standard_normal((B, F, D)).astype(np.float32)}
    ex.run("train", feed_dict=feed)          # compile
    sub = ex.subexecutor["train"]
    stats = {}
    try:
        ca = sub.cost_analysis()
        stats["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    except Exception:
        pass
    t0 = time.perf_counter()
    out = None
    for _ in range(steps):
        out = ex.run("train", feed_dict=feed)
    np.asarray(out[0])
    stats["step_ms"] = (time.perf_counter() - t0) / steps * 1e3
    return stats


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, nargs="+",
                    default=[10_000, 100_000, 1_000_000])
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--fields", type=int, default=26)
    args = ap.parse_args()

    rows = []
    for V in args.vocab:
        row = {"vocab": V}
        for mode in ("dense", "sparse"):
            s = measure(V, args.dim, args.batch, args.fields,
                        sparse=mode == "sparse")
            for k, v in s.items():
                row[f"{mode}_{k}"] = round(v, 3)
        rows.append(row)
        print(json.dumps(row))
    if rows:
        big = rows[-1]
        print(f"# at V={big['vocab']}: dense {big['dense_step_ms']:.1f} "
              f"ms/step vs lazy {big['sparse_step_ms']:.1f} ms/step "
              f"({big['dense_step_ms'] / big['sparse_step_ms']:.0f}x)")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
