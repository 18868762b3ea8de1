"""HET-at-scale demonstration: the PS host-store path trains tables the
chip cannot hold, at a per-step cost independent of table size.

The HET thesis (SURVEY §3.4, VLDB'22) is NOT that the PS path matches
in-graph speed when the table fits HBM — it is that the cache makes the
PS path viable at scales where in-graph is IMPOSSIBLE.  This benchmark
makes that concrete on one v5e (16 GB HBM):

  - W&D with a V-row × 32-dim table under in-graph Adam needs
    V·32·4 bytes × 3 (params + m + v) of HBM before activations:
    at V=80M that is ~30.7 GB — infeasible on the chip.  (Stated
    arithmetically rather than by provoking an out-of-memory failure.)
  - The PS path holds table + optimizer slots in host RAM and touches
    only the batch's unique rows per step, so its throughput is FLAT in
    V — measured here across V = 337k (the wdl_ps bench shape) →
    8M → 80M (2.4×–240× past the HBM-feasible scale), with the HET
    cache (LFU, 1% of rows) absorbing zipf traffic.

Usage:  python benchmarks/ps_scale_bench.py [--steps 30] [--quick]
Prints one JSON line: steps/s per table size + cache hit rate + the
in-graph HBM requirement at the largest size.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..")))
sys.path.insert(0, os.path.abspath(os.path.dirname(__file__)))

import numpy as np

HBM_BYTES_V5E = 16 * 1024 ** 3


def measure(rows, dim, batch, fields, steps):
    from ps_harness import build_wdl_ps, time_steps, zipf_feeds

    rng = np.random.default_rng(0)
    # server-side Adam (the in-graph comparison rule) and a 1%-of-rows
    # LFU cache — the HET design point at scale
    ex, ps_emb, ph = build_wdl_ps(rows, dim, batch, fields,
                                  optimizer="adam", lr=1e-2,
                                  cache_limit=max(4096, rows // 100),
                                  name_prefix="psc")
    feeds = zipf_feeds(rng, rows, batch, fields, ph)
    best = time_steps(ex, feeds, steps)
    stats = ps_emb.stats()
    return {"rows": rows,
            "steps_per_sec": round(1.0 / best, 2),
            "cache_hit_rate": round(stats.get("hit_rate", 0.0), 4),
            "host_bytes_gib": round(rows * dim * 4 * 3 / 1024 ** 3, 2)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--fields", type=int, default=26)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--quick", action="store_true",
                    help="small tables only (CI smoke)")
    args = ap.parse_args()

    sizes = [10_000, 100_000] if args.quick \
        else [337_000, 8_000_000, 80_000_000]
    results = [measure(v, args.dim, args.batch, args.fields, args.steps)
               for v in sizes]
    v_big = sizes[-1]
    in_graph_bytes = v_big * args.dim * 4 * 3  # params + adam m + v
    flat = results[-1]["steps_per_sec"] / max(
        r["steps_per_sec"] for r in results)
    print(json.dumps({
        "metric": "wdl_ps_het_scale_sweep",
        "unit": "steps/sec",
        "per_table": results,
        # all byte figures in GiB (1024^3), matching host_bytes_gib
        "in_graph_adam_gib_at_largest":
            round(in_graph_bytes / 1024 ** 3, 2),
        "hbm_gib_v5e": round(HBM_BYTES_V5E / 1024 ** 3, 2),
        "in_graph_feasible_at_largest":
            in_graph_bytes < HBM_BYTES_V5E,
        "throughput_vs_best_at_largest": round(flat, 3)}))


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
