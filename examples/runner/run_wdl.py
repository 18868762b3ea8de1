"""Config-driven Wide&Deep training (reference: examples/runner/run_wdl.py).

--config local : in-graph embedding (XLA gather) — the TPU-preferred path
--config lps   : embedding behind the host-RAM parameter store with a HET
                 cache (bounded-staleness reads; reference local_ps.yml's
                 hybrid mode)
--config rps   : print the per-host commands a remote PS launch would run
                 (remote_ps.yml: workers + server processes over DCN),
                 then run the lps path locally
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import numpy as np

import hetu_tpu as ht
from hetu_tpu.models import WDL


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="local",
                    choices=["local", "lps", "rps"])
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--num-embeddings", type=int, default=100000)
    ap.add_argument("--learning-rate", type=float, default=0.01)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--cache", type=int, default=5000,
                    help="HET cache rows (PS configs)")
    ap.add_argument("--dry-run", action="store_true")
    args = ap.parse_args()

    if args.config == "rps":
        from hetu_tpu.launcher import DistConfig, launch
        cfg = DistConfig(os.path.join(os.path.dirname(__file__),
                                      "remote_ps.yml"))
        plan = launch(cfg, __file__, args=("--config", "lps"),
                      dry_run=True)
        for host, cmd in plan:
            print(f"[{host}] {cmd}")
        if args.dry_run:
            return

    rng = np.random.default_rng(0)
    B, F = args.batch_size, 26
    dense = ht.placeholder_op("dense", (B, 13))
    sparse = ht.placeholder_op("sparse", (B, F), dtype=np.int32)
    labels = ht.placeholder_op("labels", (B,))

    ps_emb = None
    if args.config in ("lps", "rps"):
        from hetu_tpu.ps import PSEmbedding
        ps_emb = PSEmbedding(args.num_embeddings, 16, optimizer="sgd",
                             lr=args.learning_rate,
                             cache_limit=args.cache or None)
    model = WDL(args.num_embeddings, embedding_dim=16, ps_embedding=ps_emb)
    loss = model.loss(dense, sparse, labels)
    ex = ht.Executor({"train": [
        loss, ht.AdamOptimizer(args.learning_rate).minimize(loss)]})

    # zipf-ish synthetic Criteo traffic (hot rows exercise the HET cache)
    zipf = rng.zipf(1.2, size=(args.steps, B, F))
    for step in range(args.steps):
        ids = np.minimum(zipf[step] - 1, args.num_embeddings - 1)
        feed = {dense: rng.standard_normal((B, 13)).astype(np.float32),
                sparse: ids.astype(np.int32),
                labels: rng.integers(0, 2, B).astype(np.float32)}
        out = ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
        if step % 25 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(out[0]):.4f}")
    if ps_emb is not None:
        ex.subexecutor["train"].ps_synchronize()
        stats = getattr(ps_emb, "cache_stats", lambda: None)()
        if stats:
            print("HET cache stats:", stats)


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
