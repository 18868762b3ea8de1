"""CTR models on Criteo/Avazu-format data (reference: examples/ctr —
wdl_criteo, dfm_criteo, dcn_criteo; 13 dense + 26 sparse features).

--data points at a raw Criteo ``train.txt``/``.gz`` shard (or Avazu CSV
with --dataset avazu): the real-format ingestion pipeline
(hetu_tpu/datasets/criteo.py, the reference's load_data.py contract)
parses it, label-encodes the categorical fields into one unified table,
holds out 10%, and the run reports held-out AUC per epoch — a vendored
sample shard ships at examples/ctr/datasets/criteo_sample.txt.  Without
--data the run uses synthetic Criteo-shaped batches (shape/perf smoke).

--ps puts the embedding table behind the HET-cached parameter store
(ps/cstable.py) instead of an in-graph Variable — the path for tables
that don't fit HBM.  Usage:
    python examples/ctr/train_ctr.py --model wdl \
        --data examples/ctr/datasets/criteo_sample.txt --epochs 3
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import numpy as np

import hetu_tpu as ht
from hetu_tpu.models import WDL, DeepFM, DCN, DLRM
from hetu_tpu import metrics

MODELS = {"wdl": WDL, "deepfm": DeepFM, "dcn": DCN, "dlrm": DLRM}


def build(args, num_embeddings, num_sparse, batch):
    dense = ht.placeholder_op("dense", (batch, 13))
    sparse = ht.placeholder_op("sparse", (batch, num_sparse),
                               dtype=np.int32)
    labels = ht.placeholder_op("labels", (batch,))
    ps_emb = None
    if args.ps:
        from hetu_tpu.ps import PSEmbedding
        ps_emb = PSEmbedding(num_embeddings, args.embedding_dim,
                             optimizer="sgd", lr=args.lr,
                             cache_limit=args.cache or None)
    model = MODELS[args.model](num_embeddings,
                               embedding_dim=args.embedding_dim,
                               num_sparse=num_sparse,
                               ps_embedding=ps_emb)
    loss = model.loss(dense, sparse, labels)
    logit = model(dense, sparse)
    opt = ht.AdamOptimizer(learning_rate=args.lr)
    sparse_vars = ()
    if args.sparse_opt and ps_emb is not None:
        raise SystemExit("--sparse-opt applies to the in-graph table; it "
                         "is mutually exclusive with --ps")
    if args.sparse_opt:
        # lazy in-graph updates: Adam moments for untouched rows stay
        # frozen (reference OptimizersSparse.cu semantics)
        sparse_vars = [model.emb.table]
    ex = ht.Executor(
        {"train": [loss, opt.minimize(loss, sparse_vars=sparse_vars)],
         "predict": [logit]})
    return ex, (dense, sparse, labels)


def batches(rng, n, batch, shuffle=True):
    idx = rng.permutation(n) if shuffle else np.arange(n)
    for i in range(0, n - batch + 1, batch):
        yield idx[i:i + batch]


def eval_auc(ex, ph, dense_te, sparse_te, labels_te, batch):
    """Held-out AUC over ALL test rows (AUC is rank-based, so raw logits
    work — no sigmoid needed).  The fixed-shape predict program wants
    full batches, so the tail batch is padded with repeats and the pad
    scores dropped."""
    dense, sparse, labels = ph
    n = len(labels_te)
    scores, ys = [], []
    for i in range(0, n, batch):
        sel = np.arange(i, min(i + batch, n))
        pad = batch - len(sel)
        padded = np.concatenate([sel, np.zeros(pad, np.int64)]) \
            if pad else sel
        feed = {dense: dense_te[padded], sparse: sparse_te[padded]}
        out = ex.run("predict", feed_dict=feed,
                     convert_to_numpy_ret_vals=True)
        scores.append(out[0][:len(sel)])
        ys.append(labels_te[sel])
    return metrics.auc(np.concatenate(scores), np.concatenate(ys))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="wdl", choices=list(MODELS))
    ap.add_argument("--data", default=None,
                    help="raw Criteo train.txt/.gz (or Avazu CSV with "
                         "--dataset avazu); omit for synthetic batches")
    ap.add_argument("--dataset", default="criteo",
                    choices=["criteo", "avazu"])
    ap.add_argument("--nrows", type=int, default=None,
                    help="cap on parsed rows (full Criteo is 45.8M)")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--num-embeddings", type=int, default=100000,
                    help="table rows for the SYNTHETIC run (real data "
                         "sizes the table from the encoded features)")
    ap.add_argument("--embedding-dim", type=int, default=16)
    ap.add_argument("--steps", type=int, default=30,
                    help="synthetic-run steps")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--sparse-opt", action="store_true",
                    help="lazy (IndexedSlices) in-graph embedding updates "
                         "— only touched rows read/write per step")
    ap.add_argument("--ps", action="store_true",
                    help="host-RAM PS embedding table (server-side SGD)")
    ap.add_argument("--cache", type=int, default=0,
                    help="HET cache rows (with --ps): bounded-staleness "
                         "client cache")
    args = ap.parse_args()
    rng = np.random.default_rng(0)
    B = args.batch_size

    if args.data is None:
        # synthetic Criteo-shaped smoke run (the original example)
        ex, (dense, sparse, labels) = build(args, args.num_embeddings,
                                            26, B)
        for step in range(args.steps):
            feed = {dense: rng.standard_normal((B, 13)).astype(np.float32),
                    sparse: rng.integers(0, args.num_embeddings, (B, 26)),
                    labels: rng.integers(0, 2, (B,)).astype(np.float32)}
            out = ex.run("train", feed_dict=feed,
                         convert_to_numpy_ret_vals=True)
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:4d}  logloss {out[0]:.4f}")
        return

    from hetu_tpu.datasets import process_criteo, process_avazu
    if args.dataset == "criteo":
        ((dtr, dte), (str_, ste),
         (ltr, lte)), num_features = process_criteo(args.data,
                                                    nrows=args.nrows)
    else:
        ((str_, ste), (ltr, lte)), num_features = process_avazu(
            args.data, nrows=args.nrows)
        # Avazu has no dense features; feed a zero block (the reference
        # uses per-dataset model configs — same effect, one code path)
        dtr = np.zeros((len(ltr), 13), np.float32)
        dte = np.zeros((len(lte), 13), np.float32)
    num_sparse = str_.shape[1]
    print(f"{args.dataset}: {len(ltr)} train / {len(lte)} test rows, "
          f"{num_features} features over {num_sparse} fields")
    ex, ph = build(args, num_features, num_sparse, B)
    dense, sparse, labels = ph
    for epoch in range(args.epochs):
        losses = []
        for sel in batches(rng, len(ltr), B):
            feed = {dense: dtr[sel], sparse: str_[sel], labels: ltr[sel]}
            out = ex.run("train", feed_dict=feed,
                         convert_to_numpy_ret_vals=True)
            losses.append(float(out[0]))
        auc = eval_auc(ex, ph, dte, ste, lte, B)
        print(f"epoch {epoch}  logloss {np.mean(losses):.4f}  "
              f"held-out AUC {auc:.4f}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
