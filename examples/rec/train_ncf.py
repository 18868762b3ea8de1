"""NCF-family rating-model trainer (reference examples/rec/run_compressed.py
with --model mf|gmf|mlp|neumf over examples/rec/models/).

Trains on a synthetic low-rank rating matrix (MovieLens-shaped ids:
one shared table, item ids offset by num_users) with any head and any
embedding-compression method:

    python examples/rec/train_ncf.py --head neumf
    python examples/rec/train_ncf.py --head mf --method tt --compress-rate 0.25
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import numpy as np

import hetu_tpu as ht
from hetu_tpu import embed_compress as ec
from hetu_tpu.models import NCFModel, REC_HEADS


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--head", default="neumf", choices=sorted(REC_HEADS))
    ap.add_argument("--method", default="full", choices=ec.METHODS)
    ap.add_argument("--compress-rate", type=float, default=0.5)
    ap.add_argument("--num-users", type=int, default=4000)
    ap.add_argument("--num-items", type=int, default=2000)
    ap.add_argument("--dim", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=1e-2)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    users, items, D, B = (args.num_users, args.num_items, args.dim,
                          args.batch_size)
    # synthetic rank-8 ratings in [1, 5]
    U = rng.standard_normal((users, 8)) * 0.5
    V = rng.standard_normal((items, 8)) * 0.5
    R = np.clip(3.0 + U @ V.T, 1.0, 5.0).astype(np.float32)

    embedding = None
    # zipf-ish synthetic id frequencies; the training loop samples ids
    # from the SAME distribution (run_compressed.py's p=freq/freq.sum()),
    # so frequency-tiered methods (adapt/mgqe/autosrh) see the hot ids
    # they sized their uncompressed tiers for
    freq = (1.0 / (1 + np.arange(users + items))) ** 1.1
    user_p = freq[:users] / freq[:users].sum()
    item_p = freq[users:] / freq[users:].sum()
    if args.method != "full":
        counts = (freq / freq.sum() * 1e6).astype(np.int64)
        embedding = ec.make_compressed_embedding(
            args.method, users + items, D,
            compress_rate=args.compress_rate, batch_size=B, num_slot=2,
            frequencies=counts, rng=rng)
    model = NCFModel(users, items, D, head=args.head, embedding=embedding)

    ids = ht.placeholder_op("ids", (B, 2), dtype=np.int32)
    labels = ht.placeholder_op("labels", (B,))
    mse, mae, _ = model(ids, labels)
    loss = mse
    if embedding is not None:
        extra = embedding.extra_loss()
        if extra is not None:
            loss = loss + 0.1 * extra
    opt = ht.AdamOptimizer(learning_rate=args.lr)
    train_nodes = [mse, mae, opt.minimize(loss)]
    # per-method training machinery, as run_compressed.py wires it
    if embedding is not None and hasattr(embedding, "codebook_update"):
        train_nodes.append(embedding.codebook_update)
    if isinstance(embedding, ec.DeepLightEmbedding):
        train_nodes.append(embedding.make_prune_op(after=train_nodes[2]))
    ex = ht.Executor({"train": train_nodes})

    for step in range(args.steps):
        u = rng.choice(users, size=B, p=user_p)
        i = rng.choice(items, size=B, p=item_p)
        feed = {ids: np.stack([u, users + i], 1).astype(np.int32),
                labels: R[u, i]}
        out = ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
        if step % 20 == 0 or step == args.steps - 1:
            print(f"[{args.head}/{args.method}] step {step:4d}  "
                  f"mse {out[0]:.4f}  mae {out[1]:.4f}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
