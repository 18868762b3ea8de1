"""Embedding-compression benchmark driver (reference:
tools/EmbeddingMemoryCompression/run_compressed.py).

Trains a CTR head over ANY of the 17 compression methods at a target
compress rate.  Usage:
    python examples/rec/run_compressed.py --method tt --compress-rate 0.1
    python examples/rec/run_compressed.py --method dpq --steps 50
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import numpy as np

import hetu_tpu as ht
from hetu_tpu import embed_compress as ec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--method", default="hash", choices=ec.METHODS)
    ap.add_argument("--compress-rate", type=float, default=0.25)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--num-embeddings", type=int, default=50000)
    ap.add_argument("--embedding-dim", type=int, default=16)
    ap.add_argument("--num-fields", type=int, default=26)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--lr", type=float, default=0.01)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    B, F, D = args.batch_size, args.num_fields, args.embedding_dim
    # zipf-ish synthetic id frequencies (adapt/mgqe/autosrh need them)
    freq = (1.0 / (1 + np.arange(args.num_embeddings))) ** 1.1
    freq = (freq / freq.sum() * 1e6).astype(np.int64)

    layer = ec.make_compressed_embedding(
        args.method, args.num_embeddings, D,
        compress_rate=args.compress_rate, batch_size=B, num_slot=F,
        frequencies=freq, rng=rng)

    ids = ht.placeholder_op("ids", (B, F), dtype=np.int32)
    labels = ht.placeholder_op("labels", (B,))
    emb = layer(ids)
    flat = ht.array_reshape_op(emb, output_shape=(B, F * D))
    w = ht.Variable("head_w", shape=(F * D, 1),
                    initializer=ht.init.xavier_normal())
    logits = ht.array_reshape_op(ht.matmul_op(flat, w), output_shape=(B,))
    loss = ht.reduce_mean_op(
        ht.binarycrossentropywithlogits_op(logits, labels))
    extra = layer.extra_loss()
    if extra is not None:
        loss = loss + 0.1 * extra

    opt = ht.AdamOptimizer(learning_rate=args.lr)
    train_nodes = [loss, opt.minimize(loss)]
    if hasattr(layer, "codebook_update"):
        train_nodes.append(layer.codebook_update)
    if isinstance(layer, ec.DeepLightEmbedding):
        train_nodes.append(layer.make_prune_op(after=train_nodes[1]))
    ex = ht.Executor({"train": train_nodes})

    # zipf sampling of ids, as the reference profiler does
    probs = freq / freq.sum()
    for step in range(args.steps):
        feed = {ids: rng.choice(args.num_embeddings, size=(B, F), p=probs),
                labels: rng.integers(0, 2, (B,)).astype(np.float32)}
        out = ex.run("train", feed_dict=feed,
                     convert_to_numpy_ret_vals=True)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"[{args.method} @ {args.compress_rate}] "
                  f"step {step:4d}  loss {out[0]:.4f}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
