"""BERT-base pretraining, MLM + NSP (reference: examples/nlp/bert).

Synthetic token streams by default (the reference's data prep pipelines
produce the same [B,S] int tensors).  bf16 compute + f32 masters; attention
runs through the Pallas flash kernel on TPU.
Usage: python examples/nlp/train_bert.py [--layers 12 --steps 30]
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import numpy as np
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.models import BertConfig, BertForPreTraining


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=1e-4)
    ap.add_argument("--features", default=None,
                    help=".npz from examples/nlp/create_pretraining_data"
                         ".py — real MLM/NSP features instead of "
                         "synthetic ids")
    ap.add_argument("--vocab-size", type=int, default=30522)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    B, S = args.batch_size, args.seq_len
    data = None
    if args.features:
        with np.load(args.features) as z:
            # materialize once: NpzFile re-decompresses on every access
            data = {k: z[k] for k in z.files}
        n, S = data["input_ids"].shape
        data["mlm_labels"] = data["mlm_labels"].reshape(n, S)
        assert n >= B, f"only {n} instances for batch {B}"
        assert int(data["input_ids"].max()) < args.vocab_size, (
            "features were built with a larger vocab than --vocab-size; "
            "out-of-range ids would gather garbage embeddings silently")
        print(f"loaded {n} pretraining instances (seq {S}) from "
              f"{args.features}")
    c = BertConfig(vocab_size=args.vocab_size, hidden_size=768,
                   num_hidden_layers=args.layers, seq_len=S,
                   max_position_embeddings=max(512, S))

    input_ids = ht.placeholder_op("input_ids", (B, S), dtype=np.int32)
    token_type = ht.placeholder_op("token_type_ids", (B, S),
                                   dtype=np.int32)
    attn_mask = ht.placeholder_op("attention_mask", (B, S))
    mlm_labels = ht.placeholder_op("mlm_labels", (B * S,), dtype=np.int32)
    nsp_labels = ht.placeholder_op("nsp_labels", (B,), dtype=np.int32)

    model = BertForPreTraining(c)
    loss = model.loss(input_ids, token_type, attn_mask, mlm_labels,
                      nsp_labels)
    opt = ht.AdamWOptimizer(learning_rate=args.lr, weight_decay=0.01)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                     compute_dtype=jnp.bfloat16)

    for step in range(args.steps):
        if data is not None:
            sl = rng.choice(data["input_ids"].shape[0], B, replace=False)
            feed = {input_ids: data["input_ids"][sl],
                    token_type: data["token_type_ids"][sl],
                    attn_mask: data["attention_mask"][sl],
                    mlm_labels: data["mlm_labels"][sl].reshape(-1),
                    nsp_labels: data["nsp_labels"][sl]}
        else:
            ids = rng.integers(0, c.vocab_size, (B, S))
            mlm = np.full((B * S,), -1, np.int64)
            pos = rng.random(B * S) < 0.15
            mlm[pos] = rng.integers(0, c.vocab_size, pos.sum())
            feed = {input_ids: ids,
                    token_type: rng.integers(0, 2, (B, S)),
                    attn_mask: np.ones((B, S), np.float32),
                    mlm_labels: mlm,
                    nsp_labels: rng.integers(0, 2, (B,))}
        out = ex.run("train", feed_dict=feed,
                     convert_to_numpy_ret_vals=True)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {out[0]:.4f}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
