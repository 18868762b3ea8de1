"""Seq2seq Transformer trainer (reference examples/nlp/
train_hetu_transformer.py — IWSLT-style translation loop; here the
dataset is a synthetic token-reversal task so the example is
self-contained, same loss/optimizer scheme).

    python examples/nlp/train_transformer.py --steps 200
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import numpy as np

import hetu_tpu as ht
from hetu_tpu.models import Seq2SeqTransformer, TransformerConfig


def make_batch(rng, c, B):
    """Reverse-translation: target = reversed source (BOS=1, PAD=0)."""
    src = rng.integers(2, c.vocab_size, (B, c.src_len))
    lens = rng.integers(max(2, c.src_len // 2), c.src_len + 1, B)
    tgt_out = np.zeros_like(src)
    for b, L in enumerate(lens):
        src[b, L:] = c.pad_id
        tgt_out[b, :L] = src[b, :L][::-1]
    tgt_in = np.concatenate(
        [np.ones((B, 1), np.int64), tgt_out[:, :-1]], axis=1)
    tgt_in[tgt_out == c.pad_id] = c.pad_id
    return (src, tgt_in, tgt_out,
            (src != c.pad_id).astype(np.float32),
            (tgt_out != c.pad_id).astype(np.float32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--vocab", type=int, default=1000)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--blocks", type=int, default=2)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--d-ff", type=int, default=512)
    ap.add_argument("--seq-len", type=int, default=24)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--dropout", type=float, default=0.1)
    ap.add_argument("--generate", action="store_true",
                    help="greedy-decode a batch after training "
                         "(KV-cache decoder) and report token accuracy")
    args = ap.parse_args()

    c = TransformerConfig(vocab_size=args.vocab, d_model=args.d_model,
                          num_blocks=args.blocks, num_heads=args.heads,
                          d_ff=args.d_ff, src_len=args.seq_len,
                          tgt_len=args.seq_len,
                          dropout_rate=args.dropout)
    B = args.batch_size
    rng = np.random.default_rng(0)

    model = Seq2SeqTransformer(c)
    src = ht.placeholder_op("src", (B, c.src_len), dtype=np.int32)
    tin = ht.placeholder_op("tgt_in", (B, c.tgt_len), dtype=np.int32)
    tout = ht.placeholder_op("tgt_out", (B, c.tgt_len), dtype=np.int32)
    skeep = ht.placeholder_op("src_keep", (B, c.src_len))
    tkeep = ht.placeholder_op("tgt_keep", (B, c.tgt_len))
    loss = model.loss(src, tin, tout, skeep, tkeep)
    opt = ht.AdamOptimizer(learning_rate=args.lr)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]})

    for step in range(args.steps):
        s, ti, to, sk, tk = make_batch(rng, c, B)
        out = ex.run("train", feed_dict={src: s, tin: ti, tout: to,
                                         skeep: sk, tkeep: tk},
                     convert_to_numpy_ret_vals=True)
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {out[0]:.4f}")

    if args.generate:
        from hetu_tpu.models import seq2seq_generate
        s, _, to, sk, tk = make_batch(rng, c, B)
        gen = seq2seq_generate(ex, model, s, sk, c.tgt_len)
        acc = float((((gen == to) * tk).sum()) / tk.sum())
        print(f"greedy decode token accuracy: {acc:.3f}")
        print("src:", s[0][sk[0] > 0][:12])
        print("gen:", gen[0][:int(tk[0].sum())][:12])


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
