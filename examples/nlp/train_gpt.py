"""GPT causal-LM training (reference: examples/nlp + auto_parallel gpt).

Usage: python examples/nlp/train_gpt.py [--model small --steps 20]
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import numpy as np
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.models import GPTConfig, GPTLMHeadModel, GPT_CONFIGS


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="gpt-small",
                    choices=list(GPT_CONFIGS))
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--layers", type=int, default=0,
                    help="override layer count (0 = model default)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    base = dict(GPT_CONFIGS[args.model])
    if args.layers:
        base["num_layers"] = args.layers
    c = GPTConfig(seq_len=args.seq_len, dropout_prob=0.0, **base)
    rng = np.random.default_rng(0)
    B, S = args.batch_size, args.seq_len

    ids = ht.placeholder_op("ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op("labels", (B, S), dtype=np.int32)
    model = GPTLMHeadModel(c)
    loss = model.loss(ids, labels)
    opt = ht.AdamWOptimizer(learning_rate=args.lr, weight_decay=0.01)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                     compute_dtype=jnp.bfloat16)

    for step in range(args.steps):
        tok = rng.integers(0, c.vocab_size, (B, S + 1))
        feed = {ids: tok[:, :-1], labels: tok[:, 1:]}
        out = ex.run("train", feed_dict=feed,
                     convert_to_numpy_ret_vals=True)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {out[0]:.4f}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
