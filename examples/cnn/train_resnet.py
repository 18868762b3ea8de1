"""ResNet-18 image classification (reference: examples/cnn).

Synthetic CIFAR-10-shaped data by default; plug a real data source into
`batches()`.  Usage: python examples/cnn/train_resnet.py [--steps 50]
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import numpy as np

import hetu_tpu as ht
from hetu_tpu.models import resnet18


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--lr", type=float, default=0.05)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    B = args.batch_size
    x = ht.placeholder_op("images", (B, 3, 32, 32))
    y = ht.placeholder_op("labels", (B,), dtype=np.int32)
    model = resnet18(num_classes=10)
    logits = model(x)
    loss = ht.reduce_mean_op(ht.softmax_cross_entropy_sparse_op(logits, y))
    acc = ht.reduce_mean_op(
        ht.equal_op(ht.cast_op(ht.argmax_op(logits, dim=1),
                               dtype=np.float32),
                    ht.cast_op(y, dtype=np.float32)))
    opt = ht.MomentumOptimizer(learning_rate=args.lr, momentum=0.9)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)],
                      "eval": [loss, acc]})

    def batches():
        while True:
            imgs = rng.standard_normal((B, 3, 32, 32)).astype(np.float32)
            labels = rng.integers(0, 10, (B,))
            yield {x: imgs, y: labels}

    it = batches()
    for step in range(args.steps):
        feed = next(it)
        out = ex.run("train", feed_dict=feed,
                     convert_to_numpy_ret_vals=True)
        if step % 10 == 0 or step == args.steps - 1:
            ev = ex.run("eval", feed_dict=feed,
                        convert_to_numpy_ret_vals=True)
            print(f"step {step:4d}  loss {out[0]:.4f}  "
                  f"eval_loss {ev[0]:.4f}  acc {ev[1]:.3f}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
