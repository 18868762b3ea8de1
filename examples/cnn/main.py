"""Classic-zoo training entry (reference: examples/cnn/main.py --model).

Covers every model in the reference's examples/cnn/models directory:
mlp, logreg, cnn, lenet, alexnet, vgg16, vgg19, resnet18, resnet34,
rnn, lstm.  Synthetic MNIST/CIFAR-shaped data keeps it hermetic.

  python examples/cnn/main.py --model lstm --steps 50
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import numpy as np

import hetu_tpu as ht
from hetu_tpu import models as M

# model -> (constructor, per-sample input shape)
ZOO = {
    "mlp": (M.MLP, (784,)),
    "logreg": (M.LogReg, (784,)),
    "cnn": (M.CNN3, (1, 28, 28)),
    "lenet": (M.LeNet, (1, 28, 28)),
    "alexnet": (M.AlexNet, (1, 28, 28)),
    "vgg16": (M.vgg16, (3, 32, 32)),
    "vgg19": (M.vgg19, (3, 32, 32)),
    "resnet18": (M.resnet18, (3, 32, 32)),
    "resnet34": (M.resnet34, (3, 32, 32)),
    "rnn": (M.RNNClassifier, (28, 28)),
    "lstm": (M.LSTMClassifier, (28, 28)),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="cnn", choices=sorted(ZOO))
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--opt", default="adam",
                    choices=["sgd", "momentum", "adam"])
    args = ap.parse_args()

    build, sample_shape = ZOO[args.model]
    rng = np.random.default_rng(0)
    B = args.batch_size
    x = ht.placeholder_op("images", (B,) + sample_shape)
    y = ht.placeholder_op("labels", (B,), dtype=np.int32)
    model = build()
    logits = model(x)
    loss = ht.reduce_mean_op(ht.softmax_cross_entropy_sparse_op(logits, y))
    opt = {"sgd": lambda: ht.SGDOptimizer(args.lr),
           "momentum": lambda: ht.MomentumOptimizer(args.lr, momentum=0.9),
           "adam": lambda: ht.AdamOptimizer(args.lr)}[args.opt]()
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]})

    # separable synthetic data: class-dependent gaussian blobs
    centers = rng.standard_normal((10,) + sample_shape).astype(np.float32)
    for step in range(args.steps):
        labels = rng.integers(0, 10, B)
        imgs = (centers[labels]
                + 0.5 * rng.standard_normal(
                    (B,) + sample_shape)).astype(np.float32)
        out = ex.run("train", feed_dict={x: imgs, y: labels},
                     convert_to_numpy_ret_vals=True)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"[{args.model}] step {step:4d}  loss {out[0]:.4f}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
