"""Config-driven MLP training (reference: examples/runner/run_mlp.py).

--config local : one device, plain training
--config lar   : data-parallel over every local device (DP strategy;
                 GSPMD allreduces grads over the mesh — the reference's
                 local_allreduce.yml mode)
--config rar   : print the per-host commands a remote allreduce launch
                 would execute (remote_allreduce.yml), then run locally

Synthetic MNIST-shaped data keeps the example hermetic (no downloads).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import numpy as np

import hetu_tpu as ht
from hetu_tpu.models import MLP

OPTS = {
    "sgd": lambda lr: ht.SGDOptimizer(lr),
    "momentum": lambda lr: ht.MomentumOptimizer(lr),
    "nesterov": lambda lr: ht.MomentumOptimizer(lr, nesterov=True),
    "adagrad": lambda lr: ht.AdaGradOptimizer(lr),
    "adam": lambda lr: ht.AdamOptimizer(lr),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="local",
                    choices=["local", "lar", "rar"])
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--learning-rate", type=float, default=0.1)
    ap.add_argument("--opt", default="sgd", choices=sorted(OPTS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--validate", action="store_true")
    args = ap.parse_args()

    import jax

    if args.config == "rar":
        from hetu_tpu.launcher import DistConfig, launch
        cfg = DistConfig(os.path.join(os.path.dirname(__file__),
                                      "remote_allreduce.yml"))
        for host, cmd in launch(cfg, __file__, args=("--config", "lar"),
                                dry_run=True):
            print(f"[{host}] {cmd}")

    rng = np.random.default_rng(0)
    B = args.batch_size
    x = ht.placeholder_op("x", (B, 784))
    y = ht.placeholder_op("y", (B,), dtype=np.int32)
    model = MLP(dims=(784, 256, 256, 10))
    h = x
    for i, lin in enumerate(model.linears):
        h = lin(h)
        if i < len(model.linears) - 1:
            h = ht.relu_op(h)
    loss = ht.reduce_mean_op(ht.softmax_cross_entropy_sparse_op(h, y))
    opt = OPTS[args.opt](args.learning_rate)

    strategy = None
    if args.config == "lar":
        from hetu_tpu.parallel import DataParallel
        strategy = DataParallel(ndev=len(jax.devices()))
    subgraphs = {"train": [loss, opt.minimize(loss)]}
    if args.validate:
        subgraphs["validate"] = [loss]
    ex = ht.Executor(subgraphs, dist_strategy=strategy)

    # synthetic MNIST: 10 gaussian blobs in pixel space
    centers = rng.standard_normal((10, 784)).astype(np.float32)
    for step in range(args.steps):
        labels = rng.integers(0, 10, B)
        batch = (centers[labels]
                 + 0.5 * rng.standard_normal((B, 784))).astype(np.float32)
        out = ex.run("train", feed_dict={x: batch, y: labels},
                     convert_to_numpy_ret_vals=True)
        if step % 50 == 0 or step == args.steps - 1:
            print(f"step {step:4d} loss {float(out[0]):.4f}")
    if args.validate:
        labels = rng.integers(0, 10, B)
        batch = (centers[labels]
                 + 0.5 * rng.standard_normal((B, 784))).astype(np.float32)
        out = ex.run("validate", feed_dict={x: batch, y: labels},
                     convert_to_numpy_ret_vals=True)
        print(f"validate loss {float(out[0]):.4f}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
