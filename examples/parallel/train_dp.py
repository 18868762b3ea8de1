"""Distributed training strategies demo (reference: examples/runner +
auto_parallel — DP / FSDP / Megatron-TP over a device mesh).

On one chip, simulate 8 devices:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/parallel/train_dp.py --strategy dp
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import numpy as np
import jax

import hetu_tpu as ht
from hetu_tpu.models import MLP
from hetu_tpu.parallel import DataParallel, FSDP, MegatronLM


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategy", default="dp",
                    choices=["dp", "fsdp", "megatron", "single"])
    ap.add_argument("--ndev", type=int, default=0,
                    help="devices (0 = all visible)")
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args()

    ndev = args.ndev or len(jax.devices())
    strategy = {"dp": lambda: DataParallel(ndev=ndev),
                "fsdp": lambda: FSDP(ndev=ndev),
                "megatron": lambda: MegatronLM(ndev=ndev),
                "single": lambda: None}[args.strategy]()

    rng = np.random.default_rng(0)
    B = args.batch_size
    x = ht.placeholder_op("x", (B, 32))
    y = ht.placeholder_op("y", (B,), dtype=np.int32)
    model = MLP(dims=(32, 128, 2))
    logits = model(x)
    loss = ht.reduce_mean_op(ht.softmax_cross_entropy_sparse_op(logits, y))
    opt = ht.SGDOptimizer(learning_rate=0.3)
    ex = ht.Executor([loss, opt.minimize(loss)], dist_strategy=strategy)

    X = rng.standard_normal((B, 32)).astype(np.float32)
    Y = (X[:, 0] > 0).astype(np.int64)
    for step in range(args.steps):
        out = ex.run(feed_dict={x: X, y: Y},
                     convert_to_numpy_ret_vals=True)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"[{args.strategy} x{ndev}] step {step:4d} "
                  f"loss {out[0]:.4f}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
