"""Mixture-of-experts training with expert parallelism (reference:
examples/moe — test_moe_top / gates over an `ep` mesh axis).

Runs on the virtual CPU mesh too:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/moe/train_moe.py
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import argparse

import numpy as np
import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.layers.moe import MoELayer
from hetu_tpu.parallel import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--gate", default="top2",
                    choices=["top1", "top2", "hash"])
    ap.add_argument("--experts", type=int, default=4)
    ap.add_argument("--expert-act", default="gelu",
                    choices=["gelu", "swiglu"],
                    help="swiglu = Mixtral-style gated experts")
    ap.add_argument("--batch-size", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=32)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    B, S, Hd = args.batch_size, args.seq_len, args.hidden
    x = ht.placeholder_op("x", (B, S, Hd))
    y = ht.placeholder_op("y", (B, S, Hd))
    k = 1 if args.gate == "top1" else 2
    moe = MoELayer(Hd, 4 * Hd, args.experts, k=k,
                   gate=("hash" if args.gate == "hash" else "top"),
                   expert_act=args.expert_act)
    tok_ids = None
    if args.gate == "hash":
        tok_ids = ht.placeholder_op("tok_ids", (B, S), dtype=np.int32)
    out = moe(x, ids=tok_ids)
    loss = ht.mse_loss_op(out, y)
    loss = loss + 0.01 * moe.aux_loss()
    opt = ht.AdamOptimizer(learning_rate=0.01)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]})

    for step in range(args.steps):
        feed = {x: rng.standard_normal((B, S, Hd)).astype(np.float32),
                y: rng.standard_normal((B, S, Hd)).astype(np.float32)}
        if tok_ids is not None:
            feed[tok_ids] = rng.integers(0, 30000, (B, S))
        out_v = ex.run("train", feed_dict=feed,
                       convert_to_numpy_ret_vals=True)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {out_v[0]:.4f}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
