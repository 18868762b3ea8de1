"""GCN node classification, single-device and 1.5-D distributed
(reference: examples/gnn + gpu_ops/DistGCN_15d.py).

--dist runs the (block, rep) mesh propagation; on one chip set
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8
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
from hetu_tpu.models.gnn import (distgcn_15d_op, DistGCN15D,
                                 normalized_adjacency)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=256)
    ap.add_argument("--edges", type=int, default=2048)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--classes", type=int, default=7)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--dist", action="store_true",
                    help="1.5-D mesh propagation demo after training")
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    n = args.nodes
    src = rng.integers(0, n, args.edges).astype(np.int32)
    dst = rng.integers(0, n, args.edges).astype(np.int32)

    feats = ht.placeholder_op("feats", (n, args.features))
    labels = ht.placeholder_op("labels", (n,), dtype=np.int32)
    sv = ht.Variable("src", value=src, trainable=False)
    dv = ht.Variable("dst", value=dst, trainable=False)
    w1 = ht.Variable("w1", shape=(args.features, args.hidden),
                     initializer=ht.init.xavier_normal())
    w2 = ht.Variable("w2", shape=(args.hidden, args.classes),
                     initializer=ht.init.xavier_normal())
    h1 = ht.relu_op(distgcn_15d_op(feats, w1, sv, dv, num_nodes=n))
    logits = distgcn_15d_op(h1, w2, sv, dv, num_nodes=n)
    loss = ht.reduce_mean_op(
        ht.softmax_cross_entropy_sparse_op(logits, labels))
    ex = ht.Executor({"train": [loss,
                                ht.AdamOptimizer(0.02).minimize(loss)]})

    F = rng.standard_normal((n, args.features)).astype(np.float32)
    y = rng.integers(0, args.classes, (n,))
    for step in range(args.steps):
        out = ex.run("train", feed_dict={feats: F, labels: y},
                     convert_to_numpy_ret_vals=True)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {out[0]:.4f}")

    if args.dist:
        ndev = len(jax.devices())
        block, rep = max(1, ndev // 2), min(2, ndev)
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:block * rep]).reshape(block,
                                                                  rep),
                    ("block", "rep"))
        a = normalized_adjacency(src, dst, n)
        layer = DistGCN15D(mesh)
        w1_v = ex.get_params()[w1.name]
        z = layer(jnp.asarray(a), jnp.asarray(F), w1_v)
        print(f"1.5-D propagation on {block}x{rep} mesh -> {z.shape}")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
