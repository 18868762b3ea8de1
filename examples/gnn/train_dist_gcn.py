"""Distributed GCN on a PARTITIONED graph (reference: examples/gnn
run_dist.py + gnn_tools/part_graph.py — partition the node set, then
train with each worker owning one part).

Pipeline:
  1. ``partition_graph`` cuts the nodes into ``block`` balanced parts
     (BFS-LDG streaming + refinement — the METIS/part_graph role) and
     yields a permutation making parts contiguous.
  2. The sym-normalized adjacency is built in PERMUTED order, so
     block-sharding its rows over the mesh is exactly "device p owns
     part p" — the partitioner's locality shows up as a denser block
     diagonal, i.e. less ICI traffic for the off-part columns.
  3. ``DistGCN15D`` propagates on a (block, rep) mesh; training runs a
     2-layer GCN with cross-entropy on a train split and checks LOSS
     PARITY vs the identical single-device model.

Run on the 8-device virtual mesh:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/gnn/train_dist_gcn.py
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from hetu_tpu.gnn import partition_graph
from hetu_tpu.models.gnn import normalized_adjacency


def build_train_fn(mesh, lr):
    """2-layer GCN full-batch training step over the (block, rep) mesh:
    layer = A @ (H W); adjacency tiles sharded (block, rep), features
    row-sharded over rep, psum over rep — DistGCN15D's propagation with
    the loss/grad step fused in."""

    def gcn2(params, a, h):
        def layer(h_rows, w):
            hw = jnp.matmul(h_rows, w, preferred_element_type=jnp.float32)
            partial = jnp.matmul(a, hw, preferred_element_type=jnp.float32)
            return lax.psum(partial, "rep")
        z1 = jax.nn.relu(layer(h, params["w1"]))
        # rows of z1 are block-sharded; re-gather to rep-sharded rows
        z1_rows = lax.all_gather(z1, "block", tiled=True)
        idx = lax.axis_index("rep")
        n_rep = lax.psum(1, "rep")    # axis size, any jax version
        rows = z1_rows.shape[0] // n_rep
        z1_mine = lax.dynamic_slice_in_dim(z1_rows, idx * rows, rows)
        return layer(z1_mine, params["w2"])

    def sharded_loss(params, a, h, labels, mask):
        logits = gcn2(params, a, h).astype(jnp.float32)
        ll = jax.nn.log_softmax(logits, -1)
        picked = jnp.take_along_axis(ll, labels[:, None], 1)[:, 0]
        num = lax.psum(jnp.sum(picked * mask), "block")
        den = lax.psum(jnp.sum(mask), "block")
        return -num / den

    # differentiate THROUGH shard_map: jax transposes every collective
    # (psum/all_gather) correctly, so weight grads come out replicated —
    # no hand-placed grad psums to get wrong
    loss_fn = shard_map(
        sharded_loss, mesh=mesh,
        in_specs=(P(), P("block", "rep"), P("rep", None),
                  P("block"), P("block")),
        out_specs=P())

    @jax.jit
    def step(params, a, h, labels, mask):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, a, h, labels, mask))(params)
        new = jax.tree_util.tree_map(lambda p_, g: p_ - lr * g, params,
                                     grads)
        return new, loss

    return step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=None,
                    help="prefix of a Cora-format graph (<prefix>.content"
                         " + <prefix>.cites, e.g. examples/gnn/datasets/"
                         "cora_sample) — omit for a synthetic graph")
    ap.add_argument("--nodes", type=int, default=256)
    ap.add_argument("--edges", type=int, default=1536)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--block", type=int, default=4)
    ap.add_argument("--rep", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.5)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    if args.data:
        # real-format ingestion (reference sparse_datasets.py role):
        # citation files -> arrays -> partitioner input
        from hetu_tpu.gnn import load_cora
        ds = load_cora(args.data).to_undirected().normalize_features()
        n = ds.num_nodes
        src, dst = ds.src, ds.dst
        labels = ds.y
        feats = ds.x
        train_mask = ds.train_mask.astype(np.float32)
        args.features = feats.shape[1]
        args.classes = ds.num_classes
        print(f"{ds.name}: {n} nodes, {ds.num_edges} edges, "
              f"{args.features} features, {args.classes} classes")
    else:
        n = args.nodes
        # planted-partition graph (communities => the partitioner has
        # structure to find, and labels correlate with features)
        comm = rng.integers(0, args.classes, n)
        src, dst = [], []
        while len(src) < args.edges:
            u, v = rng.integers(0, n, 2)
            if comm[u] == comm[v] or rng.random() < 0.1:
                src.append(u)
                dst.append(v)
        src, dst = np.asarray(src), np.asarray(dst)
        labels = comm.astype(np.int32)
        feats = (rng.standard_normal((n, args.features)).astype(np.float32)
                 + np.eye(args.classes, args.features,
                          dtype=np.float32)[comm] * 2.0)
        train_mask = (rng.random(n) < 0.7).astype(np.float32)

    gp = partition_graph(src, dst, n, args.block, seed=0)
    rand_part = rng.integers(0, args.block, n)
    rand_cut = int((rand_part[src] != rand_part[dst]).sum())
    print(f"partitioned {n} nodes into {args.block} parts: "
          f"edge cut {gp.edge_cut} (random-assignment cut ~{rand_cut})")

    # permuted-order dense normalized adjacency: block rows = parts
    a = normalized_adjacency(gp.perm[src], gp.perm[dst], n)
    h = feats[gp.inv_perm]
    y = labels[gp.inv_perm]
    m = train_mask[gp.inv_perm]

    devs = np.array(jax.devices()[:args.block * args.rep]).reshape(
        args.block, args.rep)
    mesh = Mesh(devs, ("block", "rep"))
    params = {
        "w1": jnp.asarray(rng.standard_normal(
            (args.features, args.hidden)) * 0.2, jnp.float32),
        "w2": jnp.asarray(rng.standard_normal(
            (args.hidden, args.classes)) * 0.2, jnp.float32)}
    step = build_train_fn(mesh, args.lr)

    # single-device oracle for parity
    def single_step(params):
        def loss_fn(p):
            z1 = jax.nn.relu(a @ (h @ p["w1"]))
            logits = a @ (z1 @ p["w2"])
            ll = jax.nn.log_softmax(logits, -1)
            picked = jnp.take_along_axis(ll, y[:, None], 1)[:, 0]
            return -jnp.sum(picked * m) / jnp.sum(m)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        return jax.tree_util.tree_map(
            lambda p_, g: p_ - args.lr * g, params, grads), loss

    single_step = jax.jit(single_step)
    p_dist = jax.tree_util.tree_map(jnp.asarray, params)
    p_single = jax.tree_util.tree_map(jnp.asarray, params)
    aj, hj = jnp.asarray(a), jnp.asarray(h)
    yj, mj = jnp.asarray(y), jnp.asarray(m)
    for i in range(args.steps):
        p_dist, l_d = step(p_dist, aj, hj, yj, mj)
        p_single, l_s = single_step(p_single)
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i:3d}  dist loss {float(l_d):.5f}  "
                  f"single {float(l_s):.5f}")
        np.testing.assert_allclose(float(l_d), float(l_s), rtol=2e-4,
                                   atol=2e-5)
    print("loss parity: distributed == single-device at every step")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
