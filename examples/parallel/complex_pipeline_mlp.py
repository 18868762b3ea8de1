"""Mixed DP x PP pipeline MLP (reference
examples/runner/parallel/complex_pipeline_mlp.py:1 — an MLP whose blocks
carry explicit per-device contexts mixing data/model/pipeline
parallelism, launched via config{1..8}.yml worker counts).

TPU redesign: the same mix is ONE mesh.  Blocks get `with ht.stage(i)`
scopes (the reference's per-op ctx lists); the executor runs them as a
GPipe/1F1B schedule over the mesh's leading 'pp' axis, and each stage's
remaining mesh axes form its intra-stage submesh — here 'dp', so every
stage is data-parallel over the batch (GSPMD inserts the grad psum the
reference expressed as AllReduce ops).

Run on the virtual 8-device mesh (pp=4 x dp=2):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/parallel/complex_pipeline_mlp.py
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))

import numpy as np

import hetu_tpu as ht
from hetu_tpu.parallel import make_mesh
from hetu_tpu.parallel.mesh import DistState


def build(stages, width, batch, tag, dp=False):
    x = ht.placeholder_op(f"cx_{tag}", (batch, width))
    y = ht.placeholder_op(f"cy_{tag}", (batch, width))
    if dp:
        # batch-sharded over the intra-stage 'dp' axis
        x.dist_state = DistState({0: "dp"})
        y.dist_state = DistState({0: "dp"})
    h = x
    for s in range(stages):
        with ht.stage(s):
            w = ht.VariableOp(f"cw{s}_{tag}", (width, width),
                              ht.init.xavier_uniform())
            b = ht.VariableOp(f"cb{s}_{tag}", (width,), ht.init.zeros())
            h = ht.relu_op(ht.matmul_op(h, w) + ht.broadcastto_op(b, h))
    loss = ht.mse_loss_op(h, y)
    return x, y, loss


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--num-micro", type=int, default=4)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--schedule", default="gpipe",
                    choices=["gpipe", "1f1b"])
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    X = rng.standard_normal((args.batch, args.width)).astype(np.float32)
    Y = rng.standard_normal((args.batch, args.width)).astype(np.float32)

    # ONE graph drives both executors (identical seeded init); the
    # dist_state annotations only bind when a mesh is attached
    x, y, loss = build(args.stages, args.width, args.batch, "mlp",
                       dp=args.dp > 1)
    ex_ref = ht.Executor(
        {"train": [loss, ht.AdamOptimizer(1e-2).minimize(loss)]}, seed=3)
    # pp x dp mesh: stage i owns mesh.devices[i] (a dp-row of devices)
    mesh = make_mesh({"pp": args.stages, "dp": args.dp})
    ex_pp = ht.Executor(
        {"train": [loss, ht.AdamOptimizer(1e-2).minimize(loss)]}, seed=3,
        mesh=mesh, pipeline=args.schedule, num_micro=args.num_micro)

    t0 = time.time()
    for step in range(args.steps):
        l_ref = ex_ref.run("train", feed_dict={x: X, y: Y},
                           convert_to_numpy_ret_vals=True)[0]
        l_pp = ex_pp.run("train", feed_dict={x: X, y: Y},
                         convert_to_numpy_ret_vals=True)[0]
        np.testing.assert_allclose(l_pp, l_ref, rtol=3e-5, atol=3e-6)
        if step % 3 == 0 or step == args.steps - 1:
            print(f"step {step:3d}  pp×dp loss {float(l_pp):.6f}  "
                  f"single {float(l_ref):.6f}")
    print(f"loss parity over {args.steps} steps "
          f"(pp={args.stages} x dp={args.dp}, {args.schedule}, "
          f"micro={args.num_micro}) in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
