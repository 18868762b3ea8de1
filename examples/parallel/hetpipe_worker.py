"""HetPipe/preduce worker replica as a real PROCESS.

Reference: pipedream_subexecutor.py:78-88 — each worker replica runs the
pipeline schedule locally and synchronizes weights through the parameter
server (SSP-gated push/pull) or through preduce group averaging.  Here
each replica is its own OS process (spawned by tests/test_hetpipe.py or
the launcher) talking to one PSServer that holds the authoritative
weights AND the coordination plane (SSP clocks, matchmaking, group
reduce — ps/rpc.py serve_dense_params).

Usage:
  python hetpipe_worker.py <host:port> <mode> <rank> <nworkers> \
      <steps> <straggle_ms> <out_dir>
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=2")
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))


def main():
    import numpy as np
    import jax
    import jax.numpy as jnp

    from hetu_tpu.parallel import make_mesh, PipelineParallel
    from hetu_tpu.parallel.hetpipe import HetPipeTrainer, DenseParamStore
    from hetu_tpu.ps.rpc import RemoteCoordinator

    host, port = sys.argv[1].rsplit(":", 1)
    mode, rank, nworkers, steps, straggle_ms = (
        sys.argv[2], int(sys.argv[3]), int(sys.argv[4]), int(sys.argv[5]),
        float(sys.argv[6]))
    out_dir = sys.argv[7]

    # every replica builds the SAME deterministic pipeline + data
    n_stages, n_micro, mb, d = 2, 2, 4, 8
    rng = np.random.default_rng(0)
    params = {"w": jnp.asarray(rng.standard_normal((n_stages, d, d)) * 0.3,
                               jnp.float32),
              "b": jnp.zeros((n_stages, d), jnp.float32)}
    xs = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)
    tgt = jnp.zeros_like(xs)
    mesh = make_mesh({"pp": n_stages})
    pipeline = PipelineParallel(
        mesh, lambda p, x: jnp.tanh(x @ p["w"] + p["b"]), n_stages,
        n_micro, lambda o, t: jnp.mean((o - t) ** 2))

    coord = RemoteCoordinator(host, int(port))
    kw = dict(mode=mode, lr=0.05)
    if mode == "hetpipe":
        # set_rows is idempotent with identical deterministic values, so
        # every replica may seed concurrently without a barrier
        kw["store"] = DenseParamStore.remote(host, int(port), params,
                                             seed_values=True)
        kw["ssp"] = coord
        kw["staleness"] = 1
    else:
        kw["scheduler"] = coord
        kw["reducer"] = coord
        # matchmaking window must cover the injected straggle (200ms)
        # PLUS scheduling jitter: on a loaded single-core CI box a 300ms
        # window lets the fast worker miss the straggler in every round,
        # so no full group ever forms
        kw["wait_time"] = 900.0
    trainer = HetPipeTrainer(pipeline, params, nworkers, **kw)

    losses, group_sizes = [], []
    for step in range(steps):
        if straggle_ms > 0:
            time.sleep(straggle_ms / 1e3)
        loss, params = trainer.step(rank, params, xs, tgt)
        losses.append(loss)
        if mode == "preduce":
            group_sizes.append(len(trainer.last_partner))
    trainer.mark_done(rank)

    out = {"rank": rank, "losses": losses, "group_sizes": group_sizes,
           "clocks": coord.clocks() if mode == "hetpipe" else None}
    with open(os.path.join(out_dir, f"hetpipe_{rank}.json"), "w") as f:
        json.dump(out, f)
    print(f"hetpipe worker {rank} OK", flush=True)


if __name__ == "__main__":
    main()
