"""Multi-process launch smoke worker (reference: tests/pstests/test_apis.py
spawning scheduler+server+worker processes via hetu.launcher + yaml).

Launched by `hetu_tpu.launcher.launch` (or heturun) as N separate python
processes: each initializes jax.distributed from the HETU_* env
(launcher.process_env), proves the cross-process collective plane with a
process_allgather, and proves the DCN-side PS story by pushing gradients
into a ShardedTable whose shards live in a SEPARATE server process
(ps.rpc.PSServer), then verifying every process's update landed.

Usage (what the launcher runs):
  HETU_COORDINATOR=... HETU_NUM_PROCESSES=2 HETU_PROCESS_ID=r \\
      python distributed_smoke.py <ps_host:ps_port> <out_dir>
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..")))


def main():
    import numpy as np
    from hetu_tpu.launcher import initialize_from_env
    from hetu_tpu.ps import RemoteTable, ShardedTable

    jax = initialize_from_env()
    from jax.experimental import multihost_utils

    pid = jax.process_index()
    nproc = jax.process_count()

    # 1. the collective plane works across the launched processes
    gathered = np.asarray(
        multihost_utils.process_allgather(np.asarray([pid], np.int32)))
    assert sorted(gathered.reshape(-1).tolist()) == list(range(nproc)), \
        gathered

    # 2. the PS plane: both workers share ONE table served by another
    #    process over TCP (DCN analogue); sgd lr=1 makes pushes visible
    host, port = sys.argv[1].rsplit(":", 1)
    remote = RemoteTable(host, int(port))
    table = ShardedTable(remote.rows, remote.dim, tables=[remote])
    key = 7
    table.push([key], np.full((1, remote.dim), float(pid + 1), np.float32))
    multihost_utils.sync_global_devices("after_push")
    row = table.lookup([key])[0]

    out = {"pid": pid, "nproc": nproc,
           "gathered": sorted(gathered.reshape(-1).tolist()),
           "row0": float(row[0])}
    with open(os.path.join(sys.argv[2], f"worker_{pid}.json"), "w") as f:
        json.dump(out, f)
    print(f"worker {pid} OK: {out}", flush=True)


if __name__ == "__main__":
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    main()
