"""Cluster launcher (reference: bin/heturun -> python/runner.py +
python/hetu/launcher.py + DistConfig, context.py:2204-2270).

The reference bootstraps MPI ranks + PS scheduler/server processes over ssh
and wires them with DMLC_* env vars.  On TPU pods the runtime contract is
jax.distributed: one process per host, all pointing at a coordinator
(chief), with the device topology discovered by the TPU runtime.  This
module keeps the reference's cluster-yaml schema and role model (workers +
parameter-store hosts + one chief) and emits/executes the per-host
commands; `launch_local` spawns in-process worker threads against a shared
PS store for single-host runs and tests (the reference's
launcher.py:18 multiprocess spawner plays this role).
"""

from __future__ import annotations

import os
import shlex
import socket
import subprocess
import threading

try:
    import yaml
    _HAS_YAML = True
except ImportError:  # pragma: no cover
    _HAS_YAML = False

_DEFAULT_PORT = 13030


class DistConfig:
    """Cluster topology (schema-compatible with the reference yaml:
    nodes: [{host, workers, servers, chief}])."""

    def __init__(self, file=None, num_local_servers=0, num_local_workers=1,
                 settings=None, port=_DEFAULT_PORT):
        if settings is not None:
            self.settings = settings
        elif file is None:
            assert num_local_workers > 0
            self.settings = {"nodes": [{
                "host": socket.gethostname(),
                "servers": num_local_servers,
                "workers": num_local_workers,
                "chief": True,
            }]}
        else:
            assert _HAS_YAML, "pyyaml is required to read cluster files"
            with open(file) as f:
                self.settings = yaml.safe_load(f.read())
        self.port = port
        allowed = {"host", "servers", "workers", "chief"}
        self.hosts, self.servers, self.workers = [], {}, {}
        chief = None
        for node in self.settings["nodes"]:
            assert set(node) <= allowed, f"bad node keys {set(node)}"
            self.hosts.append(node["host"])
            if node.get("servers", 0):
                self.servers[node["host"]] = node["servers"]
            if node.get("workers", 0):
                self.workers[node["host"]] = node["workers"]
            if node.get("chief", False):
                assert chief is None, "only one chief allowed"
                chief = node["host"]
        assert chief, "one node must set chief: true"
        self.chief = chief
        self.num_servers = sum(self.servers.values())
        self.num_workers = sum(self.workers.values())
        self.enable_PS = self.num_servers > 0

    def save(self, path):
        assert _HAS_YAML
        with open(path, "w") as f:
            yaml.safe_dump(self.settings, f)

    def __str__(self):
        return (f"Cluster {{ chief: {self.chief}, "
                f"servers({self.num_servers}): {self.servers}, "
                f"workers({self.num_workers}): {self.workers} }}")

    # -- jax.distributed env plumbing (replaces make_ps_config DMLC_*) ----
    def coordinator_address(self):
        return f"{self.chief}:{self.port}"

    def _worker_hosts(self):
        """Worker hosts with the chief FIRST: jax.distributed requires
        process 0 to live where the coordinator address points."""
        others = sorted(h for h in self.workers if h != self.chief)
        return ([self.chief] if self.chief in self.workers else []) + others

    def process_env(self, process_id):
        """Env for worker process `process_id` (process 0 is on the chief)."""
        return {
            "HETU_COORDINATOR": self.coordinator_address(),
            "HETU_NUM_PROCESSES": str(self.num_workers),
            "HETU_PROCESS_ID": str(process_id),
            "HETU_NUM_PS_HOSTS": str(len(self.servers)),
        }

    def worker_commands(self, script, args=()):
        """[(host, command)] bring-up plan, one command per worker process
        (the reference builds mpirun -H host:n); chief processes come first
        so process 0 can bind the coordinator port.  Remote hosts get ssh
        wrappers, local ones run directly."""
        out = []
        arg_str = " ".join(shlex.quote(a) for a in args)
        pid = 0
        local_names = (socket.gethostname(), "localhost", "127.0.0.1")
        for host in self._worker_hosts():
            for _ in range(self.workers[host]):
                env = self.process_env(pid)
                env_str = " ".join(f"{k}={v}" for k, v in env.items())
                cmd = (f"{env_str} python {shlex.quote(script)} "
                       f"{arg_str}").strip()
                if host not in local_names:
                    cmd = f"ssh {shlex.quote(host)} {shlex.quote(cmd)}"
                out.append((host, cmd))
                pid += 1
        return out


def initialize_from_env():
    """Call inside a launched worker: wires jax.distributed from the env
    set by `DistConfig.process_env` (no-op when single-process).  The
    platform is jax's own choice (``JAX_PLATFORMS``)."""
    import jax
    coord = os.environ.get("HETU_COORDINATOR")
    n = int(os.environ.get("HETU_NUM_PROCESSES", "1"))
    if coord and n > 1:
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=n,
            process_id=int(os.environ["HETU_PROCESS_ID"]))
    return jax


def launch_local(worker_fn, num_workers, ps_tables=None):
    """Single-host launch: run `worker_fn(rank, nranks)` on N threads
    sharing this process's PS store / preduce scheduler (the TPU analogue of
    the reference's in-process scheduler/server/worker spawner).

    Returns the per-rank results.  Exceptions propagate.
    """
    results = [None] * num_workers
    errors = []

    def run(rank):
        try:
            results[rank] = worker_fn(rank, num_workers)
        except Exception as e:  # noqa: BLE001 - surfaced below
            errors.append((rank, e))

    threads = [threading.Thread(target=run, args=(r,), daemon=True)
               for r in range(num_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        rank, err = errors[0]
        raise RuntimeError(f"worker {rank} failed: {err!r}") from err
    return results


def launch(config: DistConfig, script, args=(), dry_run=False):
    """Bring up the cluster: emit (and unless dry_run, execute) one command
    per worker process.  Returns the [(host, cmd)] plan.

    One process for each host's chips: the TPU runtime hands all of a
    host's chips to the first process that asks, and a second process on
    that host fails or hangs.  So a host with more than one worker is
    refused unless the launch environment pins ``JAX_PLATFORMS=cpu`` (a
    CPU rehearsal); on chips, one worker drives every local chip through
    a mesh."""
    plan = config.worker_commands(script, args)
    if not dry_run:
        crowded = {h: n for h, n in config.workers.items() if n > 1}
        if crowded and not os.environ.get(
                "JAX_PLATFORMS", "").startswith("cpu"):
            raise ValueError(
                f"one process per host: {crowded} asks for several "
                "workers on one host, and a host's chips belong to one "
                "process.  Use workers: 1 (one process drives all local "
                "chips), or set JAX_PLATFORMS=cpu for a CPU rehearsal.")
        procs = [subprocess.Popen(cmd, shell=True) for _, cmd in plan]
        for p in procs:
            p.wait()
    return plan


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(
        prog="heturun", description="hetu_tpu cluster launcher")
    ap.add_argument("-c", "--config", help="cluster yaml", default=None)
    ap.add_argument("-w", "--workers", type=int, default=1,
                    help="local workers when no config file")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the bring-up plan without executing")
    ap.add_argument("script")
    ap.add_argument("args", nargs=argparse.REMAINDER,
                    help="arguments forwarded to the script verbatim")
    ns = ap.parse_args(argv)
    config = DistConfig(file=ns.config, num_local_workers=ns.workers)
    plan = launch(config, ns.script, ns.args, dry_run=ns.dry_run)
    for host, cmd in plan:
        print(f"[{host}] {cmd}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
