"""Launcher + graphboard tests (reference: runner.py cluster bring-up,
python/graphboard)."""

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.launcher import DistConfig, launch_local, launch
from hetu_tpu import graphboard


def test_distconfig_local_default():
    c = DistConfig(num_local_workers=4)
    assert c.num_workers == 4 and not c.enable_PS
    assert c.chief in c.hosts
    env = c.process_env(0)
    assert env["HETU_NUM_PROCESSES"] == "4"  # one process per worker


def test_distconfig_multi_host_plan():
    settings = {"nodes": [
        {"host": "tpu-vm-0", "workers": 1, "servers": 1, "chief": True},
        {"host": "tpu-vm-1", "workers": 1},
        {"host": "tpu-vm-2", "workers": 1, "servers": 1},
    ]}
    c = DistConfig(settings=settings)
    assert c.num_workers == 3 and c.num_servers == 2 and c.enable_PS
    assert c.chief == "tpu-vm-0"
    assert c.coordinator_address() == "tpu-vm-0:13030"
    plan = c.worker_commands("train.py", ("--bs", "64"))
    assert len(plan) == 3
    hosts = [h for h, _ in plan]
    assert hosts == sorted(["tpu-vm-0", "tpu-vm-1", "tpu-vm-2"])
    for pid, (host, cmd) in enumerate(plan):
        assert f"HETU_PROCESS_ID={pid}" in cmd
        assert "HETU_NUM_PROCESSES=3" in cmd
        assert "ssh" in cmd  # none of these fake hosts are local
        assert "train.py" in cmd and "--bs" in cmd


def test_chief_is_process_zero_even_when_sorting_later():
    settings = {"nodes": [
        {"host": "tpu-b", "workers": 1, "chief": True},
        {"host": "tpu-a", "workers": 1},
    ]}
    c = DistConfig(settings=settings)
    plan = c.worker_commands("t.py")
    # process 0 must live on the chief (it binds the coordinator port)
    host0, cmd0 = plan[0]
    assert host0 == "tpu-b" and "HETU_PROCESS_ID=0" in cmd0
    assert "HETU_COORDINATOR=tpu-b:13030" in cmd0


def test_multiple_local_workers_spawn_multiple_processes():
    c = DistConfig(num_local_workers=4)
    plan = c.worker_commands("t.py")
    assert len(plan) == 4
    for pid, (_, cmd) in enumerate(plan):
        assert f"HETU_PROCESS_ID={pid}" in cmd
        assert "HETU_NUM_PROCESSES=4" in cmd


def test_distconfig_yaml_roundtrip(tmp_path):
    yaml = pytest.importorskip("yaml")  # noqa: F841
    settings = {"nodes": [{"host": "a", "workers": 2, "chief": True}]}
    c = DistConfig(settings=settings)
    p = str(tmp_path / "cluster.yml")
    c.save(p)
    c2 = DistConfig(file=p)
    assert c2.num_workers == 2 and c2.chief == "a"


def test_launch_dry_run():
    c = DistConfig(settings={"nodes": [
        {"host": "h0", "workers": 1, "chief": True}]})
    plan = launch(c, "job.py", dry_run=True)
    assert len(plan) == 1 and "job.py" in plan[0][1]


def test_launch_refuses_several_workers_on_one_host(monkeypatch):
    """A host's chips belong to one process: two workers on one host are
    refused unless the launch environment pins the CPU platform."""
    c = DistConfig(settings={"nodes": [
        {"host": "localhost", "workers": 2, "chief": True}]})
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(ValueError, match="one process per host"):
        launch(c, "job.py")
    assert len(launch(c, "job.py", dry_run=True)) == 2   # the plan prints


def test_launch_local_workers_share_state():
    from hetu_tpu.ps import PReduceScheduler
    sched = PReduceScheduler(4)

    def worker(rank, nranks):
        assert nranks == 4
        return sched.get_partner(0, rank, nranks, 100.0)

    results = launch_local(worker, 4)
    assert all(r == (0, 1, 2, 3) for r in results)
    sched.close()


def test_launch_local_propagates_errors():
    def worker(rank, nranks):
        if rank == 1:
            raise ValueError("boom")
        return rank

    with pytest.raises(RuntimeError, match="worker 1 failed"):
        launch_local(worker, 2)


def test_graphboard_dot_and_html(tmp_path):
    x = ht.placeholder_op("gx", (4, 8))
    w = ht.Variable("gw", shape=(8, 2), initializer=ht.init.zeros())
    out = ht.softmax_op(ht.matmul_op(x, w))
    dot = graphboard.graph_to_dot([out])
    assert "digraph" in dot and "matmul" in dot and "->" in dot
    p = graphboard.dump_html([out], str(tmp_path / "graph.html"))
    content = open(p).read()
    assert "<svg" in content and "softmax" in content
    # placeholders blue, trainable vars orange
    assert "#8ecae6" in content and "#ffb703" in content
