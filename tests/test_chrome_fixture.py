"""chrome_trace against a REAL ``jax.profiler.trace`` capture.

``tests/data/real_jax_capture.trace.json.gz`` is an actual (CPU)
``jax.profiler.trace`` artifact — real metadata lanes (``/host:CPU``
process, TFRT + python threads), real ``PjitFunction(step)`` executions,
real ``$file.py:123`` host-python frames — checked in so the
merge/aggregate paths are pinned to the format jax actually writes.  One
test takes a capture of its own: the program's spans are events OF the
capture (``hetu:<name>``), so nothing has to be aligned afterwards.

Also covers the PR 9 merge surface: ``telemetry.chrome_trace()`` lays
per-rid request lanes next to the capture's device lanes and the
tracer's host-phase lane in one document, without pid collisions.
"""

import gzip
import json
import os
import re
import shutil

import pytest

from hetu_tpu import telemetry
from hetu_tpu.telemetry.tracing import SpanTracer
from hetu_tpu.timeline import trace_aggregates

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "data", "real_jax_capture.trace.json.gz")

#: the capture's jitted-step executions (3 profiled steps)
STEP_RE = r"PjitFunction"


def _install(tmp_path):
    """Lay the fixture out as a capture dir: <d>/plugins/profile/
    <stamp>/*.trace.json.gz — the layout _latest_trace_json globs."""
    d = tmp_path / "cap" / "plugins" / "profile" / "0001"
    d.mkdir(parents=True)
    shutil.copy(FIXTURE, d / "host.trace.json.gz")
    return str(tmp_path / "cap")


def _events(doc_or_path):
    if isinstance(doc_or_path, dict):
        return doc_or_path["traceEvents"]
    with open(doc_or_path) as f:
        return json.load(f)["traceEvents"]


def test_fixture_is_a_real_capture():
    """Pin the fixture's provenance-critical shape: the jax metadata
    envelope, M-lane naming, and complete X events with float ts."""
    data = json.loads(gzip.open(FIXTURE).read())
    assert set(data) >= {"traceEvents", "displayTimeUnit", "metadata"}
    evs = data["traceEvents"]
    pn = [e for e in evs if e.get("ph") == "M"
          and e.get("name") == "process_name"]
    assert pn and any("CPU" in e["args"]["name"] for e in pn)
    steps = [e for e in evs if e.get("ph") == "X"
             and re.search(STEP_RE, str(e.get("name", "")))]
    assert len(steps) >= 3
    assert all("ts" in e and "dur" in e for e in steps)
    # real captures carry host-python frames ($file.py:123 fn) — the
    # aggregate path must know to drop them
    assert any(str(e.get("name", "")).startswith("$") for e in evs)


def test_program_spans_are_in_a_real_capture(tmp_path):
    """The spans need no aligning: while ``jax.profiler`` traces, every
    enabled span IS an event of the capture, ``hetu:<name>``, on the
    profiler's clock.  Three executor steps give three ``hetu:run`` step
    markers (``_r``, ``step_num`` = the global step) on the thread that
    called ``run``, each holding its ``hetu:h2d`` / ``hetu:dispatch`` /
    ``hetu:fetch``, beside the executions of the jitted step."""
    import glob

    import jax
    import numpy as np
    from jax.profiler import ProfileData

    import hetu_tpu as ht
    from hetu_tpu.layers import Linear

    with ht.name_scope():
        x = ht.placeholder_op("cap_x", (8, 4))
        loss = ht.reduce_mean_op(Linear(4, 3)(x))
    ex = ht.Executor({"train": [loss, ht.SGDOptimizer(0.1).minimize(loss)]})
    feed = {x: np.ones((8, 4), np.float32)}
    tracer = telemetry.get_tracer()
    tracer.clear()
    tracer.enabled = True
    try:
        ex.run("train", feed_dict=feed)          # compiled outside
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            for _ in range(3):
                ex.run("train", feed_dict=feed,
                       convert_to_numpy_ret_vals=True)
        finally:
            jax.profiler.stop_trace()
    finally:
        tracer.enabled = False
        ring = tracer.spans()
        tracer.clear()
    [path] = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats)) for e in line.events
              if e.name.startswith("hetu:")]
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines]
    [events] = [ev for ev in lines if ev]        # one host thread has them
    roots = sorted(e for e in events if e[0] == "hetu:run")
    assert [e[3]["step_num"] for e in roots] == [1, 2, 3]
    assert all(e[3]["_r"] == 1 for e in roots)
    for name in ("hetu:h2d", "hetu:dispatch", "hetu:fetch"):
        kids = sorted(e for e in events if e[0] == name)
        assert len(kids) == 3
        for (_, lo, hi, _), (_, k_lo, k_hi, _) in zip(roots, kids):
            assert lo <= k_lo and k_hi <= hi     # the profiler's clock
    assert {e[0] for e in events} == {"hetu:run", "hetu:h2d",
                                      "hetu:dispatch", "hetu:fetch"}
    # the ring saw the same three steps on the host clock
    assert [r[4] for r in ring if r[0] == "run"] == [
        "train:0", "train:1", "train:2", "train:3"]


def test_unaligned_merge_keeps_separate_clock_bases(tmp_path):
    cap = _install(tmp_path)
    tr = SpanTracer(capacity=16, enabled=True)
    tr._record("dispatch", 0.001, 0.002)
    evs = _events(tr.chrome_trace(jax_trace_dir=cap))
    host = [e for e in evs if e.get("ph") == "X"
            and e.get("name") == "dispatch" and e.get("pid") == 1 << 20]
    assert len(host) == 1 and host[0]["ts"] < 1e6
    assert any(re.search(STEP_RE, str(e.get("name", ""))) for e in evs)


def test_trace_aggregates_on_real_capture(tmp_path):
    cap = _install(tmp_path)
    agg = trace_aggregates(cap)
    # the jitted program's fused ops are in there...
    dot = next(v for name, v in agg.items() if "dot" in name)
    assert dot["count"] >= 3 and dot["total_us"] > 0
    # real captures carry zero-duration events too — counts must still
    # be sane even where total_us rounds to 0
    for row in agg.values():
        assert row["count"] >= 1 and row["total_us"] >= 0
    # ...and host-python tracer frames are not (unless asked for)
    assert not any(name.startswith("$") for name in agg)
    agg2 = trace_aggregates(cap, include_host_python=True)
    assert any(name.startswith("$") for name in agg2)


def test_merged_doc_carries_device_host_and_rid_lanes(tmp_path):
    """telemetry.chrome_trace(): one document, three worlds — capture
    device/host lanes, tracer phase lane (pid 1<<20), per-rid request
    lanes (pid >= (1<<20)+1) — with no pid collisions."""
    cap = _install(tmp_path)
    tr, rt = telemetry.get_tracer(), telemetry.get_request_trace()
    tr.clear(), rt.clear()
    tr.enabled = rt.enabled = True
    try:
        with tr.span("dispatch"):
            pass
        rt.event("e0-0", "queued", engine="e0")
        rt.event("e0-0", "admitted", engine="e0")
        rt.event("e0-0", "finish", engine="e1", reason="stop",
                 cluster=True)
        doc = telemetry.chrome_trace(jax_trace_dir=cap)
    finally:
        tr.enabled = rt.enabled = False
        tr.clear(), rt.clear()
    evs = doc["traceEvents"]
    cap_pids = {e["pid"] for e in _events(json.loads(gzip.open(
        FIXTURE).read()))if "pid" in e}
    pids = {e["pid"] for e in evs if "pid" in e}
    assert cap_pids <= pids and (1 << 20) in pids
    rid_pids = {e["pid"] for e in evs if e.get("ph") == "X"
                and e.get("args", {}).get("rid") == "e0-0"}
    assert rid_pids and min(rid_pids) >= (1 << 20) + 1
    assert not rid_pids & cap_pids
    # both engine instances the rid touched have process lanes
    lanes = {e["args"]["name"] for e in evs if e.get("ph") == "M"
             and e.get("name") == "process_name"}
    assert {"engine e0", "engine e1", "hetu host spans"} <= lanes
    assert any("CPU" in n for n in lanes)


def _install_synthetic_device_capture(tmp_path):
    """A synthetic capture with a DEVICE plane: pid 100 is a
    "/device:TPU:0" process whose "XLA Ops" lane carries the fused-op
    executions, next to a host plane with python frames — the shape a
    real TPU ``jax.profiler.trace`` writes, which the CPU fixture above
    cannot exercise (``trace_aggregates`` must keep ONLY the device
    lane there)."""
    doc = {"displayTimeUnit": "ns", "metadata": {"highres-ticks": True},
           "traceEvents": [
               {"ph": "M", "pid": 100, "name": "process_name",
                "args": {"name": "/device:TPU:0"}},
               {"ph": "M", "pid": 100, "tid": 1, "name": "thread_name",
                "args": {"name": "XLA Ops"}},
               {"ph": "M", "pid": 100, "tid": 2, "name": "thread_name",
                "args": {"name": "XLA Modules"}},
               {"ph": "M", "pid": 1, "name": "process_name",
                "args": {"name": "/host:CPU"}},
               {"ph": "M", "pid": 1, "tid": 7, "name": "thread_name",
                "args": {"name": "python"}},
               # device XLA Ops lane: 2 fusions + 1 dot + 1 copy
               {"ph": "X", "pid": 100, "tid": 1, "name": "fusion.1",
                "ts": 10.0, "dur": 100.0},
               {"ph": "X", "pid": 100, "tid": 1, "name": "fusion.1",
                "ts": 150.0, "dur": 60.0},
               {"ph": "X", "pid": 100, "tid": 1, "name": "dot.2",
                "ts": 250.0, "dur": 300.0},
               {"ph": "X", "pid": 100, "tid": 1, "name": "copy.3",
                "ts": 600.0, "dur": 40.0},
               # a device lane that is NOT XLA Ops (module envelope)
               {"ph": "X", "pid": 100, "tid": 2, "name": "jit_step",
                "ts": 5.0, "dur": 700.0},
               # host lane: dispatch work + a python tracer frame
               {"ph": "X", "pid": 1, "tid": 7, "name": "ExecuteSharded",
                "ts": 0.0, "dur": 900.0},
               {"ph": "X", "pid": 1, "tid": 7, "name": "$train.py:12 f",
                "ts": 1.0, "dur": 5.0},
           ]}
    d = tmp_path / "devcap" / "plugins" / "profile" / "0001"
    d.mkdir(parents=True)
    with gzip.open(d / "dev.trace.json.gz", "wt") as f:
        json.dump(doc, f)
    return str(tmp_path / "devcap")


def test_synthetic_xla_ops_lane_aggregates_device_only(tmp_path):
    cap = _install_synthetic_device_capture(tmp_path)
    agg = trace_aggregates(cap)
    # only the XLA Ops lane aggregates: no module envelope, no host
    # dispatch, no python frames
    assert set(agg) == {"fusion.1", "dot.2", "copy.3"}
    assert agg["fusion.1"]["count"] == 2
    assert agg["fusion.1"]["total_us"] == pytest.approx(160.0)
    assert agg["dot.2"]["total_us"] == pytest.approx(300.0)
    # pct is over the device-op total only (500us), not the host lanes
    assert agg["dot.2"]["pct"] == pytest.approx(60.0)
    # forcing the host view back on still works
    host = trace_aggregates(cap, device_ops_only=False)
    assert "ExecuteSharded" in host and "jit_step" in host


def test_profiler_attach_trace_matches_trace_aggregates(tmp_path):
    """ProgramProfiler.attach_trace goes through trace_aggregates: the
    measured_ops table on the profile must equal the direct call
    row-for-row on the synthetic device capture."""
    from hetu_tpu.telemetry.profiling import ProgramProfiler
    cap = _install_synthetic_device_capture(tmp_path)
    prof = ProgramProfiler()
    prof.capture("dev_prog", cost={"flops": 1e6, "bytes accessed": 1e5})
    agg = prof.attach_trace("dev_prog", cap)
    assert agg == trace_aggregates(cap)
    assert prof.profile("dev_prog")["measured_ops"] == agg
    with pytest.raises(KeyError):
        prof.attach_trace("never_captured", cap)


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
