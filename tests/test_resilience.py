"""Resilience subsystem: StepGuard policies, rolling checkpoints,
preemption resume, fault injection, and the shared retry helper."""

import json
import os
import pickle
import warnings

import numpy as np
import pytest
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.checkpoint import read_checkpoint
from hetu_tpu.resilience import (CheckpointError, FaultInjector,
                                 GuardTripped, RollingCheckpointManager,
                                 StepGuard, faults, retry)
from hetu_tpu.datasets.prefetch import DevicePrefetcher


def _toy(tag, guard=None, **ex_kwargs):
    """Tiny MSE regression step.  Built under ``name_scope`` so a second
    build with the same tag reproduces the SAME variable names (no
    process-global ``_1`` suffixing) — init is seeded by name, so that
    makes rebuilds bitwise-identical and checkpoints restorable into a
    "restarted" executor."""
    with ht.name_scope():
        x = ht.placeholder_op(f"rz_x_{tag}", (8, 4))
        y = ht.placeholder_op(f"rz_y_{tag}", (8, 1))
        w = ht.Variable(f"rz_w_{tag}", shape=(4, 1),
                        initializer=ht.init.xavier_normal())
        loss = ht.mse_loss_op(ht.matmul_op(x, w), y)
    if guard is not None:
        ex_kwargs["step_guard"] = guard
    ex = ht.Executor({"train": [loss,
                                ht.AdamOptimizer(0.05).minimize(loss)]},
                     **ex_kwargs)
    rng = np.random.default_rng(0)
    X = rng.standard_normal((8, 4)).astype(np.float32)
    Y = rng.standard_normal((8, 1)).astype(np.float32)
    return ex, x, y, X, Y, f"rz_w_{tag}"


def _params_host(ex):
    return {k: np.asarray(v).copy() for k, v in ex.params.items()}


def _assert_bitwise(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], np.asarray(b[k]))


# -- StepGuard ------------------------------------------------------------

def test_guard_skip_discards_nonfinite_update_bitwise():
    guard = StepGuard(policy="skip", defer=False)
    ex, x, y, X, Y, wn = _toy("gs", guard)
    for _ in range(3):
        ex.run("train", feed_dict={x: X, y: Y})
    before = _params_host(ex)
    bad = X.copy()
    bad[0, 0] = np.nan
    ex.run("train", feed_dict={x: bad, y: Y})
    # the fused in-graph select discarded the whole poisoned update
    _assert_bitwise(before, ex.params)
    assert guard.stats["skipped"] == 1
    assert guard.stats["nonfinite"] == 1
    # training continues finite on the next good batch
    out = ex.run("train", feed_dict={x: X, y: Y},
                 convert_to_numpy_ret_vals=True)
    assert np.isfinite(out[0])
    assert not np.array_equal(before[wn], np.asarray(ex.params[wn]))


def test_guard_abort_raises_guard_tripped():
    guard = StepGuard(policy="abort", defer=False)
    ex, x, y, X, Y, _ = _toy("ga", guard)
    ex.run("train", feed_dict={x: X, y: Y})
    bad = X.copy()
    bad[0, 0] = np.inf
    with pytest.raises(GuardTripped, match="non-finite"):
        ex.run("train", feed_dict={x: bad, y: Y})


def test_guard_deferred_detection_lags_one_step():
    """defer=True holds the sentinel one step: the NaN step itself
    returns; the NEXT run (or flush) trips."""
    guard = StepGuard(policy="abort", defer=True)
    ex, x, y, X, Y, _ = _toy("gd", guard)
    ex.run("train", feed_dict={x: X, y: Y})
    bad = X.copy()
    bad[0, 0] = np.nan
    ex.run("train", feed_dict={x: bad, y: Y})   # no raise yet
    with pytest.raises(GuardTripped):
        ex.run("train", feed_dict={x: X, y: Y})


def test_guard_flush_drains_pending():
    guard = StepGuard(policy="abort", defer=True)
    ex, x, y, X, Y, _ = _toy("gf", guard)
    bad = X.copy()
    bad[0, 0] = np.nan
    ex.run("train", feed_dict={x: bad, y: Y})
    with pytest.raises(GuardTripped):
        guard.flush()


def test_guard_rollback_restores_exact_prefault_params(tmp_path):
    mgr = RollingCheckpointManager(tmp_path, keep=2)
    guard = StepGuard(policy="rollback", manager=mgr, defer=False)
    ex, x, y, X, Y, _ = _toy("gr", guard)
    for _ in range(4):
        ex.run("train", feed_dict={x: X, y: Y})
    mgr.save(ex)
    saved = _params_host(ex)
    saved_step = ex._global_step
    ex.run("train", feed_dict={x: X, y: Y})     # good step on top
    bad = X.copy()
    bad[0, 0] = np.nan
    with pytest.warns(UserWarning, match="rolled back"):
        ex.run("train", feed_dict={x: bad, y: Y})
    assert guard.stats["rollbacks"] == 1
    # bitwise: the restore is the exact pre-fault checkpoint
    _assert_bitwise(saved, ex.params)
    assert ex._global_step == saved_step


def test_guard_rollback_requires_manager():
    with pytest.raises(ValueError, match="manager"):
        StepGuard(policy="rollback")


def test_guard_loss_spike_detection():
    guard = StepGuard(policy="abort", spike_factor=3.0, spike_warmup=2,
                      defer=False)
    ex, x, y, X, Y, _ = _toy("gl", guard)
    for _ in range(5):
        ex.run("train", feed_dict={x: X, y: Y})
    with pytest.raises(GuardTripped, match="spike"):
        ex.run("train", feed_dict={x: X, y: Y * 100.0})


def test_guard_run_steps_strips_sentinel():
    guard = StepGuard(policy="skip")
    ex, x, y, X, Y, _ = _toy("gm", guard)
    vals = ex.run_steps("train", {x: jnp.asarray(X), y: jnp.asarray(Y)},
                        5, convert_to_numpy_ret_vals=True)
    assert len(vals) == 2       # loss + optimizer op, no hidden scalars
    assert np.isfinite(vals[0])
    guard.flush()
    assert guard.stats["steps"] == 5


def test_guard_attach_to_built_executor():
    ex, x, y, X, Y, _ = _toy("gat")
    ex.run("train", feed_dict={x: X, y: Y})     # compiled unguarded
    guard = StepGuard(policy="abort", defer=False).attach(ex)
    bad = X.copy()
    bad[0, 0] = np.nan
    with pytest.raises(GuardTripped):
        ex.run("train", feed_dict={x: bad, y: Y})
    guard.detach(ex)
    ex.run("train", feed_dict={x: X, y: Y})     # unguarded again


# -- RollingCheckpointManager ---------------------------------------------

def test_rolling_retention_and_manifest(tmp_path):
    mgr = RollingCheckpointManager(tmp_path, keep=2)
    ex, x, y, X, Y, _ = _toy("rk")
    for _ in range(4):
        ex.run("train", feed_dict={x: X, y: Y})
        mgr.save(ex)
    files = sorted(f for f in os.listdir(tmp_path) if f.endswith(".pkl"))
    assert len(files) == 2
    assert mgr.latest_step() == 4
    with open(os.path.join(tmp_path, "MANIFEST.json")) as f:
        man = json.load(f)
    assert [e["step"] for e in man["entries"]] == [3, 4]
    assert all({"crc32", "bytes"} <= set(e) for e in man["entries"])


def test_restore_latest_survives_truncated_newest(tmp_path):
    mgr = RollingCheckpointManager(tmp_path, keep=3)
    ex, x, y, X, Y, _ = _toy("rt")
    for _ in range(2):
        ex.run("train", feed_dict={x: X, y: Y})
        mgr.save(ex)
    good = _params_host(ex)
    ex.run("train", feed_dict={x: X, y: Y})
    newest = mgr.save(ex)
    faults.tear_file(newest, frac=0.5)          # torn mid-write
    with pytest.warns(UserWarning, match="skipping bad checkpoint"):
        step = mgr.restore_latest(ex)
    assert step == 2
    _assert_bitwise(good, ex.params)


def test_restore_latest_skips_corrupt_and_nonfinite(tmp_path):
    mgr = RollingCheckpointManager(tmp_path, keep=3)
    ex, x, y, X, Y, wn = _toy("rc")
    ex.run("train", feed_dict={x: X, y: Y})
    mgr.save(ex)
    # a checkpoint that captured an already-poisoned run
    ex.params[wn] = jnp.full_like(ex.params[wn], np.nan)
    ex._global_step += 1
    mgr.save(ex)
    with pytest.warns(UserWarning, match="non-finite"):
        step = mgr.restore_latest(ex)
    assert step == 1
    assert np.isfinite(np.asarray(ex.params[wn])).all()


def test_restore_latest_raises_when_nothing_survives(tmp_path):
    mgr = RollingCheckpointManager(tmp_path, keep=2)
    ex, x, y, X, Y, _ = _toy("re")
    with pytest.raises(CheckpointError, match="no restorable"):
        mgr.restore_latest(ex)


def test_restore_latest_without_manifest(tmp_path):
    """A lost manifest must not strand intact checkpoint files."""
    mgr = RollingCheckpointManager(tmp_path, keep=2)
    ex, x, y, X, Y, _ = _toy("rm")
    ex.run("train", feed_dict={x: X, y: Y})
    mgr.save(ex)
    os.remove(os.path.join(tmp_path, "MANIFEST.json"))
    assert RollingCheckpointManager(tmp_path, keep=2).restore_latest(ex) == 1


def test_ps_tables_rewind_with_rollback(tmp_path):
    """The ROADMAP PS-path gap: host-store embedding rows snapshotted at
    checkpoint cadence must rewind with the device state — post-fault
    pushes to the PS table disappear on restore_latest."""
    from hetu_tpu.ps import EmbeddingTable

    mgr = RollingCheckpointManager(tmp_path, keep=2)
    tbl = EmbeddingTable(16, 4, optimizer="sgd", lr=1.0, init_scale=0.0)
    mgr.register_ps_table("emb", tbl)
    ex, x, y, X, Y, _ = _toy("psr")
    rng = np.random.default_rng(3)
    good_rows = rng.standard_normal((16, 4)).astype(np.float32)
    tbl.set_rows(np.arange(16), good_rows)
    ex.run("train", feed_dict={x: X, y: Y})
    mgr.save(ex)
    good_dev = _params_host(ex)
    # "post-fault" work: both device params and PS rows move on
    ex.run("train", feed_dict={x: X, y: Y})
    tbl.push(np.arange(16), np.ones((16, 4), np.float32))
    assert not np.allclose(tbl.to_numpy(), good_rows)
    assert mgr.restore_latest(ex) == 1
    _assert_bitwise(good_dev, ex.params)
    np.testing.assert_array_equal(tbl.to_numpy(), good_rows)
    # snapshot files obey keep-K retention alongside their checkpoints
    ex.run("train", feed_dict={x: X, y: Y})
    for _ in range(3):
        ex._global_step += 1
        mgr.save(ex)
    ps_files = [f for f in os.listdir(tmp_path) if "-ps-" in f]
    assert len(ps_files) == 2


def test_torn_ps_snapshot_fails_over_to_older_checkpoint(tmp_path):
    """A torn PS snapshot invalidates its WHOLE checkpoint candidate:
    restoring device state from step N with PS rows from step N-1 would
    silently mix two points in time."""
    from hetu_tpu.ps import EmbeddingTable

    mgr = RollingCheckpointManager(tmp_path, keep=3)
    tbl = EmbeddingTable(8, 4, optimizer="sgd", lr=1.0, init_scale=0.0)
    mgr.register_ps_table("emb", tbl)
    ex, x, y, X, Y, _ = _toy("pst")
    rng = np.random.default_rng(4)
    older_rows = rng.standard_normal((8, 4)).astype(np.float32)
    tbl.set_rows(np.arange(8), older_rows)
    ex.run("train", feed_dict={x: X, y: Y})
    mgr.save(ex)
    older_dev = _params_host(ex)
    ex.run("train", feed_dict={x: X, y: Y})
    tbl.push(np.arange(8), np.ones((8, 4), np.float32))
    mgr.save(ex)
    newest = [e for e in mgr.entries()][0]
    faults.tear_file(os.path.join(tmp_path,
                                  newest["ps"]["emb"]["file"]), frac=0.5)
    with pytest.warns(UserWarning, match="skipping bad checkpoint"):
        assert mgr.restore_latest(ex) == 1
    _assert_bitwise(older_dev, ex.params)
    np.testing.assert_array_equal(tbl.to_numpy(), older_rows)


def test_preemption_resumes_identical_loss_trajectory(tmp_path):
    """SIGTERM mid-run -> hook flushes a checkpoint -> a FRESH executor
    restores and replays the remaining steps bitwise."""
    total, cut = 10, 5
    # uninterrupted reference trajectory
    ex, x, y, X, Y, _ = _toy("pt")
    ref = [float(ex.run("train", feed_dict={x: X, y: Y},
                        convert_to_numpy_ret_vals=True)[0])
           for _ in range(total)]

    # interrupted run: same tag on a fresh graph -> identical init
    mgr = RollingCheckpointManager(tmp_path, keep=2)
    ex1, x1, y1, _, _, _ = _toy("pt")
    mgr.install_preemption_hook(ex1, exit_on_save=False)
    try:
        first = [float(ex1.run("train", feed_dict={x1: X, y1: Y},
                               convert_to_numpy_ret_vals=True)[0])
                 for _ in range(cut)]
        faults.simulate_preemption()
        assert mgr.preempted
    finally:
        mgr.uninstall_preemption_hook()
    np.testing.assert_array_equal(first, ref[:cut])

    # "restarted process": fresh executor, restore, finish the run
    ex2, x2, y2, _, _, _ = _toy("pt")
    assert mgr.restore_latest(ex2) == cut
    rest = [float(ex2.run("train", feed_dict={x2: X, y2: Y},
                          convert_to_numpy_ret_vals=True)[0])
            for _ in range(total - cut)]
    np.testing.assert_array_equal(rest, ref[cut:])


# -- sharded (multi-host) rolling checkpoints ------------------------------

def _run_steps(ex, x, y, X, Y, n):
    for _ in range(n):
        ex.run("train", feed_dict={x: X, y: Y})


def test_sharded_rolling_save_restore_bitwise(tmp_path):
    """sharded=True writes orbax shard DIRECTORIES under rolling
    retention, the manifest covers every shard file with bytes+CRC, and
    restore_latest round-trips bitwise."""
    ex, x, y, X, Y, _ = _toy("shr")
    mgr = RollingCheckpointManager(tmp_path, keep=2, sharded=True)
    for i in range(4):
        _run_steps(ex, x, y, X, Y, 1)
        mgr.save(ex)
    ents = mgr.entries()
    assert len(ents) == 2                       # keep-2 pruned the rest
    assert all(e["kind"] == "sharded" for e in ents)
    assert all(e["file"].endswith(".orbax") for e in ents)
    on_disk = [n for n in os.listdir(tmp_path) if n.endswith(".orbax")]
    assert sorted(on_disk) == sorted(e["file"] for e in ents)
    # the manifest's shard-set evidence matches the bytes on disk
    for e in ents:
        assert e["files"], "manifest entry covers no shard files"
        for rel, meta in e["files"].items():
            p = os.path.join(tmp_path, e["file"], rel)
            assert os.path.getsize(p) == meta["bytes"]
    saved = _params_host(ex)
    _run_steps(ex, x, y, X, Y, 2)               # diverge past the save
    restored = mgr.restore_latest(ex)
    assert restored == mgr.entries()[0]["step"]
    _assert_bitwise(saved, ex.params)


def test_sharded_restore_fails_over_torn_shard_set(tmp_path):
    """A shard set with one torn (truncated) file fails verification
    BEFORE the executor is touched and restore falls back to the
    previous intact set — the multi-host version of the torn-pickle
    failover."""
    ex, x, y, X, Y, _ = _toy("shr_torn")
    mgr = RollingCheckpointManager(tmp_path, keep=3, sharded=True)
    want = {}
    for i in range(3):
        _run_steps(ex, x, y, X, Y, 1)
        mgr.save(ex)
        want[mgr.entries()[0]["step"]] = _params_host(ex)
    newest, second = mgr.entries()[0], mgr.entries()[1]
    # tear the largest shard file of the newest set (a host preempted
    # mid-write)
    rel = max(newest["files"],
              key=lambda r: newest["files"][r]["bytes"])
    faults.tear_file(os.path.join(tmp_path, newest["file"], rel),
                     frac=0.4)
    with pytest.warns(UserWarning, match="skipping bad checkpoint"):
        restored = mgr.restore_latest(ex)
    assert restored == second["step"]
    _assert_bitwise(want[second["step"]], ex.params)


def test_sharded_restore_fails_over_missing_shard_dir(tmp_path):
    import shutil

    ex, x, y, X, Y, _ = _toy("shr_gone")
    mgr = RollingCheckpointManager(tmp_path, keep=3, sharded=True)
    for i in range(2):
        _run_steps(ex, x, y, X, Y, 1)
        mgr.save(ex)
    newest, second = mgr.entries()[0], mgr.entries()[1]
    shutil.rmtree(os.path.join(tmp_path, newest["file"]))
    _run_steps(ex, x, y, X, Y, 1)
    with pytest.warns(UserWarning, match="skipping bad checkpoint"):
        restored = mgr.restore_latest(ex)
    assert restored == second["step"]


def test_sharded_preemption_hook_flushes_shard_set(tmp_path):
    """SIGTERM under sharded mode flushes a full shard-set checkpoint
    (manifest included) exactly like the pickle path."""
    ex, x, y, X, Y, _ = _toy("shr_pre")
    mgr = RollingCheckpointManager(tmp_path, keep=2, sharded=True)
    mgr.install_preemption_hook(ex, exit_on_save=False)
    try:
        _run_steps(ex, x, y, X, Y, 3)
        saved = _params_host(ex)
        faults.simulate_preemption()
        assert mgr.preempted
        _run_steps(ex, x, y, X, Y, 2)     # post-preemption work, lost
        mgr.restore_latest(ex)
        _assert_bitwise(saved, ex.params)
    finally:
        mgr.uninstall_preemption_hook()


# -- typed PS exhaustion ---------------------------------------------------

@pytest.mark.timeout(60)
def test_ps_unreachable_raises_typed_psunavailable():
    """A RemoteTable whose server is gone exhausts its wall-clock retry
    deadline and raises PSUnavailable (a typed terminal error carrying
    addr/deadline/attempts), not a generic ConnectionError — and it
    still IS a ConnectionError for existing handlers."""
    import socket
    from hetu_tpu.ps import PSUnavailable
    from hetu_tpu.ps.rpc import RemoteTable

    # grab a port nothing listens on
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    t = RemoteTable("127.0.0.1", port, timeout=0.5, retry_deadline=1.0,
                    pool_size=1, fetch_meta=False)
    try:
        with pytest.raises(PSUnavailable) as ei:
            t.lookup(np.array([0]))
        assert ei.value.attempts >= 1
        assert ei.value.deadline == 1.0
        assert isinstance(ei.value, ConnectionError)
    finally:
        t.close()


# -- fault injection ------------------------------------------------------

@pytest.mark.timeout(30)
@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_prefetcher_death_surfaces_within_one_step():
    src = ({"a": np.ones(3, np.float32)} for _ in range(100))
    pf = DevicePrefetcher(faults.killer_stream(src, at=2), depth=2,
                          sync=False)
    assert next(pf) is not None
    assert next(pf) is not None
    with pytest.raises(RuntimeError, match="producer thread died"):
        next(pf)
    pf.close()


@pytest.mark.timeout(30)
def test_prefetcher_loader_error_propagates():
    src = ({"a": np.ones(3, np.float32)} for _ in range(100))
    pf = DevicePrefetcher(faults.raising_stream(src, at=1), depth=2,
                          sync=False)
    assert next(pf) is not None
    with pytest.raises(faults.InjectedFault):
        next(pf)
    pf.close()


def test_nan_stream_poisons_only_chosen_steps():
    src = ({"d": np.zeros(4, np.float32),
            "i": np.zeros(4, np.int32)} for _ in range(5))
    out = list(faults.nan_stream(src, at=[1, 3]))
    for i, b in enumerate(out):
        assert np.isnan(b["d"]).any() == (i in (1, 3))
        assert b["i"].dtype == np.int32    # int leaves untouched


def test_fault_injector_deterministic():
    a = FaultInjector(7).pick_steps(100, n_faults=3)
    b = FaultInjector(7).pick_steps(100, n_faults=3)
    c = FaultInjector(8).pick_steps(100, n_faults=3)
    assert a == b
    assert len(set(a)) == 3
    assert a != c


@pytest.mark.timeout(60)
def test_rpc_drop_and_delay_injection():
    """A dropped-mid-wire PS RPC is absorbed by reconnect+retransmit
    (dedup keeps non-idempotent verbs exactly-once)."""
    from hetu_tpu.ps.store import EmbeddingTable
    from hetu_tpu.ps.rpc import PSServer, RemoteTable
    srv = PSServer(EmbeddingTable(16, 4, optimizer="sgd", lr=1.0,
                                  init_scale=0)).start()
    t = RemoteTable(srv.host, srv.port, retry_deadline=20.0, pool_size=1)
    try:
        undo = faults.drop_rpc(t, calls=1)
        t.set_rows(np.array([3]), np.full((1, 4), 7.0, np.float32))
        undo()
        np.testing.assert_allclose(t.lookup(np.array([3])),
                                   np.full((1, 4), 7.0))
        undo = faults.delay_rpc(t, 0.2, calls=1)
        t.push(np.array([3]), np.ones((1, 4), np.float32))
        undo()
        # sgd lr=1.0: row = 7 - 1
        np.testing.assert_allclose(t.lookup(np.array([3])),
                                   np.full((1, 4), 6.0))
    finally:
        t.close()
        srv.stop()


# -- retry helper ---------------------------------------------------------

def test_retry_succeeds_after_transient_failures():
    calls, pauses = [], []
    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("transient")
        return "ok"
    assert retry(flaky, attempts=5, backoff=0.1, factor=2.0,
                 sleep=pauses.append) == "ok"
    assert len(calls) == 3
    assert pauses == [0.1, 0.2]     # exponential, no jitter


def test_retry_exhausts_attempts_with_original_error():
    def always():
        raise ValueError("nope")
    with pytest.raises(ValueError, match="nope"):
        retry(always, attempts=3, backoff=0, sleep=lambda s: None)


def test_retry_deadline_bounds_wall_clock():
    t = [0.0]
    def always():
        raise OSError("down")
    with pytest.raises(OSError):
        retry(always, deadline=1.0, backoff=0.3, factor=1.0,
              clock=lambda: t[0],
              sleep=lambda s: t.__setitem__(0, t[0] + s))
    assert t[0] <= 1.0 + 1e-9


def test_retry_giveup_short_circuits():
    pauses = []
    def always():
        raise ConnectionError("closed underneath")
    with pytest.raises(ConnectionError):
        retry(always, attempts=10, sleep=pauses.append,
              giveup=lambda e: "closed" in str(e))
    assert pauses == []


def test_retry_requires_a_bound():
    with pytest.raises(ValueError, match="unbounded"):
        retry(lambda: None)


def test_retry_nonretryable_propagates_immediately():
    calls = []
    def once():
        calls.append(1)
        raise KeyError("bug, not flake")
    with pytest.raises(KeyError):
        retry(once, attempts=5, retry_on=(OSError,),
              sleep=lambda s: None)
    assert len(calls) == 1


# -- resilient fetch ------------------------------------------------------

def test_fetch_atomic_from_file_url(tmp_path):
    from hetu_tpu.datasets._io import fetch
    src = tmp_path / "src.txt"
    src.write_text("payload")
    dest = tmp_path / "out" / "data.txt"
    got = fetch(f"file://{src}", str(dest), attempts=2, backoff=0)
    assert got == str(dest)
    assert dest.read_text() == "payload"
    # existing dest short-circuits (no re-download)
    src.write_text("changed")
    assert fetch(f"file://{src}", str(dest)) == str(dest)
    assert dest.read_text() == "payload"


def test_fetch_failure_leaves_no_partial(tmp_path):
    from hetu_tpu.datasets._io import fetch
    dest = tmp_path / "never.txt"
    with pytest.raises(OSError):
        fetch(f"file://{tmp_path}/does-not-exist", str(dest),
              attempts=2, backoff=0)
    assert not dest.exists()
    assert not any(".part" in f for f in os.listdir(tmp_path))


# -- Executor.save/load hardening -----------------------------------------

def test_executor_save_is_atomic(tmp_path):
    ex, x, y, X, Y, _ = _toy("sa")
    ex.run("train", feed_dict={x: X, y: Y})
    p = str(tmp_path / "ck.pkl")
    ex.save(p)
    # a save that dies mid-write must not destroy the previous file
    ex.state_dict = lambda: {"params": {"f": lambda: 0}, "opt_state": {},
                             "global_step": 0, "base_key": 0}
    with pytest.raises(Exception):
        ex.save(p)
    assert isinstance(read_checkpoint(p), dict)     # previous intact
    assert not any(".tmp." in f for f in os.listdir(tmp_path))


def test_load_rejects_garbage_with_checkpoint_error(tmp_path):
    ex, x, y, X, Y, _ = _toy("lg")
    p = tmp_path / "bad.pkl"
    p.write_bytes(b"this is not a pickle")
    with pytest.raises(CheckpointError, match="torn write or corrupt"):
        ex.load(str(p))


def test_load_rejects_wrong_payload_shapes(tmp_path):
    ex, x, y, X, Y, _ = _toy("lw")
    p = tmp_path / "list.pkl"
    with open(p, "wb") as f:
        pickle.dump([1, 2, 3], f)
    with pytest.raises(CheckpointError, match="expected the dict"):
        ex.load(str(p))
    p2 = tmp_path / "missing.pkl"
    with open(p2, "wb") as f:
        pickle.dump({"params": {}}, f)
    with pytest.raises(CheckpointError, match="missing required keys"):
        ex.load(str(p2))
    with pytest.raises(CheckpointError):
        ex.load_state_dict({"params": {}})


def test_load_rejects_future_format_version(tmp_path):
    ex, x, y, X, Y, _ = _toy("lf")
    state = ex.state_dict()
    state["format"] = dict(state["format"], version=99)
    p = str(tmp_path / "v99.pkl")
    with open(p, "wb") as f:
        pickle.dump(state, f)
    with pytest.raises(CheckpointError, match="newer than"):
        ex.load(p)
