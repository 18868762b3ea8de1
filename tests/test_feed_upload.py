"""Host feeds go up in ONE ``device_put`` to where the step's in_shardings
wants them (``SubExecutor._upload``): under a mesh the jitted call receives
committed arrays that already have its input shardings, with no mesh
arrays on the default device; one path, counted in
``hetu_executor_feed_uploads_total{subgraph, placed}``.  The programs are
the benchmark's BERT cells at their toy sizes, fed as the benchmark feeds
them: five fresh numpy arrays a step."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.graph.executor import SubExecutor

CELLS = {"dp4": "bert-base.dp4-b256-s512", "one": "bert-base.b64-s512"}
FEEDS = ("attention_mask", "input_ids", "mlm_labels", "nsp_labels",
         "token_type_ids")


@pytest.fixture
def tel():
    telemetry.get_registry().reset()
    telemetry.enable()
    yield telemetry
    telemetry.disable()


def program(mesh):
    """The BERT builder's program at toy widths, ``DataParallel(4)`` or no
    mesh; twins start from the same parameters (initialisers are keyed by
    name and seed)."""
    from chipbench import run
    _, _, config, mix = run.load_cell(CELLS[mesh])
    config = run.merge(config, config["toy"])
    mix = run.merge(mix, mix["toy"])
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    with ht.name_scope():
        return builder.build(config, mix, 2 ** 31 + 7, lambda msg: None)


def uploads(subgraph="train"):
    """``{placed: arrays}`` of the subgraph's uploads so far."""
    fam = telemetry.get_registry().snapshot().get(
        "hetu_executor_feed_uploads_total", {"samples": []})
    return {s["labels"]["placed"]: s["value"] for s in fam["samples"]
            if s["labels"]["subgraph"] == subgraph and s["value"]}


def retraces(subgraph="train"):
    fam = telemetry.get_registry().snapshot()["hetu_executor_retraces_total"]
    return {s["labels"]["subgraph"]: s["value"]
            for s in fam["samples"]}[subgraph]


def spy_on_feeds(sub, monkeypatch):
    """The feeds of every call as ``run`` hands them to ``_dispatch`` and
    ``run_steps`` to its program."""
    seen = []

    def feeds_of(feed_dict):
        out = SubExecutor._feeds(sub, feed_dict)
        seen.append(dict(out[0]))
        return out
    monkeypatch.setattr(sub, "_feeds", feeds_of)
    return seen


def declared(batch):
    """A batch in the placeholders' own dtypes (the generator draws int64)."""
    return {p: v.astype(p.dtype) for p, v in batch.items()}


def wide(batch):
    """The same batch in numpy's widest: int64 and float64."""
    return {p: v.astype(np.float64 if v.dtype.kind == "f" else np.int64)
            for p, v in batch.items()}


@pytest.mark.parametrize("entry", ["run", "run_steps", "validate"])
@pytest.mark.parametrize("mesh", ["dp4", "one"])
def test_host_feeds_reach_the_call_where_it_wants_them(tel, monkeypatch,
                                                       mesh, entry):
    prog = program(mesh)
    ex = prog.ex
    subgraph = "validate" if entry == "validate" else "train"
    sub = ex.subexecutor[subgraph]
    seen = spy_on_feeds(sub, monkeypatch)
    batches = prog.make_batches(5, 2)
    # the generator's own dtypes (int64 ids), numpy's widest, the declared
    for step, feed in enumerate((batches[0], wide(batches[1]),
                                 declared(batches[0]))):
        if entry == "run_steps":
            ex.run_steps("train", feed, 2)
        else:
            ex.run(subgraph, feed_dict=feed)
        # (run_steps traces the step twice: in its loop and behind it)
        assert retraces(subgraph) == (2 if entry == "run_steps" else 1), \
            "a host feed's dtype retraced"
        assert uploads(subgraph) == {
            "sharded" if mesh == "dp4" else "default": 5 * (step + 1)}
    assert len(seen) == 3
    want = ex._input_shardings(sub)
    for feeds in seen:
        assert sorted(feeds) == sorted(FEEDS)
        for name, v in feeds.items():
            assert isinstance(v, jax.Array)
            assert v.dtype == prog.nodes[name].dtype
            if mesh == "dp4":
                assert v.committed
                assert v.sharding == want[2][name] == sub._feed_sh[name]
                assert len(v.sharding.device_set) == 4
            else:
                assert want is None and sub._feed_sh is None
                assert not v.committed
                assert v.devices() == {jax.devices()[0]}
    prog.close()


@pytest.mark.parametrize("mesh", ["dp4", "one"])
def test_the_trajectory_is_the_per_feed_path_s_bit_for_bit(monkeypatch,
                                                           mesh):
    """Three steps through the one ``device_put`` and three through what it
    replaced (each host feed an uncommitted array on the default device,
    cut up inside the jitted call): the same losses and the same
    parameters, to the bit."""
    new, old = program(mesh), program(mesh)
    for k in new.ex.params:
        np.testing.assert_array_equal(np.asarray(new.ex.params[k]),
                                      np.asarray(old.ex.params[k]))
    monkeypatch.setattr(
        old.ex.subexecutor["train"], "_upload",
        lambda host: {k: jnp.asarray(v) for k, v in host.items()})
    batches = new.make_batches(6, 3)
    for b_new, b_old in zip(batches, old.make_batches(6, 3)):
        assert new.step(b_new) == old.step(b_old)
    for k in new.ex.params:
        np.testing.assert_array_equal(np.asarray(new.ex.params[k]),
                                      np.asarray(old.ex.params[k]))
    new.close()
    old.close()


@pytest.mark.parametrize("mesh", ["dp4", "one"])
def test_device_feeds_pass_through_uncounted(tel, monkeypatch, mesh):
    """A caller's device arrays in the declared dtypes are the very arrays
    the call receives and arm the fast path; one in another dtype is cast
    on the device; neither is an upload."""
    prog = program(mesh)
    sub = prog.ex.subexecutor["train"]
    seen = spy_on_feeds(sub, monkeypatch)
    feed = {p: jnp.asarray(v) for p, v in
            declared(prog.make_batches(5, 1)[0]).items()}
    prog.ex.run("train", feed_dict=feed)
    assert sub._fast_feed is not None and uploads() == {}
    assert all(seen[0][p.name] is v for p, v in feed.items())
    mask = prog.nodes["attention_mask"]
    feed[mask] = feed[mask].astype(jnp.bfloat16)
    prog.ex.run("train", feed_dict=feed)
    assert seen[1]["attention_mask"].dtype == np.float32
    assert uploads() == {} and retraces() == 1
    prog.close()


@pytest.mark.parametrize("mesh", ["dp4", "one"])
def test_ps_rows_and_their_ids_take_the_same_call(tel, monkeypatch, mesh,
                                                  rng):
    """PS rows (here a batch's unique rows and the indices that gather
    them) are host arrays: they go up with the other host feeds, and the
    ids the host lookup consumed stay out of the jitted pytree."""
    from hetu_tpu.parallel import DataParallel
    from hetu_tpu.ps import PSEmbedding
    B, D, vocab = 16, 4, 100
    ids = ht.placeholder_op(f"fu_ids_{mesh}", (B,), dtype=np.int64)
    y = ht.placeholder_op(f"fu_y_{mesh}", (B, D))
    emb = PSEmbedding(vocab, D, optimizer="sgd", lr=0.5)
    loss = ht.mse_loss_op(emb(ids), y)
    ex = ht.Executor(
        {"train": [loss, ht.SGDOptimizer(0.1).minimize(loss)]},
        dist_strategy=DataParallel(ndev=4) if mesh == "dp4" else None)
    sub = ex.subexecutor["train"]
    seen = spy_on_feeds(sub, monkeypatch)
    feed = {ids: rng.integers(0, vocab, (B,)),
            y: rng.standard_normal((B, D)).astype(np.float32)}
    losses = [float(ex.run("train", feed_dict=feed,
                           convert_to_numpy_ret_vals=True)[0])
              for _ in range(4)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    rows, = sub.ps_rows
    assert sorted(seen[-1]) == sorted([rows.name, rows.inv_node.name,
                                       y.name])
    assert uploads() == {"sharded" if mesh == "dp4" else "default": 12}
    if mesh == "dp4":
        assert seen[-1][rows.name].sharding == sub._feed_sh[rows.name]
    ex.ps_synchronize()


@pytest.mark.parametrize("prefetched", [False, True])
def test_a_dataloader_s_batches_by_the_cached_structure(tel, monkeypatch,
                                                        prefetched):
    """A dataloader node's host batch takes the same upload from the fast
    path; a device-prefetched one passes through as it did."""
    from hetu_tpu.parallel import DataParallel
    data = np.arange(64, dtype=np.float64).reshape(16, 4)
    dl = ht.Dataloader(data, batch_size=4, shuffle=False,
                       device_prefetch=prefetched,
                       name=f"fu_dl_{prefetched}")
    op = ht.dataloader_op({"eval": dl})
    s = ht.reduce_sum_op(ht.reduce_sum_op(op, axes=1), axes=0)
    ex = ht.Executor({"eval": [s]}, training=False,
                     dist_strategy=DataParallel(ndev=4))
    sub = ex.subexecutor["eval"]
    seen = spy_on_feeds(sub, monkeypatch)
    try:
        sums = [float(ex.run("eval", convert_to_numpy_ret_vals=True)[0])
                for _ in range(3)]
        assert sums == [float(data[4 * i:4 * i + 4].sum())
                        for i in range(3)]
        assert sub._fast_feed is not None and retraces("eval") == 1
        assert uploads("eval") == ({} if prefetched else {"sharded": 3})
        if not prefetched:
            for feeds in seen:
                assert feeds[op.name].sharding == sub._feed_sh[op.name]
                assert feeds[op.name].dtype == np.float32
    finally:
        dl.stop()
