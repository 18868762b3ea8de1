"""Dataloader / metrics / logger / tokenizer tests (reference test model:
tests/test_dataloader-style batch correctness + metric numerics)."""

import os
import json

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import metrics
from hetu_tpu.dataloader import Dataloader, DataloaderOp
from hetu_tpu.tokenizers import BertTokenizer


# ---------------- dataloader ----------------

def test_dataloader_batches_cover_data():
    data = np.arange(100).reshape(100, 1)
    dl = Dataloader(data, batch_size=10, shuffle=False)
    batches = list(dl)
    assert len(batches) == 10
    np.testing.assert_array_equal(np.concatenate(batches), data)


def test_dataloader_drop_last():
    dl = Dataloader(np.arange(25), batch_size=10)
    assert dl.num_batches == 2
    dl2 = Dataloader(np.arange(25), batch_size=10, drop_last=False)
    assert dl2.num_batches == 3


def test_dataloader_dp_slicing():
    data = np.arange(100)
    shards = [Dataloader(data, 10, dp_rank=r, dp_nrank=4).data
              for r in range(4)]
    assert all(s.size == 25 for s in shards)
    np.testing.assert_array_equal(np.concatenate(shards), data)


def test_dataloader_prefetch_thread():
    dl = Dataloader(np.arange(40), batch_size=10, shuffle=True, seed=1)
    seen = [dl.next_batch() for _ in range(8)]  # wraps epochs
    assert all(b.shape == (10,) for b in seen)
    dl.stop()


def test_dataloader_op_feeds_executor():
    rng = np.random.default_rng(0)
    X = rng.standard_normal((64, 8)).astype(np.float32)
    xdl = Dataloader(X, batch_size=16, shuffle=False)
    x = DataloaderOp(xdl)
    loss = ht.reduce_mean_op(x * x)
    ex = ht.Executor({"default": [loss]}, training=False)
    vals = [float(ex.run(convert_to_numpy_ret_vals=True)[0])
            for _ in range(4)]
    expect = [float(np.mean(X[i * 16:(i + 1) * 16] ** 2)) for i in range(4)]
    np.testing.assert_allclose(vals, expect, rtol=1e-5)
    xdl.stop()


# ---------------- metrics ----------------

def test_accuracy():
    logits = np.array([[0.1, 0.9], [0.8, 0.2], [0.3, 0.7]])
    assert metrics.accuracy(logits, [1, 0, 0]) == pytest.approx(2 / 3)


def test_auc_matches_definition():
    scores = np.array([0.1, 0.4, 0.35, 0.8])
    labels = np.array([0, 0, 1, 1])
    # pairs: (0.35 vs 0.1)=1, (0.35 vs 0.4)=0, (0.8 vs 0.1)=1, (0.8 vs 0.4)=1
    assert metrics.auc(scores, labels) == pytest.approx(0.75)


def test_auc_with_ties():
    scores = np.array([0.5, 0.5, 0.5, 0.5])
    labels = np.array([0, 1, 0, 1])
    assert metrics.auc(scores, labels) == pytest.approx(0.5)


def test_precision_recall_f1():
    p, r, f1 = metrics.precision_recall_f1([1, 1, 0, 1], [1, 0, 0, 1])
    assert p == pytest.approx(2 / 3)
    assert r == pytest.approx(1.0)
    assert f1 == pytest.approx(0.8)


def test_rmse_mae_ndcg():
    assert metrics.rmse([1, 2], [1, 4]) == pytest.approx(np.sqrt(2))
    assert metrics.mae([1, 2], [1, 4]) == pytest.approx(1.0)
    assert metrics.ndcg_at_k([3, 2, 1], [1, 0, 0], k=3) == pytest.approx(1.0)


def test_percentile_matches_numpy_and_validates():
    vals = [5.0, 1.0, 3.0, 2.0, 4.0]
    for q in (0, 50, 95, 99, 100):
        assert metrics.percentile(vals, q) == pytest.approx(
            np.percentile(vals, q))
    assert np.isnan(metrics.percentile([], 50))
    with pytest.raises(ValueError):
        metrics.percentile(vals, 101)


def test_latency_stats_summary():
    s = metrics.latency_stats([0.1, 0.2, 0.3, 0.4], percentiles=(50, 99))
    assert set(s) == {"p50", "p99", "mean", "max", "count"}
    assert s["count"] == 4
    assert s["p50"] == pytest.approx(0.25)
    assert s["mean"] == pytest.approx(0.25)
    assert s["max"] == pytest.approx(0.4)
    # None entries (edge never reached) are dropped, not crashed on
    s2 = metrics.latency_stats([0.1, None, 0.3])
    assert s2["count"] == 2
    empty = metrics.latency_stats([])
    assert empty["count"] == 0 and np.isnan(empty["p50"])


def test_request_latency_summary_keys():
    records = [{"ttft": 0.05, "tpot": 0.01, "queue_wait": 0.02},
               {"ttft": 0.07, "tpot": 0.02, "queue_wait": None}]
    out = metrics.request_latency_summary(records)
    assert set(out) == {"ttft", "tpot", "queue_wait"}
    assert out["ttft"]["count"] == 2
    assert out["queue_wait"]["count"] == 1
    assert out["ttft"]["p99"] == pytest.approx(
        np.percentile([0.05, 0.07], 99))


# ---------------- logger ----------------

def test_logger_jsonl(tmp_path):
    path = str(tmp_path / "log.jsonl")
    lg = ht.HetuLogger(path=path, print_interval=2, printer=None)
    lg.log(loss=1.0)
    lg.log(loss=3.0)   # interval flush: mean 2.0
    lg.close()
    recs = [json.loads(l) for l in open(path)]
    assert recs[0]["loss"] == pytest.approx(2.0)


# ---------------- tokenizer ----------------

def _toy_tokenizer():
    words = ["the", "quick", "brown", "fox", "jump", "##ed", "##s", "over",
             "lazy", "dog", "un", "##want", "##ed", ",", "."]
    return BertTokenizer.from_vocab_list(words, max_len=16)


def test_wordpiece_greedy_longest_match():
    tok = _toy_tokenizer()
    assert tok.tokenize("unwanted") == ["un", "##want", "##ed"]
    assert tok.tokenize("jumps") == ["jump", "##s"]
    assert tok.tokenize("The quick, brown fox.") == \
        ["the", "quick", ",", "brown", "fox", "."]


def test_unknown_word_maps_to_unk():
    tok = _toy_tokenizer()
    assert tok.tokenize("zzz") == ["[UNK]"]


def test_vocab_registry_resolution(tmp_path, monkeypatch):
    """Name→path registry (reference bert_tokenizer.py:11-29, minus the
    download): register_vocab, HETU_VOCAB_DIR scan, per-name defaults."""
    from hetu_tpu.tokenizers import register_vocab, resolve_vocab
    from hetu_tpu.tokenizers.bert_tokenizer import _REGISTRY
    vocab = tmp_path / "bert-base-uncased-vocab.txt"
    vocab.write_text("\n".join(["[PAD]", "[UNK]", "[CLS]", "[SEP]",
                                "[MASK]", "the", "fox"]))
    # 1) a real file path resolves to itself
    assert resolve_vocab(str(vocab)) == str(vocab)
    # 2) an unknown name raises with guidance
    with pytest.raises(FileNotFoundError, match="register_vocab"):
        resolve_vocab("no-such-vocab")
    # 3) HETU_VOCAB_DIR scan picks up <name>-vocab.txt
    monkeypatch.setenv("HETU_VOCAB_DIR", str(tmp_path))
    assert resolve_vocab("bert-base-uncased") == str(vocab)
    tok = BertTokenizer.from_pretrained("bert-base-uncased")
    assert tok.basic.do_lower_case and tok.max_len == 512  # name defaults
    assert tok.tokenize("The fox") == ["the", "fox"]
    # 4) explicit registration wins over the dir scan
    other = tmp_path / "custom.txt"
    other.write_text("[UNK]\na\n")
    monkeypatch.setitem(_REGISTRY, "bert-base-uncased", str(other))
    assert resolve_vocab("bert-base-uncased") == str(other)
    # 5) cased names default to do_lower_case=False
    register_vocab("bert-base-cased", str(vocab))
    try:
        tok_c = BertTokenizer.from_pretrained("bert-base-cased")
        assert not tok_c.basic.do_lower_case
    finally:
        _REGISTRY.pop("bert-base-cased", None)


def test_encode_pair_and_decode():
    tok = _toy_tokenizer()
    ids, types, mask = tok.encode("the quick fox", "lazy dog", max_len=12)
    assert len(ids) == len(types) == len(mask) == 12
    assert tok.inv_vocab[ids[0]] == "[CLS]"
    assert sum(mask) == 3 + 1 + 2 + 2  # cls + 3 toks + sep + 2 toks + sep
    assert types[:5] == [0] * 5
    assert 1 in types
    assert "quick" in tok.decode(ids)


def test_encode_truncates_longest_first():
    tok = _toy_tokenizer()
    ids, _, mask = tok.encode("the quick brown fox over lazy",
                              "dog", max_len=8)
    assert len(ids) == 8 and sum(mask) == 8


def test_dataloader_device_prefetch():
    # device_prefetch=True: the producer thread uploads batches ahead of
    # the consumer, so next_batch() returns device-resident jax arrays
    import jax
    data = np.arange(64, dtype=np.float32).reshape(16, 4)
    dl = Dataloader(data, batch_size=4, device_prefetch=True,
                    dtype=np.float32)
    seen = [dl.next_batch() for _ in range(4)]
    dl.stop()
    assert all(isinstance(b, jax.Array) for b in seen)
    got = np.sort(np.concatenate([np.asarray(b) for b in seen]).ravel())
    np.testing.assert_array_equal(got, np.arange(64, dtype=np.float32))

    # flows through the executor's auto-feed path unchanged
    op = DataloaderOp(Dataloader(data, batch_size=4, device_prefetch=True,
                                 dtype=np.float32))
    w = ht.Variable("dp_w", value=np.ones((4, 1), np.float32))
    loss = ht.reduce_mean_op(ht.matmul_op(op, w))
    ex = ht.Executor({"train": [loss, ht.SGDOptimizer(0.01).minimize(loss)]})
    for _ in range(3):
        out = ex.run("train", convert_to_numpy_ret_vals=True)
        assert np.isfinite(out[0])


# -- multiprocess dataloader (reference dataloader.py:125) -----------------

def _pad_transform(batch):
    return np.concatenate([batch, np.zeros_like(batch)], axis=1)


@pytest.mark.slow
def test_mp_dataloader_matches_thread_engine():
    """Worker processes + shared-memory ring produce byte-identical batch
    sequences to the thread engine, shuffled and not."""
    from hetu_tpu.dataloader import Dataloader

    data = np.arange(20 * 3, dtype=np.float32).reshape(20, 3)
    for shuffle in (False, True):
        dl_t = Dataloader(data, 4, shuffle=shuffle, seed=5)
        dl_p = Dataloader(data, 4, shuffle=shuffle, seed=5, num_workers=2)
        try:
            for _ in range(10):   # crosses an epoch boundary
                np.testing.assert_array_equal(dl_p.next_batch(),
                                              dl_t.next_batch())
        finally:
            dl_p.stop()
            dl_t.stop()


def test_mp_dataloader_transform_and_autofeed():
    """Shape-changing transform runs in the workers; DataloaderOp derives
    the graph shape from the TRANSFORMED batch."""
    import hetu_tpu as ht
    from hetu_tpu.dataloader import Dataloader, dataloader_op

    data = np.arange(16 * 3, dtype=np.float32).reshape(16, 3)
    dl = Dataloader(data, 4, seed=0, transform=_pad_transform,
                    num_workers=2)
    try:
        node = dataloader_op(dl)
        assert node.shape == (4, 6)
        out = ht.mulbyconst_op(node, 2.0)
        ex = ht.Executor([out])
        got = ex.run(feed_dict={}, convert_to_numpy_ret_vals=True)[0]
        np.testing.assert_array_equal(got, _pad_transform(data[:4]) * 2)
    finally:
        dl.stop()


def _backend_probe(batch):
    """1.0 everywhere if this worker process has initialised a jax
    backend, else 0.0."""
    from jax._src import xla_bridge
    return np.full_like(batch, float(xla_bridge.backends_are_initialized()))


def test_mp_dataloader_worker_never_initialises_a_backend():
    """A spawned worker re-imports hetu_tpu to find its entry point.  On a
    TPU host the parent holds the chips, so a worker that initialised a
    backend would fail or hang: importing must stay off the devices."""
    from hetu_tpu.dataloader import Dataloader

    data = np.ones((16, 4), np.float32)
    dl = Dataloader(data, 4, transform=_backend_probe, num_workers=1)
    try:
        dl.start()
        batch = np.asarray(dl.next_batch())
    finally:
        dl.stop()
    assert batch.shape == (4, 4) and not batch.any()


def _augment_and_sign(batch):
    """A python transform (the reference forks worker processes for exactly
    this: a thread runs it behind the GIL), with the pid of the process that
    ran it as a last column."""
    who = np.full((batch.shape[0], 1), os.getpid(), batch.dtype)
    return np.concatenate([batch * 0.5 + 1.0, who], axis=1)


def test_mp_dataloader_runs_the_transform_in_its_worker_processes():
    """VERDICT #8 done-criterion, as far as a CPU run shared with other
    test workers can prove it (it proves counts, nothing about time): the
    process engine runs the python transform in its worker processes, batch
    ``i`` in worker ``i % num_workers``, not behind the parent's GIL, and
    delivers the batches the thread engine delivers, in order."""
    from hetu_tpu.dataloader import Dataloader

    data = np.random.default_rng(0).standard_normal((64, 8))
    n, workers = 24, 4

    def drain(dl):
        dl.start()
        got = [np.asarray(dl.next_batch()) for _ in range(n)]
        return [b[:, :-1] for b in got], [set(b[:, -1].astype(int))
                                          for b in got]

    dl_t = Dataloader(data, 4, seed=1, transform=_augment_and_sign,
                      prefetch=8)
    dl_p = Dataloader(data, 4, seed=1, transform=_augment_and_sign,
                      num_workers=workers, prefetch=8)
    try:
        batches_t, pids_t = drain(dl_t)
        batches_p, pids_p = drain(dl_p)
    finally:
        dl_t.stop()
        dl_p.stop()
    assert all(p == {os.getpid()} for p in pids_t)
    assert all(len(p) == 1 for p in pids_p)         # one worker a batch
    by_batch = [next(iter(p)) for p in pids_p]
    assert len(set(by_batch)) == workers and os.getpid() not in by_batch
    assert all(by_batch[i] == by_batch[i % workers] for i in range(n))
    for want, got in zip(batches_t, batches_p):
        np.testing.assert_array_equal(got, want)
