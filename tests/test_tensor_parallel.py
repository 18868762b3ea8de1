"""Explicit Megatron-TP blocks + vocab-parallel embedding/LM-head tests.

Reference behaviors matched: megatron VocabParallelEmbedding (mask +
local lookup + all-reduce), _VocabParallelCrossEntropy (max/sum psums,
owner-shard label pick), column/row-parallel linear f/g collectives
(core/tensor_parallel/layers.py, transformer.py)."""

import warnings

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import hetu_tpu as ht
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.parallel import make_mesh, MegatronLM
from hetu_tpu.parallel.tensor_parallel import (
    vocab_parallel_embedding, vocab_parallel_cross_entropy,
    column_parallel_linear, row_parallel_linear, shard_vocab_table,
    tp_lm_head_loss)
import pytest

# heavyweight parity suite: deselect with -m 'not slow' (VERDICT r3 item 10)
pytestmark = pytest.mark.slow

def _tp_mesh(n=4):
    return Mesh(np.array(jax.devices()[:n]), ("tp",))


def test_vocab_parallel_embedding_matches_dense(rng):
    mesh = _tp_mesh(4)
    V, H, T = 64, 8, 12
    table = jnp.asarray(rng.standard_normal((V, H)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)

    f = shard_map(
        lambda tab, i: vocab_parallel_embedding(tab, i, V, "tp"),
        mesh=mesh, in_specs=(P("tp", None), P()), out_specs=P())
    out = f(table, ids)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.take(table, ids, axis=0)),
                               rtol=1e-6)


def test_vocab_parallel_cross_entropy_matches_full(rng):
    mesh = _tp_mesh(4)
    V, T = 64, 16
    logits = jnp.asarray(rng.standard_normal((T, V)) * 3, jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)
    labels = labels.at[3].set(-1)   # ignored position

    f = shard_map(
        lambda lg, lab: vocab_parallel_cross_entropy(lg, lab, V, "tp"),
        mesh=mesh, in_specs=(P(None, "tp"), P()), out_specs=P())
    out = f(logits, labels)

    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    want = jnp.where(labels == -1, 0.0, lse - picked)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_column_then_row_parallel_matches_dense(rng):
    mesh = _tp_mesh(4)
    H, F, T = 8, 16, 6
    x = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    w1 = jnp.asarray(rng.standard_normal((H, F)), jnp.float32)
    b1 = jnp.asarray(rng.standard_normal((F,)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((F, H)), jnp.float32)
    b2 = jnp.asarray(rng.standard_normal((H,)), jnp.float32)

    def body(x, w1, b1, w2, b2):
        h = column_parallel_linear(x, w1, b1, "tp")
        h = jax.nn.gelu(h)
        return row_parallel_linear(h, w2, b2, "tp")

    f = shard_map(body, mesh=mesh,
                  in_specs=(P(), P(None, "tp"), P("tp"), P("tp", None),
                            P()),
                  out_specs=P())
    out = f(x, w1, b1, w2, b2)
    want = jax.nn.gelu(x @ w1 + b1) @ w2 + b2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_tp_lm_head_loss_matches_replicated(rng):
    mesh = make_mesh({"dp": 2, "tp": 4})
    V, H, T = 96, 8, 24
    table = jnp.asarray(rng.standard_normal((V, H)), jnp.float32)
    hidden = jnp.asarray(rng.standard_normal((T, H)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, V, (T,)), jnp.int32)
    labels = labels.at[0].set(-1)
    table_sharded = shard_vocab_table(mesh, table)

    loss = tp_lm_head_loss(mesh, hidden, table_sharded, labels,
                           dp_axis="dp")
    logits = hidden @ table.T
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    ce = jnp.where(labels == -1, 0.0, lse - picked)
    want = jnp.sum(ce) / jnp.sum(labels != -1)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)

    # grads flow to the sharded table
    def full_loss(t):
        lg = hidden @ t.T
        lse = jax.scipy.special.logsumexp(lg, -1)
        pick = jnp.take_along_axis(
            lg, jnp.maximum(labels, 0)[:, None], -1)[:, 0]
        ce = jnp.where(labels == -1, 0.0, lse - pick)
        return jnp.sum(ce) / jnp.sum(labels != -1)

    g = jax.grad(lambda t: tp_lm_head_loss(mesh, hidden, t, labels,
                                           dp_axis="dp"))(table_sharded)
    gfull = jax.grad(full_loss)(table)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gfull),
                               rtol=2e-4, atol=1e-6)


def test_megatron_strategy_shards_embedding_table(rng):
    """VERDICT #5: GPT trains under tp with the embedding/LM-head table
    vocab-sharded (per-device param bytes drop by tp), numerics parity
    vs the replicated run."""
    B, S = 4, 16
    c = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                  num_heads=4, seq_len=S, dropout_prob=0.0)
    ids = ht.placeholder_op("vp_ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op("vp_labels", (B, S), dtype=np.int32)
    model = GPTLMHeadModel(c, name="vpgpt")
    loss = model.loss(ids, labels)
    iv = rng.integers(0, c.vocab_size, (B, S))
    feed = {ids: iv, labels: np.roll(iv, -1, 1)}

    opt_r = ht.AdamOptimizer(1e-3)
    ex_ref = ht.Executor({"train": [loss, opt_r.minimize(loss)]}, seed=5)
    l_ref = [ex_ref.run("train", feed_dict=feed,
                        convert_to_numpy_ret_vals=True)[0]
             for _ in range(3)]

    opt_t = ht.AdamOptimizer(1e-3)
    strat = MegatronLM(dp=2, tp=4)
    ex_tp = ht.Executor({"train": [loss, opt_t.minimize(loss)]}, seed=5,
                        dist_strategy=strat)
    # the table is annotated vocab-parallel and actually placed sharded
    wte = ex_tp.params["vpgpt_wte_table"]
    assert wte.sharding.spec[0] == "tp", wte.sharding
    per_dev_rows = wte.sharding.shard_shape(wte.shape)[0]
    assert per_dev_rows == c.vocab_size // 4
    assert strat.matched_variables > 0

    l_tp = [ex_tp.run("train", feed_dict=feed,
                      convert_to_numpy_ret_vals=True)[0]
            for _ in range(3)]
    np.testing.assert_allclose(l_tp, l_ref, rtol=2e-4)


def test_megatron_strategy_warns_on_zero_matches():
    x = ht.placeholder_op("nm_x", (8, 8))
    w = ht.VariableOp("plain_w", (8, 8), ht.init.xavier_uniform())
    loss = ht.reduce_mean_op(ht.matmul_op(x, w))
    strat = MegatronLM(dp=2, tp=4)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        strat.annotate([loss])
    assert any("no variable matched" in str(w_.message) for w_ in rec)
