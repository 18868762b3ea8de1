"""Packed embedding tables + multi-step dispatch (VERDICT r4 item 2).

Reference: src/ops/EmbeddingLookup.cu / IndexedSlices.cu /
OptimizersSparse.cu — the CUDA kernels the packed layout replaces on
TPU (ops/pallas/sparse_densify.py).  On CPU these tests exercise the
jnp fallback paths, which are numerically identical to the Pallas
kernel by contract; the bench measures the kernel on real TPU.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import hetu_tpu as ht
from hetu_tpu.models import WDL
from hetu_tpu.models.ctr import SparseFeatureEmbedding
from hetu_tpu.ops.pallas.sparse_densify import (
    packed_lookup, pack_write, pack_table, unpack_table, pack_factor,
    packed_rows)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def test_pack_factor_and_rows():
    assert pack_factor(16) == 8
    assert pack_factor(128) == 1
    assert pack_factor(100) == 0      # doesn't divide 128
    assert pack_factor(256) == 0
    assert packed_rows(337000, 16) == 42125
    assert packed_rows(337001, 16) == 42126   # tail line


def test_pack_unpack_roundtrip(rng):
    w = rng.standard_normal((1001, 16)).astype(np.float32)
    p = pack_table(w)
    assert p.shape == (packed_rows(1001, 16), 128)
    back = np.asarray(unpack_table(p, 1001, 16))
    np.testing.assert_array_equal(back, w)


def test_packed_lookup_matches_take(rng):
    rows, dim = 640, 16
    w = rng.standard_normal((rows, dim)).astype(np.float32)
    tbl = pack_table(w)
    ids = rng.integers(0, rows, (4, 7)).astype(np.int32)
    out = np.asarray(packed_lookup(tbl, jnp.asarray(ids), dim))
    np.testing.assert_allclose(out, w[ids], rtol=1e-6)


def test_packed_lookup_vjp_matches_take_vjp(rng):
    """Gradient parity incl. duplicate ids and same-pack collisions —
    the cases the sort+cumsum merge and the write-only kernel contract
    exist for."""
    rows, dim = 640, 16
    w = rng.standard_normal((rows, dim)).astype(np.float32)
    tbl = pack_table(w)
    ids = np.concatenate([rng.integers(0, rows, 58),
                          [5, 5, 6, 7, 12, 100]]).astype(np.int32)
    ct = rng.standard_normal((len(ids), dim)).astype(np.float32)

    def ours(t):
        return jnp.sum(packed_lookup(t, jnp.asarray(ids), dim)
                       * jnp.asarray(ct))

    def ref(t):
        return jnp.sum(jnp.take(t, jnp.asarray(ids), axis=0)
                       * jnp.asarray(ct))

    g_ours = np.asarray(jax.grad(ours)(tbl))
    g_ref = np.asarray(jax.grad(ref)(jnp.asarray(w)))
    np.testing.assert_allclose(unpack_table(jnp.asarray(g_ours), rows,
                                            dim), g_ref,
                               rtol=1e-5, atol=1e-6)


def test_packed_lookup_negative_ids_clamp_like_indexed_slices(rng):
    """Padding ids (-1) follow the IndexedSlices convention: forward
    clamps to row 0, backward drops them (ADVICE r5 — unclamped, the
    forward gathered slot q-1 of line 0, an arbitrary row)."""
    rows, dim = 64, 16
    w = rng.standard_normal((rows, dim)).astype(np.float32)
    tbl = pack_table(w)
    ids = np.array([3, -1, 7, -5, 0], np.int32)
    out = np.asarray(packed_lookup(tbl, jnp.asarray(ids), dim))
    ref = w[np.maximum(ids, 0)]
    np.testing.assert_allclose(out, ref, rtol=1e-6)
    # backward: negative ids contribute NO gradient anywhere
    ct = rng.standard_normal((len(ids), dim)).astype(np.float32)
    g = jax.grad(lambda t: jnp.sum(
        packed_lookup(t, jnp.asarray(ids), dim) * jnp.asarray(ct)))(tbl)
    gu = np.asarray(unpack_table(g, rows, dim))
    ref_g = np.zeros_like(w)
    for i, r in zip(ids, ct):
        if i >= 0:
            ref_g[i] += r
    np.testing.assert_allclose(gu, ref_g, rtol=1e-6, atol=1e-7)


def test_pack_write_fallback_semantics(rng):
    p_rows = 40
    ids = np.array([3, 3, 7, -1, 0], np.int32)      # dup + invalid
    lines = rng.standard_normal((5, 128)).astype(np.float32)
    out = np.asarray(pack_write(jnp.asarray(ids), jnp.asarray(lines),
                                p_rows))
    ref = np.zeros((p_rows, 128), np.float32)
    for i, r in zip(ids, lines):
        if i >= 0:
            ref[i] += r
    np.testing.assert_allclose(out, ref, rtol=1e-6)


def _build_wdl(rng, packed, feedv, rows=3000, B=16):
    dense = ht.placeholder_op(f"pe_d{packed}", (B, 13))
    sparse = ht.placeholder_op(f"pe_s{packed}", (B, 26), dtype=np.int32)
    labels = ht.placeholder_op(f"pe_l{packed}", (B,))
    m = WDL(rows, embedding_dim=16, packed_embedding=packed)
    loss = m.loss(dense, sparse, labels)
    ex = ht.Executor({"train": [loss,
                                ht.AdamOptimizer(0.01).minimize(loss)]},
                     seed=5)
    return m, ex, {dense: feedv[0], sparse: feedv[1], labels: feedv[2]}


def test_wdl_packed_matches_unpacked_trajectory(rng):
    rows, B = 3000, 16
    feedv = (rng.standard_normal((B, 13)).astype(np.float32),
             rng.integers(0, rows, (B, 26)).astype(np.int32),
             rng.integers(0, 2, (B,)).astype(np.float32))
    w0 = rng.standard_normal((rows, 16)).astype(np.float32) * 0.01
    m_u, ex_u, feed_u = _build_wdl(rng, False, feedv, rows, B)
    m_p, ex_p, feed_p = _build_wdl(rng, True, feedv, rows, B)
    # clone the MLP params (variable names differ between the builds)
    tbl_u, tbl_p = m_u.emb.table.name, m_p.emb.table.name
    src = {k: np.asarray(v) for k, v in ex_u.params.items() if k != tbl_u}
    for ks, kd in zip(sorted(src),
                      sorted(k for k in ex_p.params if k != tbl_p)):
        ex_p.params[kd] = jnp.asarray(src[ks])
    ex_u.params[tbl_u] = jnp.asarray(w0)
    m_p.emb.load_rows(ex_p.params, w0)
    ls_u = [float(ex_u.run("train", feed_dict=feed_u,
                           convert_to_numpy_ret_vals=True)[0])
            for _ in range(6)]
    ls_p = [float(ex_p.run("train", feed_dict=feed_p,
                           convert_to_numpy_ret_vals=True)[0])
            for _ in range(6)]
    np.testing.assert_allclose(ls_u, ls_p, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(m_u.emb.host_table(ex_u.params),
                               m_p.emb.host_table(ex_p.params),
                               rtol=1e-3, atol=1e-5)


def test_packed_rejects_non_dividing_dim():
    with pytest.raises(ValueError, match="does not pack"):
        SparseFeatureEmbedding(100, 100, 26, packed=True)
    emb = SparseFeatureEmbedding(100, 100, 26, packed="auto")
    assert not emb.packed          # auto falls back to flat storage


def test_run_steps_equals_n_runs(rng):
    rows, B = 2000, 16
    feedv = (rng.standard_normal((B, 13)).astype(np.float32),
             rng.integers(0, rows, (B, 26)).astype(np.int32),
             rng.integers(0, 2, (B,)).astype(np.float32))
    m1, ex1, feed1 = _build_wdl(rng, False, feedv, rows, B)
    m2, ex2, feed2 = _build_wdl(rng, False, feedv, rows, B)
    for ks, kd in zip(sorted(ex1.params), sorted(ex2.params)):
        ex2.params[kd] = jnp.asarray(np.asarray(ex1.params[ks]))
    last = None
    for _ in range(7):
        last = float(ex1.run("train", feed_dict=feed1,
                             convert_to_numpy_ret_vals=True)[0])
    out = ex2.run_steps("train", feed2, 7, convert_to_numpy_ret_vals=True)
    assert abs(last - float(out[0])) <= 1e-6 * max(1.0, abs(last))
    np.testing.assert_allclose(
        np.asarray(ex1.params[m1.emb.table.name]),
        np.asarray(ex2.params[m2.emb.table.name]), rtol=1e-6, atol=1e-8)
    assert ex1._global_step == ex2._global_step == 7


def test_run_steps_guards():
    x = ht.placeholder_op("rs_x", (4, 8))
    w = ht.Variable("rs_w", value=np.ones((8, 2), np.float32))
    loss = ht.reduce_mean_op(ht.reduce_sum_op(ht.matmul_op(x, w), axes=1))
    ex = ht.Executor({"train": [loss,
                                ht.SGDOptimizer(0.1).minimize(loss)]})
    # missing feed
    with pytest.raises(ValueError, match="missing feeds"):
        ex.run_steps("train", {}, 3)
    out = ex.run_steps("train", {x: np.ones((4, 8), np.float32)}, 3,
                       convert_to_numpy_ret_vals=True)
    assert np.isfinite(out[0])
