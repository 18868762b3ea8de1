"""The W&D-over-PS measurement harness of benchmarks/ps_scale_bench.py:
the HET protocol (cache settings, zipf traffic, feed rotation, timing
discipline) in one place."""

from __future__ import annotations

import time

import numpy as np

HET_SETTINGS = dict(policy="lfu", stale_reads=True, push_bound=2)


def build_wdl_ps(rows, dim, batch, fields, optimizer="sgd", lr=0.01,
                 cache_limit=None, name_prefix="wps"):
    """PSEmbedding (HET settings) + WDL graph + Executor.

    Returns (executor, ps_emb, placeholders) with placeholders =
    (dense, sparse, labels)."""
    import hetu_tpu as ht
    from hetu_tpu.models.ctr import WDL
    from hetu_tpu.ps import PSEmbedding

    ps_emb = PSEmbedding(rows, dim, optimizer=optimizer, lr=lr,
                         cache_limit=cache_limit
                         if cache_limit is not None
                         else max(64, rows // 10),
                         **HET_SETTINGS)
    with ht.name_scope():
        dense = ht.placeholder_op(f"{name_prefix}_dense", (batch, 13))
        sparse = ht.placeholder_op(f"{name_prefix}_sparse",
                                   (batch, fields), dtype=np.int32)
        labels = ht.placeholder_op(f"{name_prefix}_labels", (batch,))
        model = WDL(rows, embedding_dim=dim, num_sparse=fields,
                    ps_embedding=ps_emb)
        loss = model.loss(dense, sparse, labels)
        ex = ht.Executor(
            {"train": [loss, ht.AdamOptimizer(lr).minimize(loss)]})
    return ex, ps_emb, (dense, sparse, labels)


def zipf_feeds(rng, rows, batch, fields, placeholders, n_feeds=8):
    """Device-resident dense/labels + host zipf(1.2) sparse ids (the PS
    lookup runs on the host by design)."""
    import jax.numpy as jnp

    dense, sparse, labels = placeholders

    def zipf_ids(shape):
        z = rng.zipf(1.2, size=shape)
        return ((z - 1) % rows).astype(np.int32)

    return [{dense: jnp.asarray(rng.standard_normal((batch, 13)),
                                jnp.float32),
             sparse: zipf_ids((batch, fields)),
             labels: jnp.asarray(rng.integers(0, 2, (batch,)),
                                 jnp.float32)}
            for _ in range(n_feeds)]


def time_steps(ex, feeds, steps, groups=3):
    """Best-of-`groups` mean step time; each group ends by copying the
    loss to the host, which waits for the steps that produced it."""
    import jax

    out = ex.run("train", feed_dict=feeds[0],
                 convert_to_numpy_ret_vals=True)
    assert np.isfinite(out[0])
    best = float("inf")
    for _ in range(groups):
        t0 = time.perf_counter()
        for i in range(steps):
            o = ex.run("train", feed_dict=feeds[(i + 1) % len(feeds)])
        np.asarray(jax.tree_util.tree_leaves(o)[0])
        best = min(best, (time.perf_counter() - t0) / steps)
    return best
