"""Embedding / sparse ops.

Reference kernels: src/ops/EmbeddingLookup.cu, SparseEmbeddingLookup.cu,
IndexedSlices.cu, ReduceIndexedSlice.cu (unique + segment-sum of duplicate
ids), UniqueIndices.cu, CuSparseCsrmm.cu, plus gpu_ops/EmbeddingLookUp.py's
IndexedSlices gradient path.

TPU design: lookup is a gather (XLA lowers to efficient dynamic-gather on
HBM); the gradient is gather's transpose — a scatter-add — which XLA keeps
sparse w.r.t. compute.  For optimizer-visible sparse updates (the reference's
IndexedSlices → sparse optimizer kernels), `reduce_indexedslices` implements
the unique+segment-sum dedup with a fixed-size unique buffer (static shapes
for jit).  PS-backed tables (ps/ subsystem) bypass the graph entirely, like
the reference's CacheSparseTable path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import simple_op


def _embedding_lookup(table, ids):
    return jnp.take(table, ids.astype(jnp.int32), axis=0)


embedding_lookup_op = simple_op(_embedding_lookup, "embedding_lookup")
sparse_embedding_lookup_op = embedding_lookup_op


def reduce_indexedslices(ids, values, num_unique):
    """Dedup ids by segment-summing values of equal ids.

    Returns (unique_ids_padded, summed_values) with static size
    ``num_unique`` (pad id = -1).  Mirrors ReduceIndexedSlice.cu (cub
    sort+unique) under XLA static-shape constraints.
    """
    ids = ids.reshape(-1).astype(jnp.int32)
    flat_vals = values.reshape(ids.shape[0], -1)
    uniq, inv = jnp.unique(ids, return_inverse=True, size=num_unique,
                           fill_value=-1)
    summed = jax.ops.segment_sum(flat_vals, inv.reshape(-1),
                                 num_segments=num_unique)
    return uniq, summed.reshape((num_unique,) + values.shape[len(ids.shape):])


def _scatter_add(table, ids, updates):
    ids = ids.reshape(-1).astype(jnp.int32)
    updates = updates.reshape(ids.shape[0], -1).astype(table.dtype)
    return table.at[ids].add(updates.reshape((ids.shape[0],)
                                             + table.shape[1:]))


scatter_add_op = simple_op(_scatter_add, "scatter_add")


def _csrmm(indptr, indices, data, dense, num_rows=None):
    """CSR × dense (reference CuSparseCsrmm.cu).  Represented via COO
    segment-sum; for TPU-friendly batched spmm use ops in models/gnn."""
    row = jnp.repeat(jnp.arange(num_rows), jnp.diff(indptr),
                     total_repeat_length=indices.shape[0])
    gathered = dense[indices.astype(jnp.int32)] * data[:, None]
    return jax.ops.segment_sum(gathered, row, num_segments=num_rows)


class IndexedSlices:
    """Sparse gradient value (indices + values + dense_shape).

    API parity with reference python/hetu/ndarray.py:680; used by the PS path
    and sparse optimizers.  ``deduplicate`` merges duplicate indices.
    """

    def __init__(self, indices, values, dense_shape):
        self.indices = indices
        self.values = values
        self.dense_shape = tuple(dense_shape)

    def deduplicate(self, num_unique=None):
        n = num_unique or int(self.indices.size)
        ids, vals = reduce_indexedslices(self.indices, self.values, n)
        return IndexedSlices(ids, vals, self.dense_shape)

    def to_dense(self):
        table = jnp.zeros(self.dense_shape, dtype=self.values.dtype)
        mask = (self.indices >= 0).reshape(-1, 1)
        vals = jnp.where(mask, self.values.reshape(mask.shape[0], -1), 0.0)
        safe_ids = jnp.maximum(self.indices.reshape(-1), 0)
        return table.at[safe_ids].add(
            vals.reshape((-1,) + self.dense_shape[1:]))


from ..graph.node import Op as _Op  # noqa: E402


class _PackedLookupOp(_Op):
    """Lookup from a PACKED [p_rows, 128] embedding table (see
    ops/pallas/sparse_densify.py — the TPU-native storage for narrow
    embedding dims whose vjp needs no XLA scatter).  The Pallas write
    kernel engages only off-mesh on TPU; the jnp fallback is
    numerically identical (CPU tests, sharded programs), and
    ``dispatch.take`` records which of the two ran, under ``packed_lookup``
    (pallas/dispatch.py)."""

    def _compute(self, input_vals, ctx):
        from .pallas.sparse_densify import packed_lookup
        table, ids = input_vals
        return packed_lookup(table, ids, self.attrs["dim"],
                             None if ctx is None else ctx.mesh)


def packed_embedding_lookup_op(table, ids, dim, name=None):
    """Graph op: rows [..., dim] from a packed [p_rows, 128] table."""
    from .base import _peek_id
    return _PackedLookupOp(table, ids,
                           name=name or f"packed_lookup_{_peek_id()}",
                           dim=dim)
