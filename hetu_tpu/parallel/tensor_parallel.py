"""Explicit Megatron-style tensor-parallel blocks (shard_map).

Reference: tools/Hetu-Galvatron/galvatron/core/tensor_parallel/
transformer.py and the vendored megatron/core/tensor_parallel/layers.py —
column/row-parallel linear with hand-placed f/g collectives,
VocabParallelEmbedding (rows split over tp ranks, out-of-range ids masked
to 0 then all-reduced) and vocab_parallel_cross_entropy (per-rank partial
logits reduced with max/sum psums so the full [T, V] logits never
materialize on one device).

Most TP in this framework is GSPMD-driven (annotate shardings, let XLA
insert collectives — parallel/strategies.py MegatronLM).  This module is
the explicit-control path for the two places where the hand-written
pattern beats compiler propagation:

  * the LM head + cross-entropy, where keeping logits vocab-sharded
    through the reduction is a memory guarantee, not a heuristic;
  * benchmark kernels where collective placement must be exact.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


def vocab_range(vocab_size, axis):
    """This shard's [start, end) slice of the vocabulary."""
    size = lax.axis_size(axis)
    per = vocab_size // size
    start = lax.axis_index(axis) * per
    return start, start + per


def vocab_parallel_embedding(local_table, ids, vocab_size, axis="tp"):
    """Lookup from a vocab-sharded [V/tp, H] table (inside shard_map).

    Out-of-range ids hit a zero row locally; the psum sums the one shard
    that owns each id (reference VocabParallelEmbedding.forward: mask,
    local lookup, all-reduce).
    """
    start, end = vocab_range(vocab_size, axis)
    mine = (ids >= start) & (ids < end)
    local = jnp.where(mine, ids - start, 0)
    rows = jnp.take(local_table, local, axis=0)
    rows = jnp.where(mine[..., None], rows, 0.0)
    return lax.psum(rows, axis)


def vocab_parallel_cross_entropy(local_logits, labels, vocab_size,
                                 axis="tp", ignored_index=-1):
    """Sparse softmax-CE over vocab-sharded logits (inside shard_map).

    local_logits: [T, V/tp] this shard's slice; labels: [T] global ids.
    Never materializes [T, V]: max and sum-exp reduce with psums, and the
    correct-label logit comes from the owning shard only (reference
    megatron _VocabParallelCrossEntropy.forward).
    """
    x = local_logits.astype(jnp.float32)
    # the max is a numerical-stability shift whose gradient cancels in
    # (m + log z) - picked; stop_gradient also sidesteps pmax's missing
    # differentiation rule
    # stop_gradient BEFORE pmax: with a symbolically-zero tangent the
    # missing pmax differentiation rule is never consulted
    m = lax.pmax(jnp.max(lax.stop_gradient(x), axis=-1), axis)  # [T]
    z = lax.psum(jnp.sum(jnp.exp(x - m[:, None]), axis=-1), axis)
    start, end = vocab_range(vocab_size, axis)
    lab = jnp.maximum(labels.astype(jnp.int32), 0)
    mine = (lab >= start) & (lab < end)
    local = jnp.where(mine, lab - start, 0)
    picked = jnp.take_along_axis(x, local[:, None], axis=-1)[:, 0]
    picked = lax.psum(jnp.where(mine, picked, 0.0), axis)
    loss = (m + jnp.log(z)) - picked
    return jnp.where(labels == ignored_index, 0.0, loss)


def column_parallel_linear(x, w_local, b_local=None, axis="tp",
                           gather_output=False):
    """y_local = x @ w_local (+ b_local); w sharded on the OUTPUT dim.
    The identity-forward/psum-backward 'f' function is what autodiff of
    the replicated input gives for free under shard_map."""
    y = x @ w_local
    if b_local is not None:
        y = y + b_local
    if gather_output:
        y = lax.all_gather(y, axis, axis=y.ndim - 1, tiled=True)
    return y


def row_parallel_linear(x_local, w_local, b=None, axis="tp"):
    """y = psum(x_local @ w_local) (+ b); w sharded on the INPUT dim —
    the 'g' all-reduce the reference places after row-parallel matmuls."""
    y = lax.psum(x_local @ w_local, axis)
    if b is not None:
        y = y + b
    return y


def shard_vocab_table(mesh, table, axis="tp"):
    """[V, H] -> placed vocab-sharded over ``axis``."""
    from jax.sharding import NamedSharding
    return jax.device_put(table, NamedSharding(mesh, P(axis, None)))


def tp_lm_head_loss(mesh, hidden, table, labels, axis="tp",
                    ignored_index=-1, dp_axis=None):
    """Tied-head LM loss with the full vocab-parallel treatment.

    hidden: [T, H] (replicated over tp; optionally dp-sharded on dim 0),
    table: [V, H] vocab-sharded over ``axis``; labels: [T].
    Computes mean CE without ever materializing [T, V] logits on one
    device.  This is the memory contract MegatronLM's sharded LM head
    exists for (reference core/tensor_parallel/transformer.py LM head +
    vocab CE).
    """
    V = table.shape[0]
    in_hidden = P(dp_axis, None) if dp_axis else P()
    in_labels = P(dp_axis) if dp_axis else P()

    def body(h, tab, lab):
        logits_local = h @ tab.T                      # [T, V/tp]
        ce = vocab_parallel_cross_entropy(logits_local, lab, V, axis,
                                          ignored_index)
        n = lax.psum(jnp.sum((lab != ignored_index).astype(jnp.float32)),
                     dp_axis) if dp_axis else \
            jnp.sum((lab != ignored_index).astype(jnp.float32))
        s = lax.psum(jnp.sum(ce), dp_axis) if dp_axis else jnp.sum(ce)
        return s / jnp.maximum(n, 1.0)

    f = shard_map(body, mesh=mesh,
                  in_specs=(in_hidden, P(axis, None), in_labels),
                  out_specs=P())
    return f(hidden, table, labels)
