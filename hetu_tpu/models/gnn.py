"""GNN layers + 1.5-D distributed GCN.

Reference: python/hetu/gpu_ops/DistGCN_15d.py:19-155 — GCN propagation
Z = A @ (H W) with the adjacency row-partitioned across P/c process rows,
features replicated c ways; per-stage NCCL broadcasts stream the feature
blocks through col groups and a row-group allreduce combines the partial
products (CuSparse_Csrmm per stage).

TPU redesign: the broadcast-round pipeline IS a sharding. On a
(block=P/c, rep=c) mesh, the same computation is a single matmul with
  A sharded (rows -> 'block', cols -> 'rep'),
  HW row-sharded over 'rep' (replicated over 'block'),
  partial products psum'd over 'rep',
and XLA lowers the data movement to the minimal ICI collectives — no
hand-scheduled stages.  The adjacency is kept as dense normalized blocks
(MXU-friendly; GCN adjacencies at TPU-worthwhile sizes are usually
blocked/sampled anyway); the single-device path offers a segment-sum SpMM
for COO graphs (gcn_conv).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..graph.node import Op
from ..ops.base import simple_op


# -- single-device sparse GCN conv (COO segment-sum) ----------------------

def _gcn_conv(h, w, src=None, dst=None, edge_weight=None, num_nodes=None):
    """Z[dst] += a(src,dst) * (H W)[src] — SpMM as gather + segment-sum
    (reference CuSparseCsrmm.cu path through DistGCN's need_W branch)."""
    hw = jnp.matmul(h, w, preferred_element_type=jnp.float32)
    n = num_nodes or h.shape[0]
    gathered = hw[jnp.asarray(src, jnp.int32)]
    if edge_weight is not None:
        gathered = gathered * edge_weight[:, None]
    return jax.ops.segment_sum(gathered, jnp.asarray(dst, jnp.int32),
                               num_segments=n).astype(h.dtype)


gcn_conv_op = simple_op(_gcn_conv, "gcn_conv")


def normalized_adjacency(src, dst, num_nodes, add_self_loops=True):
    """Dense sym-normalized adjacency D^-1/2 (A+I) D^-1/2 (GCN propagation
    matrix), numpy-side model prep."""
    a = np.zeros((num_nodes, num_nodes), np.float32)
    a[dst, src] = 1.0
    a = np.maximum(a, a.T)   # GCN treats the graph as undirected
    if add_self_loops:
        a[np.arange(num_nodes), np.arange(num_nodes)] = 1.0
    deg = a.sum(1)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    return a * dinv[:, None] * dinv[None, :]


# -- 1.5-D distributed propagation ----------------------------------------

class DistGCN15D:
    """Z = A @ (H @ W) on a (block, rep) mesh.

    * adjacency `a` enters sharded (P('block', 'rep')): each device holds an
      (N/block, N/rep) tile — the reference's row-partition with the stage
      loop's column range materialized as the 'rep' shard.
    * features `h` enter row-sharded over 'rep' (the c-fold replication of
      the reference becomes: each rep rank holds the feature rows its
      column-stages need, replicated across 'block').
    * the local tile matmul runs on the MXU; `psum` over 'rep' plays the
      row-group allreduce (DistGCN_15d.py:66-68).
    """

    def __init__(self, mesh, block_axis="block", rep_axis="rep"):
        self.mesh = mesh
        self.block_axis = block_axis
        self.rep_axis = rep_axis
        self._fn = jax.jit(self.propagate_fn())   # compile once

    def propagate_fn(self):
        ba, ra = self.block_axis, self.rep_axis

        def body(a_tile, h_rows, w):
            hw = jnp.matmul(h_rows, w, preferred_element_type=jnp.float32)
            partial = jnp.matmul(a_tile, hw,
                                 preferred_element_type=jnp.float32)
            return lax.psum(partial, ra)

        return shard_map(
            body, mesh=self.mesh,
            in_specs=(P(ba, ra), P(ra, None), P()),
            out_specs=P(ba, None))

    def __call__(self, a, h, w, activation=None):
        out = self._fn(a, h, w)
        if activation is not None:
            out = activation(out)
        return out


class GCNLayerOp(Op):
    """Graph-node wrapper of gcn_conv for the define-then-run API."""

    def __init__(self, h, w, src, dst, edge_weight=None, num_nodes=None,
                 name=None):
        inputs = [h, w, src, dst]
        if edge_weight is not None:
            inputs.append(edge_weight)
        super().__init__(*inputs, name=name)
        self.num_nodes = num_nodes
        self.has_ew = edge_weight is not None

    def _compute(self, input_vals, ctx):
        h, w, src, dst = input_vals[:4]
        ew = input_vals[4] if self.has_ew else None
        return _gcn_conv(h, w, src=src, dst=dst, edge_weight=ew,
                         num_nodes=self.num_nodes)


def distgcn_15d_op(h, w, src, dst, edge_weight=None, num_nodes=None,
                   name=None):
    return GCNLayerOp(h, w, src, dst, edge_weight=edge_weight,
                      num_nodes=num_nodes, name=name)
