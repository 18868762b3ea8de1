"""Collective communication over mesh axes.

Reference: /root/reference/src/communication/mpi_nccl_communication.cu — MPI-
bootstrapped flat + grouped NCCL communicators with allreduce/reduce/bcast/
allgather/reducescatter/p2p/alltoall and a hierarchical (node-leader)
alltoall; Python face in python/hetu/communicator/mpi_nccl_comm.py.

TPU equivalents are the XLA collectives over ICI/DCN, invoked inside
`shard_map` over named mesh axes.  A "grouped communicator" is just a mesh
sub-axis: every call below takes `axis_name` (or a tuple for multi-axis
groups), which is the TPU analogue of `ncclGroupInit` sub-communicators
(mpi_nccl_comm.py:157).  The hierarchical a2a (H_A2A, node-leader staging)
becomes a two-stage all_to_all over ('dcn', 'ici') axes: stage within the
fast axis first, then across the slow axis — same bandwidth shape as the
reference's gather→a2a→scatter without explicit leader ranks.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


# -- primitive wrappers (valid inside shard_map/pmapped code) --------------

def all_reduce(x, axis_name, op="sum"):
    """reference: _ncclAllReduce (mpi_nccl_communication.cu:137)."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(op)


def all_gather(x, axis_name, axis=0, tiled=True):
    """reference: dlarrayAllGather (mpi_nccl_comm.py:307)."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, axis=0):
    """reference: dlarrayReduceScatter (mpi_nccl_comm.py:311)."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def all_to_all(x, axis_name, split_axis, concat_axis):
    """reference: dlarrayAllToAll (mpi_nccl_comm.py:330) — NCCL send/recv
    loop; on TPU a single ICI all_to_all."""
    return lax.all_to_all(x, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def hierarchical_all_to_all(x, outer_axis, inner_axis, outer_size,
                            inner_size, axis=0):
    """Two-level a2a (reference HAllToAll: node-leader gather → inter-node
    a2a → scatter, mpi_nccl_comm.py:334 + H_A2A_LayoutTransform.cu).

    Drop-in equivalent of a flat ``all_to_all`` over the combined
    (outer, inner) axis with flat rank = o * inner_size + i, but with the
    traffic staged: first within the fast inner axis (ICI), then across the
    slow outer axis (DCN).  Local stride-permutes between stages keep the
    piece→destination mapping identical to the flat collective (verified
    against it in tests/test_parallel.py).
    """
    No, Ni = outer_size, inner_size
    x = jnp.moveaxis(x, axis, 0)
    S = x.shape[0]
    assert S % (No * Ni) == 0, f"axis size {S} not divisible by {No * Ni}"
    piece = S // (No * Ni)
    rest = x.shape[1:]
    # group pieces by inner destination: [No_dest, Ni_dest, p] -> [Ni_dest,...]
    x = x.reshape(No, Ni, piece, *rest)
    x = jnp.swapaxes(x, 0, 1)
    # stage 1 (ICI): route by inner destination
    x = lax.all_to_all(x, inner_axis, split_axis=0, concat_axis=0,
                       tiled=True)
    # now [Ni_src, No_dest, p]; route by outer destination
    x = jnp.swapaxes(x, 0, 1)
    x = lax.all_to_all(x, outer_axis, split_axis=0, concat_axis=0,
                       tiled=True)
    # now [No_src, Ni_src, p] == flat source-rank order
    x = x.reshape(S, *rest)
    return jnp.moveaxis(x, 0, axis)


def broadcast(x, axis_name, src=0):
    """reference: dlarrayBroadcast (mpi_nccl_comm.py:303)."""
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == src, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def reduce_(x, axis_name, dst=0, op="sum"):
    """reference: dlarrayNcclReduce (mpi_nccl_comm.py:299).  SPMD has no
    single-owner tensors; the reduced value lands on every shard but callers
    may mask to dst for parity semantics."""
    return all_reduce(x, axis_name, op)


def ppermute(x, axis_name, perm):
    """Point-to-point ring/permute (reference PipelineSend/Recv pairs,
    gpu_ops/PipelineSend.py — batched NCCL p2p)."""
    return lax.ppermute(x, axis_name, perm)


def send_next(x, axis_name, n):
    """Rotate +1 along a ring of size n (pipeline stage handoff)."""
    return lax.ppermute(x, axis_name, [(i, (i + 1) % n) for i in range(n)])


def send_prev(x, axis_name, n):
    return lax.ppermute(x, axis_name, [(i, (i - 1) % n) for i in range(n)])


def axis_index(axis_name):
    return lax.axis_index(axis_name)


def varying(x, axes):
    """Mark an array as device-varying over mesh axes (scan carries that
    start replicated but become shard-dependent need this under shard_map's
    varying-manual-axes checks)."""
    return lax.pcast(x, tuple(axes), to="varying")


def _quantize(x, scale, qmax, itype):
    return jnp.clip(jnp.round(x / scale * qmax), -qmax, qmax).astype(itype)


def _quantized_psum(x, axis_name, bits):
    """Returns (reduced, sent_local) — sent_local is the value this
    replica is accountable for delivering (stage-1 payload minus its own
    shard's stage-2 re-quantization error), so ``x_c - sent_local`` in
    error_feedback carries EXACTLY the undelivered mass.

    All quantize/dequantize/accumulate arithmetic runs in f32 (bf16
    inputs would cap bits=16 at bf16's 8 mantissa bits); only the final
    outputs cast back to x.dtype."""
    assert bits in (8, 16)
    qmax = float(2 ** (bits - 1) - 1)
    itype = jnp.int8 if bits == 8 else jnp.int16
    n = jax.lax.psum(1, axis_name)
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.shape[0]) % n
    flat_p = jnp.pad(flat, (0, pad))
    # stage 1: shared scale; int payload rides all_to_all (pure data
    # movement — the WIRE carries int8/int16, unlike psum(int32) whose
    # accumulation dtype is also its wire dtype)
    scale1 = jnp.maximum(
        jax.lax.pmax(jnp.max(jnp.abs(flat_p)), axis_name), 1e-30)
    q1 = _quantize(flat_p, scale1, qmax, itype)
    shards = jax.lax.all_to_all(q1.reshape(n, -1), axis_name,
                                split_axis=0, concat_axis=0, tiled=True)
    # local accumulation in int32 (max |sum| = n * qmax, no overflow)
    local = shards.astype(jnp.int32).sum(0)
    r = local.astype(jnp.float32) * (scale1 / qmax)
    # stage 2: re-quantize the reduced shard for the gather leg
    scale2 = jnp.maximum(jax.lax.pmax(jnp.max(jnp.abs(r)), axis_name),
                         1e-30)
    q2 = _quantize(r, scale2, qmax, itype)
    g = jax.lax.all_gather(q2, axis_name, tiled=True)
    out_flat = g.astype(jnp.float32) * (scale2 / qmax)
    out = out_flat[:flat.shape[0]].reshape(x.shape).astype(x.dtype)
    sent1 = q1.astype(jnp.float32) * (scale1 / qmax)
    # my shard's stage-2 error is MINE to re-send next step: r_i equals
    # the exact sum of everyone's dequantized stage-1 payloads at shard
    # i, so charging err2_i to replica i's ledger makes
    # sum_replicas(sent) == what was actually delivered, elementwise
    chunk = r.shape[0]
    err2 = r - q2.astype(jnp.float32) * (scale2 / qmax)
    off = (jax.lax.axis_index(axis_name) * chunk,)
    sent_eff = jax.lax.dynamic_update_slice(
        sent1, jax.lax.dynamic_slice(sent1, off, (chunk,)) - err2, off)
    sent = sent_eff[:flat.shape[0]].reshape(x.shape).astype(x.dtype)
    return out, sent


def quantized_psum(x, axis_name, bits=8):
    """Bandwidth-reduced gradient all-reduce (EQuARX-style,
    arXiv:2506.17615 — retrieved technique; beyond the reference's comm
    backend): int8/int16 payloads on BOTH legs (all_to_all + all_gather,
    each (n-1)/n·B bytes of int vs the fp32 ring psum — ~4× less wire
    traffic at bits=8), int32 local accumulation, two pmax'd shared
    scales.  LOSSY — pair with ``error_feedback`` so quantization error
    carries into the next step.  Opt-in; nothing routes through this by
    default."""
    out, _ = _quantized_psum(x, axis_name, bits)
    return out


def error_feedback(x, residual, axis_name, bits=8):
    """quantized_psum with residual carry: returns (reduced, new_residual).
    The caller threads ``residual`` (zeros-like at step 0) through its
    step state; ``x + residual`` is quantized, and the part this replica
    failed to transmit (stage-1 error) becomes the next residual."""
    xc = x + residual
    reduced, sent = _quantized_psum(xc, axis_name, bits)
    return reduced, xc - sent


# -- host-level helpers ----------------------------------------------------

def sharded_fn(mesh, in_specs, out_specs, fn):
    """shard_map wrapper with hetu-style spec objects allowed."""
    from .mesh import DistState

    def norm(s):
        if isinstance(s, DistState):
            return s.to_pspec()
        return s

    return shard_map(fn, mesh=mesh,
                     in_specs=jax.tree_util.tree_map(
                         norm, in_specs,
                         is_leaf=lambda x: isinstance(x, (P, DistState))),
                     out_specs=jax.tree_util.tree_map(
                         norm, out_specs,
                         is_leaf=lambda x: isinstance(x, (P, DistState))))
