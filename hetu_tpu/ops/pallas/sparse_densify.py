"""Packed embedding tables: scatter-free gradients via a Pallas
row-write DMA kernel.

Reference: src/ops/EmbeddingLookup.cu + IndexedSlices.cu /
OptimizersSparse.cu — the reference's CUDA kernels for embedding
lookup and sparse-gradient application.  On TPU the dense-Adam path
over a CTR embedding table is bottlenecked by XLA's scatter lowering
for the gather-transpose: latency-bound serialized row updates at small
tables (194 us for the W&D bench's 3,328 rows of a 337k x 16 table —
59% of the step) that degrade into FULL-TABLE passes at larger ones
(~390 us/table at 2M rows), and the two-output fusion it anchors splits
the Adam update into two passes over the table.

TPU-native redesign — pack the table to the 128-lane quantum:

- storage is ``[num_rows/q, 128]`` with ``q = 128/dim`` logical rows per
  lane-line (dim 16 -> 8 rows/line).  Elementwise optimizer math is
  shape-agnostic, so Adam/SGD run unchanged — and on the packed shape
  XLA emits the single-pass multi-output fusion (164 us vs 294 us at
  W&D shapes);
- ``packed_lookup`` gathers whole lane-lines and extracts the target
  row by a fused masked select-sum (no strided 16-byte accesses, and a
  non-finite co-resident row cannot leak through a 0·NaN product —
  serving's watchdog containment depends on that);
- its vjp positions each gradient row inside its lane-line, merges
  duplicates with a sort + cumsum difference (NOT segment_sum, whose
  XLA lowering is the very scatter being replaced), and DMAs each
  unique line into a zero-initialized packed gradient with the
  ``pack_write`` kernel (64 write-DMAs in flight: 44 us vs 194 us
  measured, and table-size-independent).

Unique pack ids make the write-only kernel race-free (no two in-flight
DMAs share a target line); invalid lanes (padding / merged duplicates)
are skipped under ``pl.when``.

pallas_call does not partition under GSPMD, so callers inside a
sharded program have two options: hand the ``mesh`` they see (the jnp
form is numerically identical; ``dispatch.take`` records the reason), or
call :func:`sharded_packed_lookup`, which wraps the lookup in the
``jax.shard_map`` — the id batch splits over a mesh axis,
the packed table rides replicated into every shard, and each device
runs the SAME kernel on its local slice.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import dispatch

_BLK = 64      # row-writes in flight per grid step


def pack_factor(dim):
    """Rows per 128-lane line, or 0 when the dim doesn't pack."""
    if dim <= 128 and 128 % dim == 0:
        return 128 // dim
    return 0


def packed_rows(num_rows, dim):
    """Lines needed to hold ``num_rows`` logical rows (last line may be
    partially used; lookups never see the padding)."""
    q = pack_factor(dim)
    return (num_rows + q - 1) // q


def _unsupported(dtype):
    """Why the row-write kernel refuses its operands, or None (the DMA form
    needs Mosaic and has no interpret-mode twin: ``dispatch.take`` says
    ``platform:<name>`` off a TPU)."""
    if dtype not in (jnp.float32, np.float32):
        return f"dtype:{jnp.dtype(dtype).name}"
    return None


def _make_kernel():
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(idx_ref, upd_vmem, zeros_hbm, out_hbm, sems):
        b = pl.program_id(0)
        started = []
        for k in range(_BLK):
            j = idx_ref[b * _BLK + k]

            def start(k=k, j=j):
                pltpu.make_async_copy(upd_vmem.at[k], out_hbm.at[j],
                                      sems.at[k]).start()

            def wait(k=k, j=j):
                pltpu.make_async_copy(upd_vmem.at[k], out_hbm.at[j],
                                      sems.at[k]).wait()

            pl.when(j >= 0)(start)
            started.append((j, wait))
        for j, wait in started:
            pl.when(j >= 0)(wait)
    return kernel


def _merge_duplicate_lines(pack, rows):
    """Sort by pack id and merge duplicate lines with a cumsum
    difference at each segment's last element.  Returns (pack_ids[M]
    int32 with -1 on merged/invalid slots, lines[M,128] with segment
    totals at the surviving slots)."""
    m = pack.shape[0]
    order = jnp.argsort(pack)
    pack_s = pack[order]
    rows_s = rows[order]
    csum = jnp.cumsum(rows_s, axis=0)
    neq = pack_s[1:] != pack_s[:-1]
    first = jnp.concatenate([jnp.ones((1,), bool), neq])
    last = jnp.concatenate([neq, jnp.ones((1,), bool)])
    start = jax.lax.cummax(jnp.where(first, jnp.arange(m), -1))
    prev = jnp.take(csum, jnp.maximum(start - 1, 0), axis=0)
    totals = jnp.where((start > 0)[:, None], csum - prev, csum)
    packs_u = jnp.where(last & (pack_s >= 0), pack_s, -1)
    return (packs_u.astype(jnp.int32),
            jnp.where(last[:, None], totals, 0.0))


def pack_write(pack_ids, lines, p_rows, mesh=None):
    """Write-only densify: out[pack_ids[i]] = lines[i] summed over
    duplicates (negative ids ignored), everything else zero.  Shapes:
    pack_ids [M] int, lines [M, 128] -> [p_rows, 128].  ``mesh``: the one
    the caller sees, or None."""
    return _write(pack_ids, lines, p_rows, dispatch.take(
        "pack_write", mesh, _unsupported(lines.dtype)))


def _write(pack_ids, lines, p_rows, kernel):
    """``pack_write`` by the row-write kernel or, without ``kernel``, by a
    scatter-add."""
    pack_ids = pack_ids.reshape(-1).astype(jnp.int32)
    m = pack_ids.shape[0]
    lines = lines.reshape(m, 128)
    if not kernel:
        safe = jnp.where(pack_ids >= 0, pack_ids, p_rows)
        z = jnp.zeros((p_rows + 1, 128), lines.dtype)
        return z.at[safe].add(lines)[:p_rows]
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m_pad = (m + _BLK - 1) // _BLK * _BLK
    if m_pad != m:
        pack_ids = jnp.concatenate(
            [pack_ids, jnp.full((m_pad - m,), -1, jnp.int32)])
        lines = jnp.concatenate(
            [lines, jnp.zeros((m_pad - m, 128), lines.dtype)])
    packs_u, merged = _merge_duplicate_lines(pack_ids, lines)
    zeros = jnp.zeros((p_rows, 1, 128), lines.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m_pad // _BLK,),
        in_specs=[pl.BlockSpec((_BLK, 1, 128), lambda b, i: (b, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((_BLK,))],
    )
    out = pl.pallas_call(
        _make_kernel(),
        name="hetu_packed_embedding_write",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((p_rows, 1, 128), lines.dtype),
        # alias the zero fill straight into the output: XLA's broadcast
        # provides it and the kernel only touches written lines
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(packs_u, merged.reshape(m_pad, 1, 128), zeros)
    return out.reshape(p_rows, 128)


def _position_lines(ids, g, q, dim):
    """Place each [dim] gradient row at its lane offset inside a
    [128] line.  Expressed as tile+mask so XLA keeps it one elementwise
    fusion over [M, 128] — the broadcast-multiply/einsum forms lower
    through a materialized transpose (~56 us at W&D shapes)."""
    off = jnp.where(ids >= 0, ids % q, 0)
    tiled = jnp.concatenate([g] * q, axis=1)                   # [M, 128]
    lane_slot = (jnp.arange(q * dim, dtype=jnp.int32) // dim)  # [128]
    mask = lane_slot[None, :] == off[:, None].astype(jnp.int32)
    return jnp.where(mask, tiled, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def packed_lookup(table, ids, dim, mesh=None):
    """Row lookup from a PACKED [p_rows, 128] table: returns
    [..., dim] rows for integer ``ids`` (shape-preserving like
    jnp.take).  The vjp produces the packed dense gradient through
    ``pack_write`` — no XLA scatter anywhere; ``mesh`` (the one the caller
    sees, or None) is for it."""
    q = 128 // dim
    flat = ids.reshape(-1).astype(jnp.int32)
    # negative (padding) ids clamp to logical row 0, matching the
    # unpacked embedding_lookup/IndexedSlices path — without the clamp,
    # flat // q clips to line 0 but flat % q picks slot q-1, gathering
    # an arbitrary row (ADVICE r5).  The vjp drops negatives either way.
    safe = jnp.maximum(flat, 0)
    lines = jnp.take(table, safe // q, axis=0)                 # [M, 128]
    # masked select-sum, NOT a one-hot multiply-sum: 0 * NaN = NaN, so
    # the multiply form let one non-finite row poison every row sharing
    # its lane-line (the serving watchdog's per-request containment
    # depends on a poisoned row flagging only itself).  Bitwise
    # identical for finite rows — same summation order, x + 0 terms —
    # and the same single elementwise+reduce fusion.
    mask = (safe % q)[:, None] == jnp.arange(q, dtype=jnp.int32)
    rows = jnp.sum(jnp.where(mask[:, :, None],
                             lines.reshape(-1, q, dim), 0.0), axis=1)
    return rows.reshape(ids.shape + (dim,))


def _packed_lookup_fwd(table, ids, dim, mesh):
    return packed_lookup(table, ids, dim, mesh), (ids, table.shape[0])


def _packed_lookup_bwd(dim, mesh, res, g):
    ids, p_rows = res
    q = 128 // dim
    flat = ids.reshape(-1).astype(jnp.int32)
    lines = _position_lines(flat, g.reshape(-1, dim), q, dim)
    grad = _write(flat // q, lines, p_rows, dispatch.take(
        "packed_lookup", mesh, _unsupported(lines.dtype)))
    return grad, np.zeros(ids.shape, jax.dtypes.float0)


packed_lookup.defvjp(_packed_lookup_fwd, _packed_lookup_bwd)


def sharded_packed_lookup(mesh, table, ids, dim, axis="model"):
    """:func:`packed_lookup` inside a GSPMD mesh program.

    ``pallas_call`` does not partition, so the lookup runs under
    ``shard_map``: the packed ``[p_rows, 128]`` table is
    replicated into every shard, the id batch's LEADING dim splits over
    mesh axis ``axis`` (it must divide the axis size), and each device
    runs the identical kernel — or the bitwise-equal jnp fallback off
    TPU — on its local slice.  Returns ``[..., dim]`` rows sharded the
    same way as ``ids``.  This is the inference/scoring path (the
    embedding server's lookups); training gradients keep flowing
    through the unsharded ``packed_lookup`` vjp."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    n_shards = int(mesh.shape[axis])
    if ids.shape[0] % n_shards:
        raise ValueError(
            f"ids leading dim {ids.shape[0]} must divide mesh axis "
            f"{axis!r} (size {n_shards})")

    def local(tbl, local_ids):
        return packed_lookup(tbl, local_ids, dim)   # per shard: no mesh

    spec = P(axis) if ids.ndim == 1 else P(*((axis,) + (None,) *
                                             (ids.ndim - 1)))
    out_spec = P(*(tuple(spec) + (None,)))
    f = shard_map(local, mesh=mesh, in_specs=(P(), spec),
                  out_specs=out_spec)
    return f(table, ids)


def pack_table(table, dim=None):
    """[num_rows, dim] -> packed [p_rows, 128] (host or device),
    zero-padding the tail line."""
    n, d = table.shape
    q = pack_factor(d)
    assert q, f"dim {d} does not pack into 128 lanes"
    p = packed_rows(n, d)
    pad = p * q - n
    if pad:
        table = jnp.concatenate(
            [jnp.asarray(table),
             jnp.zeros((pad, d), jnp.asarray(table).dtype)])
    return jnp.asarray(table).reshape(p, 128)


def unpack_table(packed, num_rows, dim):
    """Packed [p_rows, 128] -> [num_rows, dim]."""
    q = pack_factor(dim)
    return packed.reshape(-1, dim)[:num_rows]
