"""MoE layout-transform (token dispatch/combine) via a Pallas row gather.

Reference: src/ops/LayoutTransform.cu / ReverseLayoutTransform.cu — CUDA
kernels moving each token's row into its (expert, capacity-slot) and back.
The dense TPU formulation (einsum against one-hot [T, E, C] dispatch
tensors, ops/moe.py) is MXU-friendly but materializes O(T·E·C) memory —
the exact wall LayoutTransform.cu exists to avoid (SURVEY §2.1 N3 lists
this kernel).

TPU redesign: both directions are ROW GATHERS once the routing is known —
  dispatch: expert_in[slot]  = tokens[slot_to_token[slot]]
  combine:  out[t]          += gate_c[t] * expert_out[token_to_slot_c[t]]
so one Pallas kernel serves both.  The source table stays in HBM
(`pl.ANY` memory space) and the index vector is scalar-prefetched to
SMEM; each grid step DMAs its 8 arbitrary source rows into a VMEM
scratch (8 parallel `make_async_copy`s) and writes the masked block out
— exactly the rows needed move, no one-hot, no [T, E, C] anywhere.
(A BlockSpec index_map gather with (1, H) blocks is rejected by Mosaic:
the sublane dim of a block must be divisible by 8, and one index_map
can't pick 8 unrelated rows — hence the explicit-DMA form.)  XLA's own
gather lowering on TPU can fall back to one-hot matmul for small row
counts, which would reintroduce the memory wall; the Pallas kernel
makes the row-copy lowering deterministic.

Out-of-range indices (capacity-dropped tokens, empty slots) yield zero
rows, matching the dense path's zero dispatch rows.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from . import dispatch


def _unsupported(src_shape, dtype, use_pallas):
    """Why the row-gather kernel does not run, or None when it does (the
    DMA form needs Mosaic, so there is no interpret-mode twin)."""
    if not use_pallas:
        return "caller:use_pallas=False"
    if not dispatch.mosaic():
        return f"platform:{dispatch.platform()}"
    n, h = src_shape
    if h % 128 != 0 or h > 16384:
        return "hidden_not_128_aligned_le_16384"
    if dtype not in (jnp.float32, jnp.bfloat16, np.float32):
        return f"dtype:{jnp.dtype(dtype).name}"
    return None


_BLK = 8  # output rows per grid step = the TPU sublane quantum


def _make_kernel():
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(n_rows, idx_ref, src_hbm, out_ref, scratch, sems):
        # scratch is [_BLK, 1, h]: the DMA'd dim must sit OUTSIDE the
        # (8, 128)-tiled trailing pair — a 1-row slice of a 2-D VMEM
        # buffer is not a legal DMA target ("slice along dimension 0
        # must be aligned to tiling (8)")
        b = pl.program_id(0)
        copies = []
        for k in range(_BLK):
            j = idx_ref[b * _BLK + k]
            jc = jnp.clip(j, 0, n_rows - 1)
            # src arrives as [n, 1, h] so the gathered dim is untiled on
            # the source side too (ANY may resolve to VMEM for small
            # tables, where a 1-row slice of a tiled dim is illegal)
            c = pltpu.make_async_copy(src_hbm.at[jc],
                                      scratch.at[k],
                                      sems.at[k])
            c.start()
            copies.append(c)
        for c in copies:
            c.wait()
        # zero rows whose logical index was out of range (the contract —
        # and the jnp fallback — zero-fill both sides)
        idxs = jnp.stack([idx_ref[b * _BLK + k] for k in range(_BLK)])
        # expand the minor dim while still i32 (Mosaic rejects the
        # equivalent reshape on an i1 vector), then compare
        idxs2 = idxs[:, None]
        valid = (idxs2 >= 0) & (idxs2 < n_rows)
        out_ref[...] = jnp.where(valid, scratch[:, 0, :],
                                 jnp.zeros((_BLK, scratch.shape[2]),
                                           scratch.dtype))
    return kernel


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def row_gather(src, idx, use_pallas=True):
    """out[i] = src[idx[i]] for 0 <= idx[i] < src.shape[0], else zeros.

    Falls back to a jnp take when the Pallas envelope doesn't apply
    (CPU tests, ragged hidden sizes) or when ``use_pallas`` is False —
    callers inside GSPMD-sharded programs must pass False, since
    pallas_call does not partition."""
    return _row_gather_fwd_impl(src, idx, use_pallas)


def _row_gather_fwd_impl(src, idx, use_pallas=True):
    n, h = src.shape
    m = idx.shape[0]
    if not dispatch.record("moe_row_gather",
                           _unsupported(src.shape, src.dtype, use_pallas)):
        # jnp.take wraps NEGATIVE indices numpy-style; remap them to an
        # out-of-bounds sentinel so they fill with zeros like the kernel
        safe = jnp.where(idx >= 0, idx, n)
        return jnp.take(src, safe, axis=0, mode="fill", fill_value=0)
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # pad the index vector to a whole number of 8-row blocks; the pad
    # rows carry the invalid sentinel and come out zero
    m_pad = (m + _BLK - 1) // _BLK * _BLK
    idx_p = jnp.full((m_pad,), -1, jnp.int32).at[:m].set(
        idx.astype(jnp.int32))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m_pad // _BLK,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((_BLK, h), lambda b, idx_ref: (b, 0)),
        scratch_shapes=[pltpu.VMEM((_BLK, 1, h), src.dtype),
                        pltpu.SemaphoreType.DMA((_BLK,))],
    )
    import functools as _ft
    out = pl.pallas_call(
        _ft.partial(_make_kernel(), n),
        name="hetu_moe_row_gather",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m_pad, h), src.dtype),
    )(idx_p, src[:, None, :])
    return out[:m] if m_pad != m else out


def _row_gather_fwd(src, idx, use_pallas):
    return _row_gather_fwd_impl(src, idx, use_pallas), (idx, src.shape[0])


def _row_gather_bwd(use_pallas, res, ct):
    idx, n = res
    # scatter-add of cotangent rows back to their sources; indices are
    # unique in the MoE use (capacity queue guarantees one token per
    # slot), but add is correct regardless
    valid = (idx >= 0) & (idx < n)
    safe = jnp.clip(idx, 0, n - 1)
    ct = jnp.where(valid[:, None], ct, 0)
    d_src = jnp.zeros((n, ct.shape[1]), ct.dtype).at[safe].add(ct)
    return d_src, None


row_gather.defvjp(_row_gather_fwd, _row_gather_bwd)
