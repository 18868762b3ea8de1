"""Grouped matrix products over tile-aligned expert groups (dropless MoE).

The dropless MoE path (ops/moe.py ``dropless_moe``) sorts the (token,
choice) pairs by expert and lays each expert's rows out from a row-tile
boundary on (``ops/moe.py grouped_layout``): every row tile of ``tm`` rows
then belongs to one expert, named by ``tile_expert[tile]``, and the rows
past an expert's last pair are zeros.  So the kernels need no row mask and
no group offsets, only the scalar-prefetched ``tile_expert`` to pick the
weight block (the idea of MegaBlocks' block-sparse products, Gale et al.
2022, with the padding paid in the layout instead of in a mask):

* ``gmm(x [M, K], w [E, K, N]) -> [M, N]``: row tile ``i`` times
  ``w[tile_expert[i]]``; with ``transpose_rhs`` ``w`` is ``[E, N, K]``
  (the backward product for ``dx``).  Consecutive tiles of one expert keep
  the weight block's index, so Pallas fetches each expert's weights once.
* ``tgmm(x [M, K], dy [M, N]) -> [E, K, N]``: ``dw[e]`` is the sum over
  expert ``e``'s tiles of ``x_tile^T dy_tile`` (rows innermost in the grid,
  an f32 accumulator carried across one expert's tiles).  Every expert owns
  at least one tile, so every ``dw[e]`` is written.

Tiles from ``n_used`` on (the static row bound is never reached) are
skipped: ``gmm`` writes zeros there, ``tgmm`` adds nothing.  The layout
assigns them to the last expert, so ``tile_expert`` stays sorted.

The blocks are the product's own widths (``gmm_plan``, ``tgmm_plan``:
functions of ``(tm, k, n, dtype)`` and ``VMEM_LIMIT`` alone; no argument,
environment variable or table decides one).  ``gmm`` takes the contraction
WHOLE wherever its blocks fit ``BLOCK_BUDGET``: with one contraction step
the weight block's index changes only where the expert does, so an expert's
weights are fetched once; split over ``kk``, the innermost grid axis, they
are fetched again for every row tile, live or skipped (PR 73: 2.0 MB a grid
step under 1.3 us of product at Mellum2's 2,304).  Then the widest column
block that still fits, the whole width first: each column pass reads every
row tile again.  ``tgmm`` takes the blocks that walk the rows the fewest
times, once where ``[K, N]`` fits: each ``(a, b)`` of its grid is a pass
over ALL row tiles.  A product too wide for the budget gets the blocks of
``fit128`` under the caps the kernels had before PR 73.  Every traced
kernel counts its plan (``dispatch.count_gmm_plan``).

Named ``hetu_moe_gmm_fwd`` / ``hetu_moe_gmm_dx`` / ``hetu_moe_gmm_dw`` in
the device trace.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import dispatch
from .common import VMEM_LIMIT, WALK, fit, params

# (scoped VMEM: the kernels ask for ``VMEM_LIMIT``, 64 MiB of the v5e's 128
# where Mosaic's default is 16.  A plan sums its blocks: the two operands'
# and the output's, each twice (Pallas double-buffers them), and the f32
# accumulator once.  Whole, bf16, 128-row tiles: ``gmm`` 10 MB at Mellum2's
# 2,304 x 896, 17 at Xing4.0's 3,584 x 1,024, 22 at Nemotron-H's 2,688 x
# 1,856; ``tgmm`` 18, 31 and 42, and 38 at ZAYA1's 2,048 x 2,048 on 256-row
# tiles.  The rest of the limit is the body's: the product's f32 value
# before it is added, at most another accumulator)
#: what a plan's blocks may sum to, of ``VMEM_LIMIT``
BLOCK_BUDGET = VMEM_LIMIT * 3 // 4
#: the widest dimension taken as one block where no multiple of 128 divides
#: it, and the contraction block of a product the budget cannot take whole
WHOLE_WIDTH = 2048


def fit128(dim, want):
    """The largest multiple of 128 that divides ``dim`` and is at most
    ``want`` (``dim`` itself when it is at most ``want`` or none does)."""
    return dim if dim <= want else fit(dim, want, 128) or dim


def blocks(dim):
    """The blocks ``dim`` can be cut into, widest first: itself, then every
    multiple of 128 that divides it."""
    return [dim] + [t for t in range(dim // 128 * 128, 0, -128)
                    if t < dim and dim % t == 0]


class Plan(NamedTuple):
    """A grouped product's blocks: ``tk`` of ``k`` and ``tn`` of ``n``, the
    ``k_blocks = k / tk`` and ``n_blocks = n / tn`` they make, and the
    ``vmem`` bytes the kernel's blocks sum to."""
    tk: int
    tn: int
    k_blocks: int
    n_blocks: int
    vmem: int


def _plan(tm, k, n, dtype, tk, tn, acc_rows):
    """The plan of blocks ``tk``, ``tn``: three double-buffered blocks of
    the operands' type and an f32 accumulator ``[acc_rows, tn]``."""
    size = jnp.dtype(dtype).itemsize
    return Plan(tk, tn, k // tk, n // tn,
                2 * size * (tm * tk + tk * tn + tm * tn) + 4 * acc_rows * tn)


def gmm_plan(tm, k, n, dtype):
    """``gmm``'s blocks for ``[tm, k]`` row tiles times ``[k, n]`` weights:
    the contraction whole and the widest ``tn`` that fits ``BLOCK_BUDGET``
    beside it; where no ``tn`` does, ``fit128`` under the old caps."""
    return next(
        (p for p in (_plan(tm, k, n, dtype, k, tn, tm) for tn in blocks(n))
         if p.vmem <= BLOCK_BUDGET),
        _plan(tm, k, n, dtype, fit128(k, WHOLE_WIDTH), fit128(n, 1024), tm))


def tgmm_plan(tm, k, n, dtype):
    """``tgmm``'s blocks for ``[tm, k]^T [tm, n]`` sums: of those that fit
    ``BLOCK_BUDGET`` (of all, where none does: a width no multiple of 128
    divides has one block) the fewest passes over the rows, ``k_blocks x
    n_blocks``, then the fewest operand bytes read again (``x`` once a
    column block, ``dy`` once a block of ``k``)."""
    plans = [_plan(tm, k, n, dtype, tk, tn, tk)
             for tk in blocks(k) for tn in blocks(n)]
    return min([p for p in plans if p.vmem <= BLOCK_BUDGET] or plans,
               key=lambda p: (p.k_blocks * p.n_blocks,
                              p.n_blocks * k + p.k_blocks * n))


def unsupported(m, k, n, tm, dtype):
    """Why the grouped-product kernels do not run, or None when they do."""
    if not (dispatch.mosaic() or dispatch.interpret()):
        return f"platform:{dispatch.platform()}"
    if m % tm:
        return f"rows_not_tile_aligned:{m}%{tm}"
    if dispatch.mosaic():
        # a width that is no multiple of 128 has no 128-aligned divisor:
        # ``fit128`` takes it whole, as one block the size of the array's
        # dimension, which Mosaic takes (Nemotron-H's experts are 1,856
        # wide, 14.5 x 128) so long as a block of it fits the scoped VMEM
        if any(d % 128 and d > WHOLE_WIDTH for d in (k, n)) or tm % 8:
            return "dims_not_128_aligned"
        if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                    jnp.dtype(jnp.float32)):
            return f"dtype:{jnp.dtype(dtype).name}"
    return None


def live(i, n_used):
    """Row tile ``i``, or the last live one for a skipped tile: its block
    index then repeats and Pallas fetches nothing new."""
    return jnp.minimum(i, n_used[0] - 1)


# The kernels are jitted functions: a layer's three or two products, its
# backward pass, every layer of a model and every pass over a held layout's
# rows call the same few (shapes, options), and each is traced and lowered
# once a program, not once a call site (tracing a kernel is Python time).

@functools.partial(jax.jit, static_argnames=("tm", "transpose_rhs", "name"))
def gmm(x, w, tile_expert, n_used, *, tm, transpose_rhs=False,
        name="hetu_moe_gmm_fwd"):
    """``out[tile i] = x[tile i] @ w[tile_expert[i]]`` (``w[...]^T`` with
    ``transpose_rhs``) for the first ``n_used`` row tiles, zeros after."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    m, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    tk, tn, tiles_k, passes, _ = gmm_plan(tm, k, n, x.dtype)
    dispatch.count_gmm_plan(name, tiles_k, passes)
    contract = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))

    def kernel(te, nu, x_ref, w_ref, o_ref, acc):
        i, kk = pl.program_id(1), pl.program_id(2)

        @pl.when(kk == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(i < nu[0])
        def _():
            acc[...] += jax.lax.dot_general(
                x_ref[...], w_ref[...], contract,
                preferred_element_type=jnp.float32)

        @pl.when(kk == tiles_k - 1)
        def _():
            o_ref[...] = acc[...].astype(o_ref.dtype)

    if transpose_rhs:
        w_spec = pl.BlockSpec(
            (None, tn, tk),
            lambda j, i, kk, te, nu: (te[live(i, nu)], j, kk))
    else:
        w_spec = pl.BlockSpec(
            (None, tk, tn),
            lambda j, i, kk, te, nu: (te[live(i, nu)], kk, j))
    return pl.pallas_call(
        kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, m // tm, tiles_k),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda j, i, kk, te, nu: (live(i, nu), kk)),
                w_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda j, i, kk, te, nu: (i, j)),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=params(
            dispatch.interpret(), ("parallel", "arbitrary", "arbitrary"),
            VMEM_LIMIT),
        interpret=dispatch.interpret(),
    )(tile_expert, n_used, x, w)


@functools.partial(jax.jit, static_argnames=("num_experts", "tm", "name"))
def tgmm(x, dy, tile_expert, n_used, num_experts, *, tm,
         name="hetu_moe_gmm_dw"):
    """``dw[e] = sum over expert e's row tiles of x_tile^T @ dy_tile``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    m, k = x.shape
    n = dy.shape[1]
    tk, tn, tiles_k, tiles_n, _ = tgmm_plan(tm, k, n, x.dtype)
    dispatch.count_gmm_plan(name, tiles_k, tiles_k * tiles_n)
    tiles_m = m // tm

    def kernel(te, nu, x_ref, dy_ref, o_ref, acc):
        i = pl.program_id(2)
        e = te[i]
        first = jnp.logical_or(i == 0, te[jnp.maximum(i - 1, 0)] != e)
        last = jnp.logical_or(i == tiles_m - 1,
                              te[jnp.minimum(i + 1, tiles_m - 1)] != e)

        @pl.when(first)
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(i < nu[0])
        def _():
            acc[...] += jax.lax.dot_general(
                x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            o_ref[...] = acc[...].astype(o_ref.dtype)

    return pl.pallas_call(
        kernel, name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, tiles_m),
            in_specs=[
                pl.BlockSpec((tm, tk),
                             lambda a, b, i, te, nu: (live(i, nu), a)),
                pl.BlockSpec((tm, tn),
                             lambda a, b, i, te, nu: (live(i, nu), b))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda a, b, i, te, nu: (te[i], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((num_experts, k, n), x.dtype),
        compiler_params=params(dispatch.interpret(), WALK, VMEM_LIMIT),
        interpret=dispatch.interpret(),
    )(tile_expert, n_used, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def grouped_matmul(x, w, tile_expert, n_used, tm, num_experts):
    """``x [M, K] @ w[expert of each row tile] -> [M, N]`` with the Pallas
    kernels above forward and backward."""
    return gmm(x, w, tile_expert, n_used, tm=tm)


def _fwd(x, w, tile_expert, n_used, tm, num_experts):
    return (gmm(x, w, tile_expert, n_used, tm=tm),
            (x, w, tile_expert, n_used))


def _bwd(tm, num_experts, res, dy):
    x, w, tile_expert, n_used = res
    dx = gmm(dy, w, tile_expert, n_used, tm=tm, transpose_rhs=True,
             name="hetu_moe_gmm_dx")
    dw = tgmm(x, dy, tile_expert, n_used, num_experts, tm=tm)
    return dx, dw, None, None


grouped_matmul.defvjp(_fwd, _bwd)
