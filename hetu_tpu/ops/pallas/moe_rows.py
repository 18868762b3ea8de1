"""Rows to tokens in a held expert layer: a sum over tiles of tokens.

A layer that holds a share of the experts (ops/moe.py ``_held_pass``) lays
the pairs routed here out as ``M`` rows in expert order; a token owns at
most ``k`` of them, and ``y[t]`` is the sum of its rows.  ``ops/moe.py
token_tiles`` puts the rows in TOKEN order, grouped by tiles of ``tt``
tokens, each group from a row-tile boundary on (the layout of
``grouped_layout`` with the token tile in the expert's place), so a row tile
of ``tm`` rows belongs to one token tile, named by ``tile_group[tile]``, and
``loc`` says which of the tile's tokens a row is (``-1``: padding).

``rows_sum`` then is ``tgmm`` of ops/pallas/moe_gmm.py with the left operand
built in VMEM: for row tile ``i`` the LOCAL one-hot ``[tt, tm]`` from ``loc``
and an iota, times the ``[tm, H-block]`` rows, added into an f32 ``[tt,
H-block]`` that is carried across one token tile's row tiles and written,
rounded once, after the last.  Every token tile owns at least one row tile,
so every block of ``y`` is written; the row tiles from ``n_used`` on hold
nothing and are skipped.  ``2 M' tt H`` operations where the ``[T, M]``
one-hot product took ``2 T M H``.

Named ``hetu_moe_rows_sum`` in the device trace (not ``hetu_moe_gmm*``: the
benchmark takes those events for the grouped products).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dispatch
from .common import VMEM_LIMIT, params
from .moe_gmm import fit128, live


def unsupported(tokens, hidden, tt, tm, dtype):
    """Why the segmented sum does not run where the grouped products' kernels
    do (Mosaic, or interpret mode), or None when it does."""
    if tokens % tt:
        return f"tokens_not_tile_aligned:{tokens}%{tt}"
    if dispatch.mosaic():
        # a row tile's ``loc`` lies along the lanes, a token tile's one-hot
        # down the sublanes of a product's left operand
        if hidden % 128 or tm % 128 or tt % 128:
            return "dims_not_128_aligned"
        if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                    jnp.dtype(jnp.float32)):
            return f"dtype:{jnp.dtype(dtype).name}"
    return None


@functools.partial(jax.jit, static_argnames=("tokens", "tt", "th"))
def rows_sum(rows, loc, tile_group, n_used, *, tokens, tt, th=2048):
    """``y[g tt + r] = sum of rows[s]`` over the rows ``s`` of token tile
    ``g``'s row tiles with ``loc[0, s] == r``; ``rows [M', H]``, ``loc [1,
    M']``, ``y [tokens, H]`` of ``rows``' type, summed in f32."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    m, h = rows.shape
    tiles_m = tile_group.shape[0]
    tm, th = m // tiles_m, fit128(h, th)
    full = jax.lax.Precision.HIGHEST if rows.dtype == jnp.float32 else None

    def kernel(tg, nu, loc_ref, rows_ref, o_ref, acc):
        i = pl.program_id(1)
        g = tg[i]
        first = jnp.logical_or(i == 0, tg[jnp.maximum(i - 1, 0)] != g)
        last = jnp.logical_or(i == tiles_m - 1,
                              tg[jnp.minimum(i + 1, tiles_m - 1)] != g)

        @pl.when(first)
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(i < nu[0])
        def _():
            # loc lies along the lanes and broadcasts down the sublanes:
            # the one-hot comes out transposed, ready to be the left operand
            hot = jax.lax.broadcasted_iota(jnp.int32, (tt, tm), 0) \
                == loc_ref[...]
            acc[...] += jnp.dot(
                jnp.where(hot, 1.0, 0.0).astype(rows_ref.dtype),
                rows_ref[...], precision=full,
                preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            o_ref[...] = acc[...].astype(o_ref.dtype)

    return pl.pallas_call(
        kernel, name="hetu_moe_rows_sum",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h // th, tiles_m),
            in_specs=[
                pl.BlockSpec((1, tm),
                             lambda j, i, tg, nu: (0, live(i, nu))),
                pl.BlockSpec((tm, th),
                             lambda j, i, tg, nu: (live(i, nu), j))],
            out_specs=pl.BlockSpec((tt, th),
                                   lambda j, i, tg, nu: (tg[i], j)),
            scratch_shapes=[pltpu.VMEM((tt, th), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((tokens, h), rows.dtype),
        compiler_params=params(dispatch.interpret(),
                               ("parallel", "arbitrary"), VMEM_LIMIT),
        interpret=dispatch.interpret(),
    )(tile_group, n_used, loc, rows)
