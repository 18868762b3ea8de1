"""Rotary position embeddings on the projections' ``[B, S, H d]`` as two
Pallas kernels, queries and keys in one call (``ops/rotary.py`` has the node,
the tables and the ``jax.numpy`` form, ``_rotary``, these are held to).

``y = x cos + rotate_half(x) sin`` is pure memory traffic: a pass has to read
a tensor once and write it once.  XLA ran it (PR 47's traced Ouro step) on the
f32 ``[B, S, H, d]`` head view: a reshape that is a pass over HBM, two 64-lane
halves of a 128-lane tile sliced, negated, concatenated and padded, the tables
broadcast at every call, and a reshape back; thirteen times the traffic.  Here
a block of ``[rows, H d]`` of ``q`` and of ``k`` stays in VMEM; where a head is
whole lane tiles, ``rotate_half`` is a rotation of its lanes by ``d / 2`` with
its sign folded into the sine table (``sin±``: ``-sin`` on a head's first
``d / 2`` lanes, ``+sin`` on the rest), so a head's column is

    y = x cos + roll(x, d / 2) sin±          f32, one rounding at the store

with no slice, no concatenation and no head view.

``hetu_rope_fwd``: grid (row blocks, batch).  A program reads its block of
``q``, of ``k`` and of the tables ``[2, rows, d]`` (``cos``, ``sin±``; f32, the
batch innermost so that a block of them is fetched once) and walks it in
chunks of rows; a chunk's tables stay in registers over all heads of both
tensors.

``hetu_rope_bwd``: the same body with ``sin±`` negated: the transpose of a
rotation is the rotation by the opposite angle, ``dx_j = g_j cos_j - s_j
g_(j ^ d/2) sin_j``.  Nothing is kept for the backward pass but the tables.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dispatch
from .common import fit, params

#: rows a chunk and a block are multiples of: a bf16 tile's sublanes
ROWS = 16
#: bytes of a block of ``q`` (four such blocks twice, double-buffered, and the
#: tables': 8.5 MiB of the 16 MiB a kernel may use by default) and the rows of
#: a chunk (its ``cos``, ``sin±``, ``x`` and rolled ``x`` are 8 f32 registers
#: each at 128 lanes).  v5e at bf16 ``[1, 8192, 2048]`` (PERF.md, PR 48; a
#: pair moves 143 MB, 0.174 ms at 819 GB/s): in the Ouro step a forward call
#: 0.203 ms and a backward one 0.164; between two products and the flash
#: kernels alone 0.104 each, and blocks of 0.5 / 1 / 2 MiB at chunks of 16 to
#: 128 rows all 0.103-0.107: nothing here is worth tuning.
TILE, CHUNK = 2 ** 20, 64

_F32 = jnp.float32


def unsupported(q, k, *, head_dim):
    """Why the kernels do not take ``q``, ``k [B, S, H d]``, or None when they
    do."""
    if head_dim % 128:
        return "head_dim_not_128_aligned"
    types = {jnp.dtype(t.dtype) for t in (q, k)}
    if len(types) > 1:
        return "dtype:mixed"
    if not types <= {jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)}:
        return f"dtype:{types.pop().name}"
    if q.shape[1] % ROWS:
        return f"seq_not_{ROWS}_aligned"
    if q.shape != k.shape:
        return "q_k_widths_differ"
    return None


def _kernel(q_ref, k_ref, t_ref, qo_ref, ko_ref, *, chunk, backward):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows, lanes = q_ref.shape
    d = t_ref.shape[2]

    def step(i, carry):
        at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        cos, sin = t_ref[0, at, :], t_ref[1, at, :]
        if backward:
            sin = -sin
        for ref, out in ((q_ref, qo_ref), (k_ref, ko_ref)):
            for h in range(lanes // d):
                head = slice(h * d, (h + 1) * d)
                x = ref[at, head].astype(_F32)
                out[at, head] = (x * cos + pltpu.roll(x, d // 2, 1) * sin
                                 ).astype(out.dtype)
        return carry
    jax.lax.fori_loop(0, rows // chunk, step, 0)


def _call(name, backward, q, k, tables, interpret, tile, chunk):
    import jax.experimental.pallas as pl
    B, S, W = q.shape
    d = tables.shape[2]
    ts = fit(S, max(tile // (W * q.dtype.itemsize), ROWS), ROWS)
    block = pl.BlockSpec((None, ts, W), lambda s, b: (b, s, 0))
    return pl.pallas_call(
        functools.partial(_kernel, chunk=fit(ts, max(chunk, ROWS), ROWS),
                          backward=backward),
        name=name, grid=(S // ts, B),
        in_specs=[block, block,
                  pl.BlockSpec((2, ts, d), lambda s, b: (0, s, 0))],
        out_specs=[block, block],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype)],
        compiler_params=params(interpret, ("parallel", "parallel")),
        interpret=interpret,
    )(q, k, tables)


_STATIC = ("interpret", "tile", "chunk")


@functools.partial(jax.jit, static_argnames=_STATIC)
def hetu_rope_fwd(q, k, tables, *, interpret, tile=TILE, chunk=CHUNK):
    """``q``, ``k [B, S, H d]`` and ``tables [2, S, d]`` f32 (``cos``,
    ``sin±``) -> the rotated ``(q, k)`` in their type."""
    return _call("hetu_rope_fwd", False, q, k, tables, interpret, tile, chunk)


@functools.partial(jax.jit, static_argnames=_STATIC)
def hetu_rope_bwd(dq, dk, tables, *, interpret, tile=TILE, chunk=CHUNK):
    """The cotangents of ``hetu_rope_fwd``'s ``q``, ``k`` from its
    results'."""
    return _call("hetu_rope_bwd", True, dq, dk, tables, interpret, tile,
                 chunk)


@jax.custom_vjp
def rope(q, k, tables):
    """The rotation through the kernel pair: ``q``, ``k [B, S, H d]``,
    ``tables [2, S, d]`` -> ``(q, k)`` rotated."""
    return tuple(hetu_rope_fwd(q, k, tables,
                               interpret=dispatch.interpret()))


def _rope_fwd(q, k, tables):
    return rope(q, k, tables), tables


def _rope_bwd(tables, g):
    dq, dk = hetu_rope_bwd(*g, tables, interpret=dispatch.interpret())
    return dq, dk, None                 # the tables: no gradient


rope.defvjp(_rope_fwd, _rope_bwd)
