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

``q`` and ``k`` have each their own width, any multiple of the head size
(grouped queries: 64 query heads on 8 key heads); the grid then gets a third
axis over the lanes, a key head and its query heads a program, and the body
walks the ``width // d`` heads of its block of each tensor.  A PARTIAL rotation (the first ``r < d`` lanes of a head
turn, with frequencies over ``r``; the rest pass) keeps whole 128-lane tiles
too: ``rotate_half`` over ``r`` lanes is two rotations of the head's lanes,
each with a sine table that is zero where the other one holds,

    y = x cos + roll(x, r / 2) sA + roll(x, d - r / 2) sB

``sA = +sin`` on the lanes ``[r / 2, r)``, ``sB = -sin`` on ``[0, r / 2)``,
``cos = 1`` and both sines 0 from ``r`` on: tables ``[3, S, d]``.  At ``r = d``
the two rotations coincide and ``sA + sB`` is ``sin±``, which is the ``[2, S,
d]`` form above; only ``r < d`` carries the third table.  The backward pass
negates both sines.
"""

from __future__ import annotations

import functools
import math

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
    return None


def _kernel(q_ref, k_ref, t_ref, qo_ref, ko_ref, *, chunk, backward,
            shift=None):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    rows = q_ref.shape[0]
    d = t_ref.shape[2]
    # [2, S, d]: every lane of a head turns, one rotation by d / 2 and
    # ``sin±``; [3, S, d]: the first 2 * shift lanes turn, two rotations
    shifts = (d // 2,) if t_ref.shape[0] == 2 else (shift, d - shift)

    def step(i, carry):
        at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        cos = t_ref[0, at, :]
        sines = [t_ref[1 + n, at, :] for n in range(len(shifts))]
        if backward:
            sines = [-sin for sin in sines]
        for ref, out in ((q_ref, qo_ref), (k_ref, ko_ref)):
            for h in range(ref.shape[1] // d):
                head = slice(h * d, (h + 1) * d)
                x = ref[at, head].astype(_F32)
                y = x * cos
                for by, sin in zip(shifts, sines):
                    y = y + pltpu.roll(x, by, 1) * sin
                out[at, head] = y.astype(out.dtype)
        return carry
    jax.lax.fori_loop(0, rows // chunk, step, 0)


def _call(name, backward, q, k, tables, interpret, tile, chunk, rotary_dim):
    import jax.experimental.pallas as pl
    B, S, W = q.shape
    n, _, d = tables.shape
    assert n == (2 if rotary_dim in (None, d) else 3), (n, d, rotary_dim)
    more = {} if n == 2 else {"shift": rotary_dim // 2}
    # q and k of different widths (grouped queries): a third grid axis over
    # the lanes, as many programs as divide both head counts (a key head and
    # its query heads each, where the key heads divide the query heads).  The
    # body is unrolled over its block's heads, and one that walked the 64 + 8
    # of a Laguna window layer took 3.6 s to trace
    parts = math.gcd(W // d, k.shape[2] // d) if k.shape[2] != W else 1
    widths = [x.shape[2] // parts for x in (q, k)]
    ts = fit(S, max(tile // (widths[0] * q.dtype.itemsize), ROWS), ROWS)
    # q's rows and k's, each as wide as it is; the tables' block stays the
    # same over the batch and the lanes, so it is fetched once
    blocks = [pl.BlockSpec((None, ts, w), lambda s, b, g=0: (b, s, g))
              for w in widths]
    return pl.pallas_call(
        functools.partial(_kernel, chunk=fit(ts, max(chunk, ROWS), ROWS),
                          backward=backward, **more),
        name=name, grid=(S // ts, B) + ((parts,) if parts > 1 else ()),
        in_specs=blocks + [pl.BlockSpec((n, ts, d),
                                        lambda s, b, g=0: (0, s, 0))],
        out_specs=blocks,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype)],
        compiler_params=params(
            interpret, ("parallel",) * (2 + (parts > 1))),
        interpret=interpret,
    )(q, k, tables)


_STATIC = ("interpret", "tile", "chunk", "rotary_dim")


@functools.partial(jax.jit, static_argnames=_STATIC)
def hetu_rope_fwd(q, k, tables, *, interpret, tile=TILE, chunk=CHUNK,
                  rotary_dim=None):
    """``q [B, S, H d]``, ``k [B, S, KV d]`` and the f32 ``tables [2, S, d]``
    (``cos``, ``sin±``), or ``[3, S, d]`` (``cos``, ``sA``, ``sB``) where only
    the first ``rotary_dim < d`` lanes of a head turn -> the rotated ``(q,
    k)`` in their type."""
    return _call("hetu_rope_fwd", False, q, k, tables, interpret, tile, chunk,
                 rotary_dim)


@functools.partial(jax.jit, static_argnames=_STATIC)
def hetu_rope_bwd(dq, dk, tables, *, interpret, tile=TILE, chunk=CHUNK,
                  rotary_dim=None):
    """The cotangents of ``hetu_rope_fwd``'s ``q``, ``k`` from its
    results'."""
    return _call("hetu_rope_bwd", True, dq, dk, tables, interpret, tile,
                 chunk, rotary_dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def rope(q, k, tables, rotary_dim=None):
    """The rotation through the kernel pair: ``q [B, S, H d]``, ``k [B, S,
    KV d]``, ``tables`` (``hetu_rope_fwd``) -> ``(q, k)`` rotated."""
    return tuple(hetu_rope_fwd(q, k, tables, rotary_dim=rotary_dim,
                               interpret=dispatch.interpret()))


def _rope_fwd(q, k, tables, rotary_dim):
    return rope(q, k, tables, rotary_dim), tables


def _rope_bwd(rotary_dim, tables, g):
    dq, dk = hetu_rope_bwd(*g, tables, rotary_dim=rotary_dim,
                           interpret=dispatch.interpret())
    return dq, dk, None                 # the tables: no gradient


rope.defvjp(_rope_fwd, _rope_bwd)
