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

A norm a head in the same pass (PR 63: ``hetu_qk_norm_rope_fwd`` / ``_bwd``,
``norm_rope``; SDAR's layers, 32 query heads on 4 key heads of 128 at 16,384
positions).  On the ``[B, H, S, D]`` graph such a layer's norm, rotation,
casts, transposes and repeated key heads were 130 ms of a 550 ms step (PERF.md
section 6, PR 63); here the pair above gets a second body on the same grid,
blocks, chunk walk and ``[2, S, d]`` tables, and the first pair's text does
not change.  Forward, a head of a chunk: ``x`` in f32, ``x^ = x rsqrt(mean
x^2 + eps)`` ROUNDED to the operands' type, times its tensor's scale (``w [2,
d]`` f32: q's row, k's row) and ROUNDED again (``ops/nn.py _rms_norm``'s two
roundings: no rounding is dropped and none added), then the rotation of that
value in f32 and one store.  Backward: from the kept operands, the cotangents
turn back (``-sin±``), round as the normed value does, and ``dx = rstd (a - x^
mean(a x^))`` with ``a = g w`` a head; the scales' cotangents leave as one
``[2, d]`` f32 partial sum a program, added up outside.  ``rstd`` is made
again from ``x``; kept for the backward pass are the operands, the scales and
the tables, nothing f32 of width ``H d``.  ``norm_unsupported`` adds
``zero_centered`` and ``partial_rotation`` (forms the body does not take) to
the first pair's reasons.
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


def _grid(q, k, d, tile):
    """``(grid, blocks, rows)`` of a call on ``q [B, S, H d]`` and ``k [B, S,
    KV d]``: the programs, q's and k's ``BlockSpec`` and a block's rows."""
    import jax.experimental.pallas as pl
    B, S, W = q.shape
    # q and k of different widths (grouped queries): a third grid axis over
    # the lanes, as many programs as divide both head counts (a key head and
    # its query heads each, where the key heads divide the query heads).  The
    # body is unrolled over its block's heads, and one that walked the 64 + 8
    # of a Laguna window layer took 3.6 s to trace
    parts = math.gcd(W // d, k.shape[2] // d) if k.shape[2] != W else 1
    widths = [x.shape[2] // parts for x in (q, k)]
    ts = fit(S, max(tile // (widths[0] * q.dtype.itemsize), ROWS), ROWS)
    # q's rows and k's, each as wide as it is
    blocks = [pl.BlockSpec((None, ts, w), lambda s, b, g=0: (b, s, g))
              for w in widths]
    return (S // ts, B) + ((parts,) if parts > 1 else ()), blocks, ts


def _tables_block(n, ts, d):
    """The tables' block: the same over the batch and the lanes, so it is
    fetched once."""
    import jax.experimental.pallas as pl
    return pl.BlockSpec((n, ts, d), lambda s, b, g=0: (0, s, 0))


def _call(name, backward, q, k, tables, interpret, tile, chunk, rotary_dim):
    import jax.experimental.pallas as pl
    n, _, d = tables.shape
    assert n == (2 if rotary_dim in (None, d) else 3), (n, d, rotary_dim)
    more = {} if n == 2 else {"shift": rotary_dim // 2}
    grid, blocks, ts = _grid(q, k, d, tile)
    return pl.pallas_call(
        functools.partial(_kernel, chunk=fit(ts, max(chunk, ROWS), ROWS),
                          backward=backward, **more),
        name=name, grid=grid,
        in_specs=blocks + [_tables_block(n, ts, d)], out_specs=blocks,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype)],
        compiler_params=params(interpret, ("parallel",) * len(grid)),
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


# -- a norm a head, then the rotation: one pass ---------------------------------

#: rows of a chunk of the pair that norms first: a head-row's lane sums, its
#: ``rsqrt`` and its roll are a chain whose latency only more rows hide.  v5e,
#: bf16 ``[1, 16384, 4096]`` on ``[1, 16384, 512]`` (PERF.md, PR 63, call 63.1;
#: 20 calls chained in one jitted loop, whose carry costs each call about 0.5
#: ms: in the SDAR step a forward call is 1.11 ms and a backward one 1.27 at
#: 64 rows; forward / backward, ms, at blocks of 1 MiB): 32 rows 1.81 / 2.29,
#: 64 rows 1.61 / 1.77, 128 rows 1.21 / 1.39, 256 rows 1.01 / 1.13 (their
#: bytes at 819 GB/s: 0.37 / 0.55); blocks of 0.5 MiB the same to 0.05, and at
#: 2 MiB the backward body's spills no longer fit the scoped VMEM from 128 rows
NORM_CHUNK = 256


def norm_unsupported(q, k, wq, wk, *, head_dim, zero_centered=False,
                     rotary_dim=None):
    """Why the pair that norms a head first does not take ``q``, ``k [B, S, H
    d]`` under the scales ``wq``, ``wk [d]``, or None when it does: the rotary
    pair's reasons, the scales of the operands' type (``_rms_norm`` multiplies
    by the scale in the type the two promote to), and the two forms the body
    does not take."""
    if zero_centered:
        return "zero_centered"
    if rotary_dim not in (None, head_dim):
        return "partial_rotation"
    why = unsupported(q, k, head_dim=head_dim)
    if why is None and {jnp.dtype(w.dtype) for w in (wq, wk)} != {
            jnp.dtype(q.dtype)}:
        return "dtype:mixed"
    return why


def _rounded(x, dtype):
    """f32 ``x`` at ``dtype``'s precision."""
    return x.astype(dtype).astype(_F32)


def _rms(x, eps, dtype):
    """The first half of ``ops/nn.py _rms_norm`` on a head's f32 ``x [chunk,
    d]``: ``(x^, n, rstd)``, ``rstd = rsqrt(mean x^2 + eps) [chunk, 1]``, ``x^
    = x rstd`` as it is and ``n`` its value rounded to ``dtype``, back in
    f32."""
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps)
    xh = x * r
    return xh, _rounded(xh, dtype), r


def _norm_fwd_kernel(q_ref, k_ref, w_ref, t_ref, qo_ref, ko_ref, *, chunk,
                     eps):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    d = t_ref.shape[2]
    dtype = qo_ref.dtype

    def step(i, carry):
        at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        cos, sin = t_ref[0, at, :], t_ref[1, at, :]
        for row, (ref, out) in enumerate(((q_ref, qo_ref), (k_ref, ko_ref))):
            w = w_ref[row:row + 1, :]
            for h in range(ref.shape[1] // d):
                head = slice(h * d, (h + 1) * d)
                _, n, _ = _rms(ref[at, head].astype(_F32), eps, dtype)
                # the scale's product rounds too; the rotation reads that
                y = _rounded(n * w, dtype)
                out[at, head] = (y * cos + pltpu.roll(y, d // 2, 1) * sin
                                 ).astype(dtype)
        return carry
    jax.lax.fori_loop(0, q_ref.shape[0] // chunk, step, 0)


def _norm_bwd_kernel(q_ref, k_ref, gq_ref, gk_ref, w_ref, t_ref, dq_ref,
                     dk_ref, dw_ref, *, chunk, eps):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    d = t_ref.shape[2]
    dtype = dq_ref.dtype

    def step(i, acc):
        at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        cos, sin = t_ref[0, at, :], -t_ref[1, at, :]
        acc = list(acc)
        for row, (ref, g_ref, out) in enumerate((
                (q_ref, gq_ref, dq_ref), (k_ref, gk_ref, dk_ref))):
            w = w_ref[row:row + 1, :]
            for h in range(ref.shape[1] // d):
                head = slice(h * d, (h + 1) * d)
                g = g_ref[at, head].astype(_F32)
                # the normed value's cotangent: the rotation by the opposite
                # angle, in the type the value has
                g = _rounded(g * cos + pltpu.roll(g, d // 2, 1) * sin, dtype)
                xh, n, r = _rms(ref[at, head].astype(_F32), eps, dtype)
                # a scale's terms, eight rows deep: sums of whole tiles
                acc[row] = acc[row] + (g * n).reshape(-1, 8, d).sum(0)
                a = _rounded(g * w, dtype)
                out[at, head] = (r * (a - xh * jnp.mean(
                    a * xh, axis=1, keepdims=True))).astype(dtype)
        return tuple(acc)
    zero = jnp.zeros((8, d), _F32)
    acc = jax.lax.fori_loop(0, q_ref.shape[0] // chunk, step, (zero, zero))
    for row, terms in enumerate(acc):
        dw_ref[row:row + 1, :] = jnp.sum(terms, axis=0, keepdims=True)


def _norm_call(name, kernel, operands, w, tables, interpret, tile, chunk,
               eps, partial=False):
    """``operands``: q and k, or q, k and their results' cotangents; ``w [2,
    d]`` f32, q's scale and k's; with ``partial`` one more result, a program's
    ``[2, d]`` f32 sums of the scales' cotangents."""
    import jax.experimental.pallas as pl
    q, k = operands[:2]
    n, _, d = tables.shape
    assert n == 2 and w.shape == (2, d), (tables.shape, w.shape)
    grid, blocks, ts = _grid(q, k, d, tile)
    out_specs = list(blocks)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in (q, k)]
    if partial:
        out_specs.append(pl.BlockSpec((None,) * len(grid) + (2, d),
                                      lambda *program: program + (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct(grid + (2, d), _F32))
    return pl.pallas_call(
        functools.partial(kernel, chunk=fit(ts, max(chunk, ROWS), ROWS),
                          eps=eps),
        name=name, grid=grid,
        in_specs=blocks * (len(operands) // 2) + [
            pl.BlockSpec((2, d), lambda s, b, g=0: (0, 0)),
            _tables_block(n, ts, d)],
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=params(interpret, ("parallel",) * len(grid)),
        interpret=interpret,
    )(*operands, w, tables)


_NORM_STATIC = ("eps", "interpret", "tile", "chunk")


@functools.partial(jax.jit, static_argnames=_NORM_STATIC)
def hetu_qk_norm_rope_fwd(q, k, w, tables, *, eps, interpret, tile=TILE,
                          chunk=NORM_CHUNK):
    """``q [B, S, H d]``, ``k [B, S, KV d]``, the f32 ``w [2, d]`` (the scale
    of q's norm a head, then of k's) and the f32 ``tables [2, S, d]`` -> ``(q,
    k)``, each head normed (``_rms_norm``, both its roundings) and rotated, in
    their type."""
    return _norm_call("hetu_qk_norm_rope_fwd", _norm_fwd_kernel, [q, k], w,
                      tables, interpret, tile, chunk, eps)


@functools.partial(jax.jit, static_argnames=_NORM_STATIC)
def hetu_qk_norm_rope_bwd(q, k, gq, gk, w, tables, *, eps, interpret,
                          tile=TILE, chunk=NORM_CHUNK):
    """``hetu_qk_norm_rope_fwd``'s operands and its results' cotangents ->
    ``(dq, dk, partials [*grid, 2, d])``: f32 sums of the two scales'
    cotangents a program."""
    return _norm_call("hetu_qk_norm_rope_bwd", _norm_bwd_kernel,
                      [q, k, gq, gk], w, tables, interpret, tile, chunk, eps,
                      partial=True)


def _scales(wq, wk):
    return jnp.stack([wq, wk]).astype(_F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def norm_rope(q, k, wq, wk, tables, eps):
    """A norm a head (``wq``, ``wk [d]``, ``eps``) and the rotation through
    the kernel pair: ``q [B, S, H d]``, ``k [B, S, KV d]``, ``tables [2, S,
    d]`` -> ``(q, k)``."""
    return tuple(hetu_qk_norm_rope_fwd(q, k, _scales(wq, wk), tables, eps=eps,
                                       interpret=dispatch.interpret()))


def _norm_rope_fwd(q, k, wq, wk, tables, eps):
    return norm_rope(q, k, wq, wk, tables, eps), (q, k, wq, wk, tables)


def _norm_rope_bwd(eps, kept, g):
    q, k, wq, wk, tables = kept
    dq, dk, partials = hetu_qk_norm_rope_bwd(
        q, k, *g, _scales(wq, wk), tables, eps=eps,
        interpret=dispatch.interpret())
    dw = partials.reshape(-1, *partials.shape[-2:]).sum(0)
    return (dq, dk, dw[0].astype(wq.dtype), dw[1].astype(wk.dtype),
            None)                       # the tables: no gradient


norm_rope.defvjp(_norm_rope_fwd, _norm_rope_bwd)
