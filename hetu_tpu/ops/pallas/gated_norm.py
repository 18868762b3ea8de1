"""The recurrent mixers' gated RMS norm as two Pallas kernels
(``ops/gated_norm.py`` has the node, the rule's call and the windows; the
layers' ``_out`` are the ``jax.numpy`` forms these are held to).

Between a mixer's scan and its output product stand a gate and an RMS norm
over groups of ``width`` channels of ``o [B, S, C]``, in one of two orders that
are the layers' mathematics:

    Gated DeltaNet (``gate_first`` False, width a value head):
        n = o rsqrt(mean(o^2) + eps)            f32, rounded to ``o``'s type
        y = (scale n) silu(z)                   scale n in the types' product
                                                type, then f32, one cast
    Mamba-2 (``gate_first`` True, width ``C / groups``; all 4,096 channels
    where the model has one group):
        g = o silu(z)                           f32
        y = g rsqrt(mean(g^2) + eps) scale      f32, one cast

Both are pure memory traffic: a pass has to read ``o`` and ``z`` once and
write ``y`` once.  XLA runs them as f32 elementwise passes with an f32
``[B, S, C]`` or two between them, a ``[.., groups, width]`` view of its own
layout for the sum, and a copy of ``z`` out of the projection's output; here a
``[rows, lanes]`` block of whole groups stays in VMEM, the sum of squares runs
along its lanes in f32 and the rounding is where the ``jax.numpy`` form has
it.

``hetu_gated_norm_fwd``: grid (lane blocks, batch, row blocks).  A program
reads its block of ``o`` and of ``z`` once and walks it in chunks of rows,
group by group.  ``z`` is read where it lies: channel ``c`` of it is lane
``lo + c // span * stride + c % span`` of a wider array (``Window``: Mamba-2's
first ``C`` lanes of ``zxbcdt``; DeltaNet's 256 lanes at offset 512 of each
key head's 768 in ``qkvz``), which a block spec's lane-block index reaches
wherever the block's lanes divide ``lo``, ``span`` and ``stride``.

``hetu_gated_norm_bwd``: the same grid.  Nothing is kept by the forward pass
but its operands: a chunk's statistics are rebuilt in f32 from ``o`` and
``z``, ``do`` and ``dz`` are written once in the operands' types (``dz`` as
``[B, S, C]``: XLA puts it into the wide array's gradient as it does a
slice's), and the scale's cotangent accumulates as f32 sums over rows in an
output block ``[8, lanes]`` that stays in VMEM over the batch and the
sequence; XLA adds the eight sublanes (and DeltaNet's heads) up.  No f32
``[B, S, C]`` array reaches HBM.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import dispatch
from .common import fit, params

#: rows a chunk and a block are multiples of: a bf16 tile's sublanes
ROWS = 16
#: lanes a block at most (fewer where a window's runs are shorter: DeltaNet's
#: ``z`` comes 256 lanes a key head), bytes of a block of ``o`` and f32
#: elements of a group's chunk.  The backward pass holds five such blocks
#: twice (double buffers): 10 MiB of the 16 MiB a kernel may use by default,
#: whatever the width.  v5e at bf16 ``[1, 8192, 4096]`` (PERF.md, PR 44; HBM
#: floors 0.23 / 0.39 ms), forward / backward: one group of 4,096 lanes 0.22 /
#: 0.42 ms (blocks of 512 KiB 0.25 / 0.42, of 256 KiB 0.27 / 0.47); groups of
#: 512 in blocks of 4,096 lanes 0.24 / 0.41, of 1,024 lanes 0.26 / 0.44 (at
#: chunks of 8,192 elements 0.36 / 0.57), of 512 lanes 0.60 / 0.98; groups of
#: 128 in their 256 lanes 0.37 / 0.57 (chunks of 8,192 elements 0.39 / 0.63, of
#: 4,096 0.55 / 0.95; blocks of 128 lanes 0.57 / 0.94).
LANES, TILE, CHUNK = 4096, 2 ** 20, 16 * 1024

_F32 = jnp.float32


class Window(NamedTuple):
    """Where ``z``'s channels lie in a wider array: channel ``c`` at lane
    ``lo + c // span * stride + c % span``."""
    lo: int
    span: int
    stride: int


def _lanes(channels, width, window, most):
    """The lanes of a block: the most whole groups up to ``most`` lanes that
    tile the channels and, with a window, its runs (0 where none does)."""
    span = channels if window is None else math.gcd(*window)
    return fit(math.gcd(channels, span), max(most, width), width)


def in_place(channels, width, window):
    """Whether the kernels can read ``z`` through ``window`` where it lies."""
    return _lanes(channels, width, window, LANES) > 0


def unsupported(o, z, scale, *, width):
    """Why the kernels do not take the gated norm's operands (``z`` the
    compact ``[B, S, C]`` or the wider array a window reads), or None when
    they do."""
    C = o.shape[-1]
    if width % 128 or C % width:
        return "width_not_128_aligned"
    if scale.shape[-1] not in (width, C):
        return "scale_not_a_group_or_all"
    types = {jnp.dtype(t.dtype) for t in (o, z)}
    if len(types) > 1:
        return "dtype:mixed"
    if not types <= {jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)}:
        return f"dtype:{types.pop().name}"
    if o.shape[1] % ROWS:
        return f"seq_not_{ROWS}_aligned"
    if width * ROWS * o.dtype.itemsize > TILE:
        return "group_wider_than_a_block"
    return None


def _silu(zf):
    sig = jax.nn.sigmoid(zf)
    return sig, zf * sig


def _unit(x, eps):
    """``x`` over its RMS along the lanes, and the reciprocal RMS."""
    r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * r, r


def _unit_bwd(n, r, dn):
    return r * (dn - n * jnp.mean(dn * n, -1, keepdims=True))


def _scaled(w, n, dtype):
    """``scale n`` rounded as the ``jax.numpy`` form rounds it: ``n`` to
    ``o``'s type, the product to the two types' (a product of two bf16 numbers
    is exact in f32, so one rounding of it is the bf16 product)."""
    nb = n.astype(dtype).astype(_F32)
    out = jnp.promote_types(w.dtype, dtype)
    return nb, (w.astype(_F32) * nb).astype(out).astype(_F32)


def _group_fwd(o, z, w, *, gate_first, eps):
    """One group's chunk ``[rows, width]`` (``w [1, width]``)."""
    of = o.astype(_F32)
    _, silu = _silu(z.astype(_F32))
    if gate_first:
        n, _ = _unit(of * silu, eps)
        return (n * w.astype(_F32)).astype(o.dtype)
    n, _ = _unit(of, eps)
    return (_scaled(w, n, o.dtype)[1] * silu).astype(o.dtype)


def _group_bwd(o, z, w, dy, *, gate_first, eps):
    """``(do, dz, the scale's cotangent row by row)`` of one group's chunk,
    all f32."""
    of, zf, dyf, wf = (t.astype(_F32) for t in (o, z, dy, w))
    sig, silu = _silu(zf)
    dsilu = sig * (1.0 + zf * (1.0 - sig))
    if gate_first:
        n, r = _unit(of * silu, eps)
        dg = _unit_bwd(n, r, dyf * wf)
        return dg * silu, dg * of * dsilu, dyf * n
    n, r = _unit(of, eps)
    nb, s = _scaled(w, n, o.dtype)
    ds = dyf * silu
    return _unit_bwd(n, r, ds * wf), dyf * s * dsilu, ds * nb


def _walk(rows, lanes, *, chunk, width, body):
    """``body(rows of a chunk, lanes of a group)`` over a ``[rows, lanes]``
    block."""
    import jax.experimental.pallas as pl

    def step(i, carry):
        at = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        for j in range(lanes // width):
            body(at, slice(j * width, (j + 1) * width))
        return carry
    jax.lax.fori_loop(0, rows // chunk, step, 0)


def _fwd_kernel(o_ref, z_ref, w_ref, y_ref, *, width, chunk, **how):
    def body(at, group):
        y_ref[at, group] = _group_fwd(o_ref[at, group], z_ref[at, group],
                                      w_ref[:, group], **how)
    _walk(*o_ref.shape, chunk=chunk, width=width, body=body)


def _bwd_kernel(o_ref, z_ref, w_ref, dy_ref, do_ref, dz_ref, dw_ref, *, width,
                chunk, **how):
    import jax.experimental.pallas as pl

    @pl.when(jnp.logical_and(pl.program_id(1) == 0, pl.program_id(2) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def body(at, group):
        do, dz, dw = _group_bwd(o_ref[at, group], z_ref[at, group],
                                w_ref[:, group], dy_ref[at, group], **how)
        do_ref[at, group] = do.astype(do_ref.dtype)
        dz_ref[at, group] = dz.astype(dz_ref.dtype)
        dw_ref[:, group] += dw.reshape(chunk // 8, 8, width).sum(0)
    _walk(*o_ref.shape, chunk=chunk, width=width, body=body)


def _plan(o, width, window, lanes, tile, chunk):
    """A chunk's rows, the grid, and the block specs of ``o`` (and every
    ``[B, S, C]`` array), of ``z`` and of one of ``k`` rows a channel (the
    scale, its cotangent's sums)."""
    import jax.experimental.pallas as pl
    B, S, C = o.shape
    tl = _lanes(C, width, window, lanes)
    ts = fit(S, max(tile // (tl * o.dtype.itemsize), ROWS), ROWS)
    lo, span, stride = window or (0, C, C)
    z_lane = lambda c: (lo + c * tl // span * stride + c * tl % span) // tl
    return (fit(ts, max(chunk // width, ROWS), ROWS), (C // tl, B, S // ts),
            pl.BlockSpec((None, ts, tl), lambda c, b, s: (b, s, c)),
            pl.BlockSpec((None, ts, tl), lambda c, b, s: (b, s, z_lane(c))),
            lambda k: pl.BlockSpec((k, tl), lambda c, b, s: (0, c)))


_STATIC = ("width", "gate_first", "eps", "window", "interpret", "lanes",
           "tile", "chunk")


@functools.partial(jax.jit, static_argnames=_STATIC)
def hetu_gated_norm_fwd(o, z, w, *, width, gate_first, eps, window, interpret,
                        lanes=LANES, tile=TILE, chunk=CHUNK):
    """``o [B, S, C]``, ``z`` as wide as ``window`` says (``[B, S, C]``
    without one), ``w [1, C]`` -> ``y [B, S, C]`` in ``o``'s type."""
    import jax.experimental.pallas as pl
    chunk, grid, block, z_block, row = _plan(o, width, window, lanes, tile,
                                             chunk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, width=width, chunk=chunk,
                          gate_first=gate_first, eps=eps),
        name="hetu_gated_norm_fwd", grid=grid,
        in_specs=[block, z_block, row(1)], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=params(interpret, ("parallel",) * 3),
        interpret=interpret,
    )(o, z, w)


@functools.partial(jax.jit, static_argnames=_STATIC)
def hetu_gated_norm_bwd(o, z, w, dy, *, width, gate_first, eps, window,
                        interpret, lanes=LANES, tile=TILE, chunk=CHUNK):
    """``do``, ``dz [B, S, C]`` in the operands' types and ``dw [8, C]`` f32:
    the scale's cotangent summed over all rows but for its eight sublanes."""
    import jax.experimental.pallas as pl
    chunk, grid, block, z_block, row = _plan(o, width, window, lanes, tile,
                                             chunk)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, width=width, chunk=chunk,
                          gate_first=gate_first, eps=eps),
        name="hetu_gated_norm_bwd", grid=grid,
        in_specs=[block, z_block, row(1), block],
        out_specs=[block, block, row(8)],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(o.shape, z.dtype),
                   jax.ShapeDtypeStruct((8, o.shape[2]), _F32)],
        compiler_params=params(interpret,
                                ("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(o, z, w, dy)


def take(wide, window, channels):
    """``z [B, S, channels]`` out of the wider array: the slice the kernels
    spare, and (transposed) where ``dz`` goes in the wide array's gradient."""
    if window is None:
        return wide
    lo, span, stride = window
    B, S, _ = wide.shape
    runs = channels // span
    if runs == 1:
        return wide[..., lo:lo + span]
    return wide[..., :runs * stride].reshape(B, S, runs, stride)[
        ..., lo:lo + span].reshape(B, S, channels)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _norm(o, z, w, width, gate_first, eps, window):
    return hetu_gated_norm_fwd(o, z, w, width=width, gate_first=gate_first,
                               eps=eps, window=window,
                               interpret=dispatch.interpret())


def _norm_fwd(o, z, w, width, gate_first, eps, window):
    return _norm(o, z, w, width, gate_first, eps, window), (o, z, w)


def _norm_bwd(width, gate_first, eps, window, res, dy):
    o, z, w = res
    do, dz, dw = hetu_gated_norm_bwd(
        o, z, w, dy, width=width, gate_first=gate_first, eps=eps,
        window=window, interpret=dispatch.interpret())
    if window is not None:       # the window's gradient in the array's
        dz, = jax.linear_transpose(
            lambda t: take(t, window, o.shape[2]), z)(dz)
    return do, dz, dw.sum(0, keepdims=True).astype(w.dtype)


_norm.defvjp(_norm_fwd, _norm_bwd)


def gated_norm(o, z, scale, *, width, gate_first, eps, window=None):
    """The gated norm through the kernel pair: ``o [B, S, C]``; ``z [B, S,
    C]`` or, with a ``window``, the wider array that holds it (read in place
    where the blocks reach it, else sliced first); ``scale [width]`` a group
    or ``[C]`` -> ``y [B, S, C]`` in ``o``'s type."""
    C = o.shape[2]
    if window is not None and not in_place(C, width, window):
        z, window = take(z, window, C), None
    w = jnp.tile(scale, C // scale.shape[-1]).reshape(1, C)
    return _norm(o, z, w, width, gate_first, float(eps), window)
