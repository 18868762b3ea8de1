"""The mixers' short causal convolution with its SiLU as two Pallas kernels
(``ops/causal_conv.py`` has the function and the ``jax.numpy`` form these are
held to).

``y_t = silu(sum_j w_j x_(t - K + 1 + j) + b)`` over ``x [B, S, C]`` is pure
memory traffic: a pass has to read ``x`` once and write ``y`` once.  XLA pads a
copy of ``x``, reads it at ``K`` sublane-unaligned offsets and, in the backward
pass, sends an f32 ``[B, S, C]`` array through HBM; here a tile stays in VMEM
and the taps are shifts along its sublanes.

``hetu_conv_fwd``: grid (channel tiles, batch, sequence tiles), the last axis
sequential.  A program reads its ``[rows, lanes]`` tile of ``x`` once and walks
it in chunks of rows that stay in registers: a chunk as f32, its
``K - 1`` shifts (a rotation of the chunk along the sublanes whose first rows
are taken from the eight rows before it: the chunk before, or across a tile's
edge a VMEM scratch the tile before left, zeros at position 0), the ``K``
products summed in f32 from the oldest tap to the newest as the ``jax.numpy``
form does, the bias, SiLU, one cast, one write.

``hetu_conv_bwd``: the same grid with the sequence tiles and a tile's chunks
from the last to the first.  Nothing is kept by the forward pass but its
operands: a chunk's pre-activation is rebuilt in registers (the rows before a
tile come as a second, ``HALO``-row block of ``x``), ``dpre = dy silu'(pre)``,
``dx_u = sum_j w_j dpre_(u + K - 1 - j)`` with the rows after a chunk carried
from the chunk after it (zeros after the last position), written once.  ``dw
[K, C]`` and ``db [C]`` are sums over rows, which are vector adds: they
accumulate in f32 output blocks ``[.., 8, lanes]`` that stay in VMEM over the
batch and the sequence, and XLA adds the eight sublanes up.  No f32 ``[S, C]``
array reaches HBM.

A window of a wider array (``lo`` and the width multiples of 128 lanes: the
Mamba-2 layers' ``xBC`` inside the projection's output) is read in place: the
blocks of ``x`` are given by their first element, so the window's first lane
need not be a multiple of a tile's lanes (Granite 4.0-H: 4,096 and tiles of
4,352 / 17 = 256).  ``dx`` is the window's own, and XLA pads it into the wide
array's gradient as it does a slice's.  Against XLA's slice first and the
kernels on the copy, in the step (v5e, PERF.md, PR 38): 245.7 against 247.8
ms on the Nemotron-H cell, 387.9 against 391.9 on the Granite cell.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dispatch
from .common import fit, params

#: rows of ``x`` before a tile that the backward pass reads with it (a bf16
#: tile is 16 rows; the last ``K - 1 <= 8`` are used).  Chunks and tiles are
#: multiples of it.
HALO = 16
#: lanes a program at most, bytes of its tile of ``x`` and f32 elements of a
#: chunk: 1,024 rows x 512 lanes of bf16 a tile, 32 x 512 a chunk (16 f32
#: registers a value of the chain), more rows where the lanes are fewer.
#: v5e at bf16 [1, 8192, 8192] (PERF.md, PR 38; HBM floors 0.33 / 0.49 ms):
#: 0.48 ms forward / 0.86 backward; tiles of 512 rows 0.53 / 0.88, of 256
#: lanes 0.66 / 0.98 at 512 rows and 0.53 / 0.88 at 2,048, of 128 lanes 0.78 /
#: 1.14; chunks of 16 and 64 rows 0.58 / 0.98 and 0.55 / 0.92.  The shifts as
#: loads at row offsets from an f32 ``[rows + 8, lanes]`` scratch: 0.57
#: forward where the rotations took 0.53 at the same tile.
LANES, TILE, CHUNK = 512, 2 ** 20, 32 * 512

_F32 = jnp.float32


def unsupported(x, w, b=None, window=None, act="silu"):
    """Why the kernels do not take ``causal_conv``'s operands, or None when
    they do."""
    if act != "silu":                   # SiLU is inside both kernels
        return f"act:{str(act).lower()}"
    lo, hi = window or (0, x.shape[-1])
    if lo % 128 or (hi - lo) % 128:
        return "channels_not_128_aligned"
    if w.shape[0] > 8:
        return "taps>8"
    if jnp.dtype(x.dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return f"dtype:{jnp.dtype(x.dtype).name}"
    if x.shape[1] % HALO:
        return f"seq_not_{HALO}_aligned"
    return None


def _down(cur, before, s):
    """``out[t] = cur[t - s]``, the first ``s`` rows from the end of the eight
    rows ``before``."""
    from jax.experimental.pallas import tpu as pltpu
    rc = pltpu.roll(cur, s, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, before.shape, 0)
    head = jnp.where(row < s, pltpu.roll(before, s, 0), rc[:8])
    return jnp.concatenate([head, rc[8:]], 0)


def _up(cur, after, s):
    """``out[t] = cur[t + s]``, the last ``s`` rows from the start of the
    eight rows ``after``."""
    from jax.experimental.pallas import tpu as pltpu
    r = cur.shape[0]
    rc = pltpu.roll(cur, r - s, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, after.shape, 0)
    tail = jnp.where(row >= 8 - s, pltpu.roll(after, 8 - s, 0), rc[r - 8:])
    return jnp.concatenate([rc[:r - 8], tail], 0)


def _tap(w_ref, j):
    return w_ref[j:j + 1, :].astype(_F32)


def _pre(cur, before, w_ref, b_ref):
    """The chunk's shifted inputs, newest tap last, and its pre-activation:
    the products summed from the oldest tap on, then the bias."""
    K = w_ref.shape[0]
    xs = [_down(cur, before, K - 1 - j) for j in range(K - 1)] + [cur]
    pre = xs[0] * _tap(w_ref, 0)
    for j in range(1, K):
        pre = pre + xs[j] * _tap(w_ref, j)
    if b_ref is not None:
        pre = pre + b_ref[...].astype(_F32)
    return xs, pre


def _fwd_kernel(*refs, bias, chunk):
    import jax.experimental.pallas as pl
    x_ref, w_ref, *rest, y_ref, edge = refs
    b_ref = rest[0] if bias else None
    n = x_ref.shape[1] // chunk

    @pl.when(pl.program_id(2) == 0)
    def _():
        edge[...] = jnp.zeros_like(edge)

    def step(i, before):
        rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        cur = x_ref[0, rows, :].astype(_F32)
        _, pre = _pre(cur, before, w_ref, b_ref)
        y_ref[rows, :] = (pre * jax.nn.sigmoid(pre)).astype(y_ref.dtype)
        return cur[chunk - 8:]
    edge[...] = jax.lax.fori_loop(0, n, step, edge[...])


def _bwd_kernel(*refs, bias, chunk):
    import jax.experimental.pallas as pl
    x_ref, halo_ref, w_ref, *rest, edge = refs
    b_ref = rest.pop(0) if bias else None
    dy_ref, dx_ref, dw_ref, *rest = rest
    db_ref = rest[0] if bias else None
    K = w_ref.shape[0]
    n = x_ref.shape[1] // chunk
    first = pl.program_id(2) == pl.num_programs(2) - 1   # the walk is reversed

    @pl.when(pl.program_id(2) == 0)
    def _():
        edge[...] = jnp.zeros_like(edge)

    @pl.when(jnp.logical_and(pl.program_id(1) == 0, pl.program_id(2) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        if bias:
            db_ref[...] = jnp.zeros_like(db_ref)

    def rows_sum(t):                       # [chunk, lanes] -> [8, lanes]
        return t.reshape(chunk // 8, 8, t.shape[1]).sum(0)

    def step(m, after):
        i = n - 1 - m
        rows = pl.ds(pl.multiple_of(i * chunk, chunk), chunk)
        # the HALO rows before the chunk: the tile's own, the tile before's
        # for the first chunk, zeros before position 0
        own = x_ref[0, pl.ds(pl.multiple_of(
            jnp.maximum(i * chunk - HALO, 0), HALO), HALO), :]
        before = jnp.where(i > 0, own, halo_ref[0]).astype(_F32)
        before = jnp.where(jnp.logical_and(i == 0, first), 0.0, before)
        cur = x_ref[0, rows, :].astype(_F32)
        xs, pre = _pre(cur, before[HALO - 8:], w_ref, b_ref)
        sig = jax.nn.sigmoid(pre)
        dpre = dy_ref[rows, :].astype(_F32) * (
            sig * (1.0 + pre * (1.0 - sig)))
        dx = dpre * _tap(w_ref, K - 1)
        for j in range(K - 1):
            dx = dx + _up(dpre, after, K - 1 - j) * _tap(w_ref, j)
        for j in range(K):
            dw_ref[j] += rows_sum(dpre * xs[j])
        if bias:
            db_ref[...] += rows_sum(dpre)
        dx_ref[rows, :] = dx.astype(dx_ref.dtype)
        return dpre[:8]
    edge[...] = jax.lax.fori_loop(0, n, step, edge[...])


def _window(*block_and_map):
    """A block given by its first element, not its index: a window's first
    lane is a multiple of 128, not of the tile."""
    import jax.experimental.pallas as pl
    *block, at = block_and_map
    return pl.BlockSpec(tuple(pl.Element(n) for n in block), at)


def _plan(x, lo, width, lanes, tile, chunk):
    """Lanes and rows of a program's tile, a chunk's rows, the grid, the
    first lane of channel tile ``c`` in ``x``, and the block of ``k`` rows of
    taps (or of the bias)."""
    import jax.experimental.pallas as pl
    tc = fit(width, lanes, 128)
    ts = fit(x.shape[1], max(tile // (tc * x.dtype.itemsize), HALO), HALO)
    return (tc, ts, fit(ts, max(chunk // tc, HALO), HALO),
            (width // tc, x.shape[0], x.shape[1] // ts),
            lambda c: pl.multiple_of(lo + c * tc, 128),
            lambda k: pl.BlockSpec((k, tc), lambda c, b, s: (0, c)))


@functools.partial(jax.jit, static_argnames=(
    "lo", "width", "interpret", "lanes", "tile", "chunk"))
def hetu_conv_fwd(x, w, b, *, lo, width, interpret, lanes=LANES, tile=TILE,
                  chunk=CHUNK):
    """``x [B, S, C']``, ``w [K, C]``, ``b [1, C]`` or None -> ``y [B, S, C]``
    of the window ``[lo, lo + C)`` of ``x``'s channels."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, S, _ = x.shape
    K = w.shape[0]
    tc, ts, chunk, grid, lane, taps = _plan(x, lo, width, lanes, tile, chunk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bias=b is not None, chunk=chunk),
        name="hetu_conv_fwd", grid=grid,
        in_specs=[_window(1, ts, tc, lambda c, b, s: (b, s * ts, lane(c))),
                  taps(K)] + ([taps(1)] if b is not None else []),
        out_specs=pl.BlockSpec((None, ts, tc), lambda c, b, s: (b, s, c)),
        out_shape=jax.ShapeDtypeStruct((B, S, width), x.dtype),
        scratch_shapes=[pltpu.VMEM((8, tc), _F32)],
        compiler_params=params(interpret,
                                ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*((x, w) + ((b,) if b is not None else ())))


@functools.partial(jax.jit, static_argnames=(
    "lo", "width", "interpret", "lanes", "tile", "chunk"))
def hetu_conv_bwd(x, w, b, dy, *, lo, width, interpret, lanes=LANES,
                  tile=TILE, chunk=CHUNK):
    """``dx [B, S, C]`` of the window, ``dw [K, 8, C]`` f32 and, with a bias,
    ``db [8, C]`` f32: the sums over all positions but for their eight
    sublanes."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    B, S, _ = x.shape
    K = w.shape[0]
    bias = b is not None
    tc, ts, chunk, grid, lane, taps = _plan(x, lo, width, lanes, tile, chunk)
    at = lambda s: grid[2] - 1 - s
    tile = pl.BlockSpec((None, ts, tc), lambda c, b, s: (b, at(s), c))
    sums = [pl.BlockSpec((K, 8, tc), lambda c, b, s: (0, 0, c))] + (
        [pl.BlockSpec((8, tc), lambda c, b, s: (0, c))] if bias else [])
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, bias=bias, chunk=chunk),
        name="hetu_conv_bwd", grid=grid,
        in_specs=[_window(1, ts, tc, lambda c, b, s: (
                      b, at(s) * ts, lane(c))),
                  _window(1, HALO, tc, lambda c, b, s: (
                      b, pl.multiple_of(jnp.maximum(at(s) * ts - HALO, 0),
                                        HALO), lane(c))),
                  taps(K)] + ([taps(1)] if bias else []) + [tile],
        out_specs=[tile] + sums,
        out_shape=[jax.ShapeDtypeStruct((B, S, width), x.dtype),
                   jax.ShapeDtypeStruct((K, 8, width), _F32)] + (
                       [jax.ShapeDtypeStruct((8, width), _F32)]
                       if bias else []),
        scratch_shapes=[pltpu.VMEM((8, tc), _F32)],
        compiler_params=params(interpret,
                                ("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*((x, x, w) + ((b,) if bias else ()) + (dy,)))
    dx, dw, *db = out
    return dx, dw, (db[0] if bias else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv(x, w, b, lo, width):
    return hetu_conv_fwd(x, w, b, lo=lo, width=width,
                         interpret=dispatch.interpret())


def _conv_fwd(x, w, b, lo, width):
    return _conv(x, w, b, lo, width), (x, w, b)


def _conv_bwd(lo, width, res, dy):
    x, w, b = res
    dx, dw, db = hetu_conv_bwd(x, w, b, dy, lo=lo, width=width,
                               interpret=dispatch.interpret())
    if x.shape[2] != width:          # the window's gradient in the array's
        dx = jnp.pad(dx, ((0, 0), (0, 0), (lo, x.shape[2] - lo - width)))
    return (dx, dw.sum(1).astype(w.dtype),
            None if b is None else db.sum(0, keepdims=True).astype(b.dtype))


_conv.defvjp(_conv_fwd, _conv_bwd)


def conv(x, w, b=None, window=None):
    """``causal_conv`` through the kernel pair: ``x [B, S, C']``, ``w [K,
    C]``, ``b [C]`` or None, ``window = (lo, hi)`` the channels of ``x`` that
    are convolved (all of them without one) -> ``y [B, S, C]`` in ``x``'s
    type."""
    lo, hi = window or (0, x.shape[-1])
    return _conv(x, w, None if b is None else b.reshape(1, -1), lo, hi - lo)
