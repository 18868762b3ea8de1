"""The hyper-connections' maps and two mixes as two Pallas kernel pairs
(``layers/hyper_connection.py`` has the mathematics, the ``jax.numpy`` forms
these are held to, the nodes and the rule's call).

A sublayer reads the ``n`` residual streams ``X [tokens, n C]`` twice: before
its function (the maps and ``u = Hpre X``) and behind it (``X' = Hres X +
Hpost^T y``).  XLA runs that as a pass for the RMS, one for ``X phi``, one for
``u`` and one for ``X'``, each stream sliced, cast to f32 and concatenated, and
the backward pass adds two cotangents of ``X`` in a pass of its own.  Here the
streams cross HBM once on each side of the function in each pass, and the cut
between the two ``custom_vjp``s is chosen for the backward pass::

    pre:  X, phi, b, alpha -> u, maps, R = Hres X        9 widths  (n + 1 + n)
    mix:  R, maps, y       -> X' = R + Hpost^T y         9 widths
    mix': dX', maps, y     -> dy, dHpost  (dR IS dX')    6 widths
    pre': X, dR, du, dmaps -> dX, dphi, db, dalpha      13 widths

(a width is ``C`` values a token; ``chipbench/flops_xing4.py hc_sublayer``
states 14 forward and 19 backward as due.)  ``mix``'s cotangent for ``R`` is
``dX'`` itself, so no array is made for it, and ``pre'`` writes ``dX`` whole:
``Hres^T dR + Hpre^T du``, the product's ``dz phi^T`` and the norm's ``c X``.

A program holds ``TILE`` whole token rows ``[TILE, n C]`` in VMEM.  Per-token
numbers live in two layouts.  As COLUMNS ``[TILE, 128]`` f32 (a token a
sublane, quantity ``k`` lane ``k``; lanes ``0 .. 2 n + n^2`` are the maps in
the layer's order): the form in which a number scales a token's row (one lane
broadcast a chunk of ``ROWS`` rows, reused over a stream's lane tiles) and in
which the maps reach HBM (``[tokens, 128]`` f32, 7% of a width: lane-dense
stores; the layer's ``[.., 2 n + n^2]`` is a slice of it).  As ROWS ``[KP,
TILE]`` f32 (a token a lane): the form of ``z = phi^T X^T`` (contracted as
``q k^T`` is in flash attention, so it needs no transposition), in which the
sigmoids and the Sinkhorn rounds are whole-vector additions and
multiplications with no cross-lane sum (a 4 x 4's column and row sums are sums
of four of its sixteen entry-vectors).  One ``[128, 128]`` transposition takes
the per-token sums of squares and inner products from columns to rows, one
takes the maps (or ``dz`` and the norm's coefficient) back.

The Sinkhorn rounds are not differentiated by hand: the backward kernel traces
``jax.vjp`` of ``_sinkhorn_rows`` (the KDA kernels' way).  ``phi``'s cotangent
is an f32 ``[KP, n C]`` block resident over the token grid, ``b``'s and
``alpha``'s are f32 ``[KP, TILE]`` partial sums that XLA adds up.

Precision: the streams stay the compute type, every product has an f32 result,
the maps, the rounds and both mixes accumulate in f32.  ``u`` and ``R`` are
cast once each, so ``X' = R + Hpost^T y`` carries one more rounding to the
compute type than the ``jax.numpy`` form, which rounds the whole sum once: the
price of ``R`` crossing HBM in the streams' type (the Xing4.0 cell's
``logits_gap`` reads 6% and its ``hc_res_gap`` 13% above the form's, PERF.md
section 6, PR 57).  A round divides by multiplying with ``1 / (sum + eps)`` (a
division a sum, not a division an entry).
"""

from __future__ import annotations

import functools
import operator
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import dispatch
from .common import NN, NT, chunk_rows, dot, params, walk

#: tokens a program (the side of the transpositions) and rows a chunk of the
#: mixing loops (a bf16 tile's sublanes)
TILE, ROWS, LANE = 128, 16, 128
#: scoped VMEM a kernel may use at most (a v5e core has 128 MiB)
VMEM_MOST = 100 * 2 ** 20

_F32 = jnp.float32


def width(n):
    """Numbers a token the maps are: ``Hpre``, ``Hpost``, ``Hres``."""
    return 2 * n + n * n


def _kp(n):
    """Rows of the row layout: the maps and one more (the sum of squares),
    padded to whole sublane tiles."""
    return -(-(width(n) + 1) // 8) * 8


def _vmem(n, c, itemsize):
    """Bytes of the backward kernel's blocks (double-buffered) and scratch."""
    row = n * c
    blocks = TILE * (3 * row + c) * itemsize + 3 * TILE * LANE * 4 \
        + _kp(n) * row * (itemsize + 4)
    return 2 * blocks + TILE * row * 4 + 2 * TILE * LANE * 4


def unsupported(x, n):
    """Why the kernels do not take the streams ``x [.., n C]``, or None."""
    c = x.shape[-1] // n
    if x.shape[-1] != n * c or c % LANE:
        return "stream_not_whole_lane_tiles"
    if jnp.dtype(x.dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return f"dtype:{jnp.dtype(x.dtype).name}"
    if _kp(n) + 8 > LANE:
        return "maps_wider_than_a_lane_tile"
    if _vmem(n, c, x.dtype.itemsize) + (8 << 20) > VMEM_MOST:
        return "rows_exceed_vmem"
    return None


def _sum(terms):
    return functools.reduce(operator.add, terms)


# -- the maps, a token a lane ------------------------------------------------------

def _sinkhorn_rows(logits, *, n, iters, eps, clamp):
    """``layers/hyper_connection.py sinkhorn`` on the ``n^2`` entry-vectors
    ``[1, T]`` of a tile's matrices, row by row."""
    m = [jnp.exp(jnp.clip(v, clamp[0], clamp[1])) for v in logits]
    for _ in range(iters):
        r = [1.0 / (_sum([m[i * n + j] for i in range(n)]) + eps)
             for j in range(n)]
        m = [m[i * n + j] * r[j] for i in range(n) for j in range(n)]
        r = [1.0 / (_sum([m[i * n + j] for j in range(n)]) + eps)
             for i in range(n)]
        m = [m[i * n + j] * r[i] for i in range(n) for j in range(n)]
    return m


def _stack(rows, like):
    """Entry-vectors ``{k: [1, T]}`` as rows ``k`` of a ``like``-shaped array,
    zeros elsewhere."""
    at = jax.lax.broadcasted_iota(jnp.int32, like.shape, 0)
    out = jnp.zeros_like(like)
    for k, row in rows.items():
        out = jnp.where(at == k, row, out)
    return out


def _logits(z, ssq, ab, *, row, eps):
    """``(zn, inv, logits)`` of a tile: ``z [KP, T]`` f32, ``ssq [1, T]`` the
    rows' sums of squares, ``ab [KP, 2]`` the gain and the bias a row."""
    inv = jax.lax.rsqrt(ssq * (1.0 / row) + eps)
    zn = z * inv
    return zn, inv, ab[:, 0:1] * zn + ab[:, 1:2]


def _maps_rows(logits, *, n, iters, eps, clamp):
    """The maps ``[KP, T]`` of a tile's logits (rows beyond them zero), and
    what the backward pass reads again: the sigmoids, and ``pull`` of the
    rounds."""
    k = width(n)
    sig = jax.nn.sigmoid(logits)
    res, pull = jax.vjp(
        functools.partial(_sinkhorn_rows, n=n, iters=iters, eps=eps,
                          clamp=clamp),
        [logits[i:i + 1] for i in range(2 * n, k)])
    at = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    maps = jnp.where(at < n, sig, jnp.where(at < 2 * n, 2.0 * sig, _stack(
        {2 * n + i: r for i, r in enumerate(res)}, logits)))
    return maps, sig, pull


def _maps_rows_bwd(dmaps, sig, pull, *, n):
    """The logits' cotangent ``[KP, T]`` of the maps'."""
    k = width(n)
    dres = pull([dmaps[i:i + 1] for i in range(2 * n, k)])[0]
    at = jax.lax.broadcasted_iota(jnp.int32, dmaps.shape, 0)
    dsig = dmaps * sig * (1.0 - sig)
    return jnp.where(at < n, dsig, jnp.where(at < 2 * n, 2.0 * dsig, _stack(
        {2 * n + i: r for i, r in enumerate(dres)}, dmaps)))


def _to_cols(rows):
    """``[KP, T] -> [T, 128]``: row ``k`` becomes lane ``k``."""
    kp, t = rows.shape
    if kp < LANE:
        rows = jnp.concatenate([rows, jnp.zeros((LANE - kp, t), _F32)], 0)
    return rows.T


def _lane(k, value):
    """``value [ROWS, 1]`` at lane ``k`` of a ``[ROWS, 128]`` of zeros."""
    at = jax.lax.broadcasted_iota(jnp.int32, (value.shape[0], LANE), 1)
    return jnp.where(at == k, value, 0.0)


def _spread(cols, k):
    """Lane ``k`` of ``cols [ROWS, 128]`` on every lane."""
    return jnp.broadcast_to(cols[:, k:k + 1], cols.shape)


def _chunks(tile, body):
    """``body(rows)`` over a tile's chunks of ``ROWS`` rows."""
    walk(tile // ROWS, lambda i: body(chunk_rows(i, ROWS)))


def _scales(cols, n):
    """``Hpre [n]`` and ``Hres [n][n]`` of a chunk's ``cols``, each on every
    lane."""
    return ([_spread(cols, j) for j in range(n)],
            [[_spread(cols, 2 * n + i * n + j) for j in range(n)]
             for i in range(n)])


def _lanes(c):
    """A stream's lane tiles."""
    return [slice(lo, lo + LANE) for lo in range(0, c, LANE)]


def _at(j, c, lanes):
    """``lanes`` of stream ``j``."""
    return slice(j * c + lanes.start, j * c + lanes.stop)


def _rowsum(x):
    return jnp.sum(x, axis=1, keepdims=True)


# -- before the function -----------------------------------------------------------

def _pre_fwd_kernel(x_ref, phit_ref, ab_ref, u_ref, maps_ref, r_ref, *, n,
                    iters, eps, clamp):
    tile, row = x_ref.shape
    c, kp = row // n, phit_ref.shape[0]

    def squares(rows):
        acc = jnp.zeros((ROWS, LANE), _F32)
        for j in range(n):
            for lanes in _lanes(c):
                xf = x_ref[rows, _at(j, c, lanes)].astype(_F32)
                acc = acc + xf * xf
        maps_ref[rows, :] = _lane(kp - 1, _rowsum(acc))
    _chunks(tile, squares)

    ssq = maps_ref[...].T[kp - 1:kp]
    z = dot(phit_ref[...], x_ref[...], NT)
    _, _, logits = _logits(z, ssq, ab_ref[...], row=row, eps=eps)
    maps, _, _ = _maps_rows(logits, n=n, iters=iters, eps=eps, clamp=clamp)
    maps_ref[...] = _to_cols(maps)

    def mixes(rows):
        pre, res = _scales(maps_ref[rows, :], n)
        for lanes in _lanes(c):
            xs = [x_ref[rows, _at(j, c, lanes)].astype(_F32)
                  for j in range(n)]
            u_ref[rows, lanes] = _sum(
                [pre[j] * xs[j] for j in range(n)]).astype(u_ref.dtype)
            for i in range(n):
                r_ref[rows, _at(i, c, lanes)] = _sum(
                    [res[i][j] * xs[j] for j in range(n)]).astype(r_ref.dtype)
    _chunks(tile, mixes)


def _pre_bwd_kernel(x_ref, phit_ref, ab_ref, maps_ref, dr_ref, du_ref,
                    dmaps_ref, dx_ref, dphit_ref, dlog_ref, cols_ref, dxz_ref,
                    *, n, iters, eps, clamp):
    import jax.experimental.pallas as pl
    tile, row = x_ref.shape
    c, kp = row // n, phit_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _():
        dphit_ref[...] = jnp.zeros_like(dphit_ref)
        dlog_ref[...] = jnp.zeros_like(dlog_ref)

    def products(rows):
        """Per token: the sum of squares, ``<du, X_j>``, ``<dR_i, X_j>``."""
        zero = jnp.zeros((ROWS, LANE), _F32)
        ssq, pre, res = zero, [zero] * n, [[zero] * n for _ in range(n)]
        for lanes in _lanes(c):
            xs = [x_ref[rows, _at(j, c, lanes)].astype(_F32)
                  for j in range(n)]
            duf = du_ref[rows, lanes].astype(_F32)
            for j in range(n):
                ssq = ssq + xs[j] * xs[j]
                pre[j] = pre[j] + duf * xs[j]
            for i in range(n):
                drf = dr_ref[rows, _at(i, c, lanes)].astype(_F32)
                for j in range(n):
                    res[i][j] = res[i][j] + drf * xs[j]
        cols = dmaps_ref[rows, :] + _lane(kp - 1, _rowsum(ssq))
        for j in range(n):
            cols = cols + _lane(j, _rowsum(pre[j]))
            for i in range(n):
                cols = cols + _lane(2 * n + i * n + j, _rowsum(res[i][j]))
        cols_ref[rows, :] = cols
    _chunks(tile, products)

    given = cols_ref[...].T[:kp]
    x = x_ref[...]
    ab = ab_ref[...]
    z = dot(phit_ref[...], x, NT)
    zn, inv, logits = _logits(z, given[kp - 1:kp], ab, row=row, eps=eps)
    _, sig, pull = _maps_rows(logits, n=n, iters=iters, eps=eps, clamp=clamp)
    dlogits = _maps_rows_bwd(given, sig, pull, n=n)
    dzn = ab[:, 0:1] * dlogits
    dz = dzn * inv
    coef = jnp.sum(dzn * z, axis=0, keepdims=True) * (
        inv * inv * inv * (-1.0 / row))
    dlog_ref[:kp] += dlogits
    dlog_ref[kp:] += dlogits * zn
    dphit_ref[...] += dot(dz.astype(x.dtype), x, NN)
    cols_ref[...] = _to_cols(
        jnp.concatenate([dz, jnp.broadcast_to(coef, (8, tile))], 0))
    dzc = cols_ref[:, :kp].astype(x.dtype)
    for j in range(n):
        at = slice(j * c, (j + 1) * c)
        dxz_ref[:, at] = dot(dzc, phit_ref[:, at], NN)

    def streams(rows):
        pre, res = _scales(maps_ref[rows, :], n)
        norm = _spread(cols_ref[rows, :], kp)
        for lanes in _lanes(c):
            duf = du_ref[rows, lanes].astype(_F32)
            drs = [dr_ref[rows, _at(i, c, lanes)].astype(_F32)
                   for i in range(n)]
            for j in range(n):
                at = _at(j, c, lanes)
                dx_ref[rows, at] = (
                    _sum([res[i][j] * drs[i] for i in range(n)])
                    + pre[j] * duf + norm * x_ref[rows, at].astype(_F32)
                    + dxz_ref[rows, at]).astype(dx_ref.dtype)
    _chunks(tile, streams)


# -- behind the function -----------------------------------------------------------

def _mix_fwd_kernel(r_ref, maps_ref, y_ref, out_ref, *, n):
    tile, c = y_ref.shape

    def body(rows):
        cols = maps_ref[rows, :]
        post = [_spread(cols, n + i) for i in range(n)]
        for lanes in _lanes(c):
            yf = y_ref[rows, lanes].astype(_F32)
            for i in range(n):
                at = _at(i, c, lanes)
                out_ref[rows, at] = (r_ref[rows, at].astype(_F32)
                                     + post[i] * yf).astype(out_ref.dtype)
    _chunks(tile, body)


def _mix_bwd_kernel(dout_ref, maps_ref, y_ref, dy_ref, dmaps_ref, *, n):
    tile, c = y_ref.shape

    def body(rows):
        cols = maps_ref[rows, :]
        post = [_spread(cols, n + i) for i in range(n)]
        acc = [jnp.zeros((ROWS, LANE), _F32)] * n
        for lanes in _lanes(c):
            yf = y_ref[rows, lanes].astype(_F32)
            ds = [dout_ref[rows, _at(i, c, lanes)].astype(_F32)
                  for i in range(n)]
            dy_ref[rows, lanes] = _sum(
                [post[i] * ds[i] for i in range(n)]).astype(dy_ref.dtype)
            acc = [acc[i] + ds[i] * yf for i in range(n)]
        dmaps_ref[rows, :] = _sum(
            [_lane(n + i, _rowsum(acc[i])) for i in range(n)])
    _chunks(tile, body)


# -- the calls ---------------------------------------------------------------------

def _specs(tokens, widths):
    """The grid over token tiles and a block spec a width."""
    import jax.experimental.pallas as pl
    return (tokens // TILE,), [
        pl.BlockSpec((TILE, w), lambda t: (t, 0)) for w in widths]


def _whole(shape):
    import jax.experimental.pallas as pl
    return pl.BlockSpec(shape, lambda t: (0,) * len(shape))


_STATIC = ("n", "iters", "eps", "clamp", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def hetu_hc_pre_fwd(x, phit, ab, *, n, iters, eps, clamp, interpret):
    """``x [tokens, n C]``, ``phit [KP, n C]`` of its type, ``ab [KP, 2]`` f32
    -> ``u [tokens, C]``, the maps ``[tokens, 128]`` f32, ``R [tokens, n C]``
    (tokens a multiple of ``TILE``)."""
    import jax.experimental.pallas as pl
    tokens, row = x.shape
    c = row // n
    grid, (wide, narrow, cols) = _specs(tokens, (row, c, LANE))
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, n=n, iters=iters, eps=eps,
                          clamp=clamp),
        name="hetu_hc_pre_fwd", grid=grid,
        in_specs=[wide, _whole(phit.shape), _whole(ab.shape)],
        out_specs=[narrow, cols, wide],
        out_shape=[jax.ShapeDtypeStruct((tokens, c), x.dtype),
                   jax.ShapeDtypeStruct((tokens, LANE), _F32),
                   jax.ShapeDtypeStruct((tokens, row), x.dtype)],
        compiler_params=params(interpret, ("parallel",),
                               _vmem(n, c, x.dtype.itemsize) + (8 << 20)),
        interpret=interpret,
    )(x, phit, ab)


@functools.partial(jax.jit, static_argnames=_STATIC)
def hetu_hc_pre_bwd(x, phit, ab, maps, dr, du, dmaps, *, n, iters, eps, clamp,
                    interpret):
    """``dx [tokens, n C]`` in ``x``'s type, ``phi^T``'s cotangent ``[KP, n
    C]`` f32, and ``[2 KP, TILE]`` f32: the logits' cotangent (``b``'s, summed
    over lanes) over the same times the normed product (``alpha``'s)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tokens, row = x.shape
    c, kp = row // n, phit.shape[0]
    grid, (wide, narrow, cols) = _specs(tokens, (row, c, LANE))
    return pl.pallas_call(
        functools.partial(_pre_bwd_kernel, n=n, iters=iters, eps=eps,
                          clamp=clamp),
        name="hetu_hc_pre_bwd", grid=grid,
        in_specs=[wide, _whole(phit.shape), _whole(ab.shape), cols, wide,
                  narrow, cols],
        out_specs=[wide, _whole((kp, row)), _whole((2 * kp, TILE))],
        out_shape=[jax.ShapeDtypeStruct((tokens, row), x.dtype),
                   jax.ShapeDtypeStruct((kp, row), _F32),
                   jax.ShapeDtypeStruct((2 * kp, TILE), _F32)],
        scratch_shapes=[pltpu.VMEM((TILE, LANE), _F32),
                        pltpu.VMEM((TILE, row), _F32)],
        compiler_params=params(interpret, ("arbitrary",),
                               _vmem(n, c, x.dtype.itemsize) + (8 << 20)),
        interpret=interpret,
    )(x, phit, ab, maps, dr, du, dmaps)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def hetu_hc_mix_fwd(r, maps, y, *, n, interpret):
    """``R [tokens, n C]``, the maps ``[tokens, 128]``, ``y [tokens, C]`` ->
    ``X' = R + Hpost^T y``."""
    import jax.experimental.pallas as pl
    tokens, row = r.shape
    grid, (wide, narrow, cols) = _specs(tokens, (row, row // n, LANE))
    return pl.pallas_call(
        functools.partial(_mix_fwd_kernel, n=n),
        name="hetu_hc_mix_fwd", grid=grid,
        in_specs=[wide, cols, narrow], out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(r.shape, r.dtype),
        compiler_params=params(interpret, ("parallel",),
                               _vmem(n, row // n, r.dtype.itemsize)),
        interpret=interpret,
    )(r, maps, y)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def hetu_hc_mix_bwd(dout, maps, y, *, n, interpret):
    """``dy = Hpost dX'`` in ``y``'s type and the maps' cotangent ``[tokens,
    128]`` f32 (``<dX'_i, y>`` at ``Hpost``'s lanes, zeros elsewhere)."""
    import jax.experimental.pallas as pl
    tokens, row = dout.shape
    grid, (wide, narrow, cols) = _specs(tokens, (row, row // n, LANE))
    return pl.pallas_call(
        functools.partial(_mix_bwd_kernel, n=n),
        name="hetu_hc_mix_bwd", grid=grid,
        in_specs=[wide, cols, narrow], out_specs=[narrow, cols],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct((tokens, LANE), _F32)],
        compiler_params=params(interpret, ("parallel",),
                               _vmem(n, row // n, y.dtype.itemsize)),
        interpret=interpret,
    )(dout, maps, y)


# -- the pair ----------------------------------------------------------------------

def _small(phi, b, alpha, x, n):
    """``phi^T [KP, n C]`` in the streams' type and ``[KP, 2]`` f32: the gain
    and the bias of each row of the maps."""
    k, kp = width(n), _kp(n)
    phit = jnp.pad(phi.astype(x.dtype).T, ((0, kp - k), (0, 0)))
    gain = jnp.repeat(alpha.astype(_F32), jnp.array([n, n, n * n]),
                      total_repeat_length=k)
    ab = jnp.stack([gain, b.astype(_F32)], 1)
    return phit, jnp.pad(ab, ((0, kp - k), (0, 0)))


class How(NamedTuple):
    """The layer's constants (a ``custom_vjp``'s static argument)."""
    n: int
    iters: int
    eps: float
    clamp: tuple


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pre(x, phi, b, alpha, how):
    phit, ab = _small(phi, b, alpha, x, how.n)
    return tuple(hetu_hc_pre_fwd(x, phit, ab, interpret=dispatch.interpret(),
                                 **how._asdict()))


def _pre_fwd(x, phi, b, alpha, how):
    out = _pre(x, phi, b, alpha, how)
    return out, (x, phi, b, alpha, out[1])


def _pre_bwd(how, kept, cotangents):
    x, phi, b, alpha, maps = kept
    du, dmaps, dr = cotangents
    n = how.n
    k, kp = width(n), _kp(n)
    phit, ab = _small(phi, b, alpha, x, n)
    dx, dphit, dlog = hetu_hc_pre_bwd(
        x, phit, ab, maps, dr, du, dmaps, interpret=dispatch.interpret(),
        **how._asdict())
    dlog = dlog.sum(-1)
    by_gain = dlog[kp:kp + k]
    dalpha = jnp.stack([by_gain[:n].sum(), by_gain[n:2 * n].sum(),
                        by_gain[2 * n:].sum()])
    return (dx, dphit[:k].T.astype(phi.dtype), dlog[:k].astype(b.dtype),
            dalpha.astype(alpha.dtype))


_pre.defvjp(_pre_fwd, _pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _mix(r, maps, y, n):
    return hetu_hc_mix_fwd(r, maps, y, n=n, interpret=dispatch.interpret())


def _mix_fwd(r, maps, y, n):
    return _mix(r, maps, y, n), (maps, y)


def _mix_bwd(n, kept, dout):
    maps, y = kept
    dy, dmaps = hetu_hc_mix_bwd(dout, maps, y, n=n,
                                interpret=dispatch.interpret())
    return dout, dmaps, dy


_mix.defvjp(_mix_fwd, _mix_bwd)


def _tiles(x):
    """``[.., w] -> [tokens up to whole tiles, w]``."""
    flat = x.reshape(-1, x.shape[-1])
    return jnp.pad(flat, ((0, -flat.shape[0] % TILE), (0, 0)))


def pre(x, phi, b, alpha, *, n, iters, eps, clamp):
    """Before the function, through the kernels: ``x [.., n C]`` -> ``(u [..,
    C], maps [.., 128] f32, R [.., n C])``; the layer's maps are the first ``2
    n + n^2`` lanes of ``maps``, and ``mix`` reads ``maps`` and ``R``."""
    how = How(n, int(iters), float(eps), (float(clamp[0]), float(clamp[1])))
    lead, tokens = x.shape[:-1], x.size // x.shape[-1]
    return tuple(t[:tokens].reshape(lead + t.shape[-1:])
                 for t in _pre(_tiles(x), phi, b, alpha, how))


def mix(r, maps, y, *, n):
    """Behind the function: ``X' = R + Hpost^T y`` in ``R``'s type."""
    tokens = r.size // r.shape[-1]
    return _mix(_tiles(r), _tiles(maps), _tiles(y), n)[:tokens].reshape(
        r.shape)
