"""Mamba-1's selective scan as two Pallas kernels (``ops/selective_scan.py``
has the function and the ``jax.numpy`` form these are held to).

``h_t = exp(delta_t A) h_(t-1) + (delta_t u_t) B_t``, ``y_t = h_t C_t`` with a
decay for every channel AND state is a chain of ``S`` dependent steps of
vector work: nothing of it is a matrix product.  A program holds the state of
``LANES`` channels in registers as ``[N, 128]`` tiles (the states down the
sublanes, the channels along the lanes) and walks the positions of a chunk
one after the other, ``ROWS`` of them a loop step so that ``u``, ``delta`` and
``y`` are read and written as whole ``[8, lanes]`` tiles.

``B_t`` and ``C_t`` multiply down the sublanes, so they are needed as COLUMNS
``[N, 1]`` spread over the lanes, while they come ``[T, N]`` with the states
along the lanes.  A chunk's are turned once, before its walk (``_columns``):
the 0/1 matrix that repeats row ``t`` ``N`` times, on the matrix unit over the
three bf16 parts of the f32 operand (exact: one term a sum), a mask that
keeps entry ``n`` of row ``(t, n)``, and a sum along the lanes.

``hetu_s6_fwd``: grid (batch, channel tiles, chunks), the chunks in turn with
the state in a VMEM scratch.  Beside ``y`` it writes the state at each
chunk's START, ``[B, S / T, N, C]`` f32 (42 MB at 16,384 x 5,120 x 16), which is
all the backward pass keeps.

``hetu_s6_bwd``: the same grid with the chunks from the last to the first.  A
program runs its chunk's recurrence again from the kept state into a VMEM
scratch of ``T + 1`` states, then walks the positions backwards with the
adjoint ``g`` of the state in registers (carried over the chunks in a scratch):
``g += C_t dy_t``; ``dC_t = sum_c h_t dy_t``; ``dB_t = sum_c g delta_t u_t``;
``d(delta_t u_t) = sum_n g B_t``; with ``a = exp(delta_t A)``, ``ga = g h_(t-1)
a``: ``d delta_t += sum_n ga A``, ``dA += ga delta_t``; ``g = a g``.  The sums
over ``n`` are sums down the sublanes.  The sums over the channels (``dB``,
``dC``) are folded to 128 lanes by vector adds, kept ``[T N, 128]`` in VMEM, and
a chunk's are summed along the lanes AND turned into a row by one product
with ones on the matrix unit (exact over the three bf16 parts); XLA adds the
channel tiles' rows up.  ``dA`` accumulates in its output block over the
chunks of a sequence; XLA adds the batch up.

``delta``, ``A``, every decay, the state, ``y`` and every cotangent but
``du`` are f32.  (Not ``common.walk``: the loops here carry the state, or its
adjoint and ``dA``, in registers from one group of rows to the next.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dispatch
from .common import NN, NT, VMEM_LIMIT, dot, fit, params, parts

#: positions a chunk (a kept state each), channels a program, positions a
#: loop step
T, LANES, ROWS = 128, 512, 8

_F32 = jnp.float32
_BF16 = jnp.bfloat16


def unsupported(u, A):
    """Why the kernels do not take ``selective_scan``'s operands, or None
    when they do."""
    n = A.shape[1]
    if u.shape[-1] % 128:
        return "channels_not_128_aligned"
    if n % 8 or n > 128 or n & (n - 1):
        return "state_not_a_power_of_two_in_8_128"
    if jnp.dtype(u.dtype) not in (jnp.dtype(_BF16), jnp.dtype(_F32)):
        return f"dtype:{jnp.dtype(u.dtype).name}"
    return None


def _columns(m, n):
    """``m [T, 128]`` f32 (a row's first ``n`` lanes hold ``B_t`` or ``C_t``)
    -> ``[T n, 128]``: row ``t n + i`` holds ``m[t, i]`` on every lane."""
    t = m.shape[0]
    shift = n.bit_length() - 1
    shape = (t * n, t)
    rep = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) >> shift
           == jax.lax.broadcasted_iota(jnp.int32, shape, 1)).astype(_BF16)
    x = None
    for p in reversed(parts(m)):
        term = dot(rep, p, NN)
        x = term if x is None else x + term
    pick = ((jax.lax.broadcasted_iota(jnp.int32, x.shape, 0) & (n - 1))
            == jax.lax.broadcasted_iota(jnp.int32, x.shape, 1))
    col = jnp.sum(jnp.where(pick, x, 0.0), axis=1, keepdims=True)
    return jnp.broadcast_to(col, x.shape)


def _lane_sums_row(p):
    """``p [R, 128]`` f32 -> ``[1, R]``: each row's sum along the lanes."""
    ones = jnp.ones((8, p.shape[1]), _BF16)
    out = None
    for part in reversed(parts(p)):
        term = dot(ones, part, NT)
        out = term if out is None else out + term
    return out[0:1]


def _tiles(ref_or_val, k):
    return [ref_or_val[:, i * 128:(i + 1) * 128] for i in range(k)]


def _state_rows(t, n):
    import jax.experimental.pallas as pl
    return pl.ds(pl.multiple_of(t * n, n), n)


def _group_rows(i):
    import jax.experimental.pallas as pl
    return pl.ds(pl.multiple_of(i * ROWS, ROWS), ROWS)


def _put_row(tile, j, row):
    """``tile [8, 128]`` with its row ``j`` (static) set to ``row [1, 128]``."""
    rid = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0)
    return jnp.where(rid == j, row, tile)


def _advance(d, du, a, h, bb):
    """``exp(delta_t A) h + (delta_t u_t) B_t`` on one ``[N, 128]`` tile:
    ``d``, ``du`` rows ``[1, 128]``, ``bb`` ``B_t`` down the sublanes."""
    return jnp.exp(d * a) * h + du * bb


def _fwd_kernel(u_ref, d_ref, a_ref, b_ref, c_ref, y_ref, hs_ref,
                h_scr, bb_scr, cc_scr, *, n):
    import jax.experimental.pallas as pl
    t_rows, lanes = u_ref.shape
    k = lanes // 128

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_scr[...] = jnp.zeros_like(h_scr)

    hs_ref[...] = h_scr[...]
    bb_scr[...] = _columns(b_ref[...], n)
    cc_scr[...] = _columns(c_ref[...], n)
    A = _tiles(a_ref[...], k)

    def group(i, h):
        rows = _group_rows(i)
        d8 = d_ref[rows, :]
        du8 = d8 * u_ref[rows, :].astype(_F32)
        ys = [jnp.zeros((ROWS, 128), _F32)] * k
        for j in range(ROWS):
            at = _state_rows(i * ROWS + j, n)
            bb, cc = bb_scr[at, :], cc_scr[at, :]
            new = []
            for x in range(k):
                sl = slice(x * 128, (x + 1) * 128)
                hx = _advance(d8[j:j + 1, sl], du8[j:j + 1, sl], A[x], h[x],
                              bb)
                ys[x] = _put_row(ys[x], j, jnp.sum(hx * cc, axis=0,
                                                   keepdims=True))
                new.append(hx)
            h = tuple(new)
        y_ref[rows, :] = jnp.concatenate(ys, axis=1)
        return h
    h = jax.lax.fori_loop(0, t_rows // ROWS, group,
                          tuple(_tiles(h_scr[...], k)))
    h_scr[...] = jnp.concatenate(h, axis=1)


def _bwd_kernel(u_ref, d_ref, a_ref, b_ref, c_ref, hs_ref, dy_ref,
                du_ref, dd_ref, da_ref, db_ref, dc_ref,
                g_scr, h_all, bb_scr, cc_scr, pb_scr, pc_scr, *, n):
    import jax.experimental.pallas as pl
    t_rows, lanes = u_ref.shape
    k = lanes // 128
    groups = t_rows // ROWS

    @pl.when(pl.program_id(2) == 0)
    def _():
        g_scr[...] = jnp.zeros_like(g_scr)
        da_ref[...] = jnp.zeros_like(da_ref)

    bb_scr[...] = _columns(b_ref[...], n)
    cc_scr[...] = _columns(c_ref[...], n)
    A = _tiles(a_ref[...], k)

    # the chunk's states again: h_all's block t + 1 is h_t, block 0 the kept
    # state in front of the chunk
    h_all[0:n, :] = hs_ref[...]

    def again(i, h):
        rows = _group_rows(i)
        d8 = d_ref[rows, :]
        du8 = d8 * u_ref[rows, :].astype(_F32)
        for j in range(ROWS):
            t = i * ROWS + j
            bb = bb_scr[_state_rows(t, n), :]
            new = []
            for x in range(k):
                sl = slice(x * 128, (x + 1) * 128)
                hx = _advance(d8[j:j + 1, sl], du8[j:j + 1, sl], A[x], h[x],
                              bb)
                h_all[_state_rows(t + 1, n), sl] = hx
                new.append(hx)
            h = tuple(new)
        return h
    jax.lax.fori_loop(0, groups, again, tuple(_tiles(hs_ref[...], k)))

    def back(m, carry):
        g, da = carry
        i = groups - 1 - m
        rows = _group_rows(i)
        d8 = d_ref[rows, :]
        u8 = u_ref[rows, :].astype(_F32)
        dy8 = dy_ref[rows, :]
        du8 = d8 * u8
        r1 = [jnp.zeros((ROWS, 128), _F32)] * k     # sum_n g B_t
        r2 = [jnp.zeros((ROWS, 128), _F32)] * k     # sum_n ga A
        for j in reversed(range(ROWS)):
            t = i * ROWS + j
            at, after = _state_rows(t, n), _state_rows(t + 1, n)
            bb, cc = bb_scr[at, :], cc_scr[at, :]
            pb = pc = None
            new_g, new_da = [], []
            for x in range(k):
                sl = slice(x * 128, (x + 1) * 128)
                dy = dy8[j:j + 1, sl]
                d = d8[j:j + 1, sl]
                gx = g[x] + cc * dy
                pcx = h_all[after, sl] * dy
                pbx = gx * du8[j:j + 1, sl]
                pb = pbx if pb is None else pb + pbx
                pc = pcx if pc is None else pc + pcx
                r1[x] = _put_row(r1[x], j, jnp.sum(gx * bb, axis=0,
                                                   keepdims=True))
                a = jnp.exp(d * A[x])
                ga = gx * h_all[at, sl] * a
                r2[x] = _put_row(r2[x], j, jnp.sum(ga * A[x], axis=0,
                                                   keepdims=True))
                new_da.append(da[x] + ga * d)
                new_g.append(a * gx)
            g, da = tuple(new_g), tuple(new_da)
            pb_scr[at, :] = pb
            pc_scr[at, :] = pc
        r1, r2 = jnp.concatenate(r1, axis=1), jnp.concatenate(r2, axis=1)
        dd_ref[rows, :] = r2 + r1 * u8
        du_ref[rows, :] = (r1 * d8).astype(du_ref.dtype)
        return g, da
    zeros = tuple(jnp.zeros((n, 128), _F32) for _ in range(k))
    g, da = jax.lax.fori_loop(0, groups, back,
                              (tuple(_tiles(g_scr[...], k)), zeros))
    g_scr[...] = jnp.concatenate(g, axis=1)
    da_ref[...] += jnp.concatenate(da, axis=1)
    db_ref[...] = _lane_sums_row(pb_scr[...])
    dc_ref[...] = _lane_sums_row(pc_scr[...])


def _plan(u, n, chunk, lanes):
    """Padded length, chunk rows, lanes a program, the grid."""
    B, S, C = u.shape
    rows = -(-S // chunk) * chunk
    tc = fit(C, lanes, 128)
    return rows, tc, (B, C // tc, rows // chunk)


def _padded(S, rows, wide, narrow):
    """``wide`` arrays ``[B, S, C]`` with zeros up to ``rows`` positions, then
    ``narrow`` ones ``[B, S, N]`` (``B_t``, ``C_t``) as f32 with zeros up to
    ``rows`` positions and 128 lanes."""
    return [jnp.pad(t, ((0, 0), (0, rows - S), (0, 0))) if rows != S else t
            for t in wide] + [
        jnp.pad(t.astype(_F32), ((0, 0), (0, rows - S),
                                 (0, 128 - t.shape[-1]))) for t in narrow]


@functools.partial(jax.jit, static_argnames=("interpret", "chunk", "lanes"))
def hetu_s6_fwd(u, delta, A, B, C, *, interpret, chunk=T, lanes=LANES):
    """``(y [B, S, C] f32, states [B, S' / chunk, N, C] f32)``: the scan's
    output and the state in front of each chunk (``S'`` is ``S`` rounded up
    to whole chunks)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, S, ch = u.shape
    n = A.shape[1]
    rows, tc, grid = _plan(u, n, chunk, lanes)
    u, delta, B, C = _padded(S, rows, (u, delta.astype(_F32)), (B, C))
    wide = pl.BlockSpec((None, chunk, tc), lambda b, c, s: (b, s, c))
    narrow = pl.BlockSpec((None, chunk, 128), lambda b, c, s: (b, s, 0))
    y, hs = pl.pallas_call(
        functools.partial(_fwd_kernel, n=n), name="hetu_s6_fwd", grid=grid,
        in_specs=[wide, wide, pl.BlockSpec((n, tc), lambda b, c, s: (0, c)),
                  narrow, narrow],
        out_specs=[wide, pl.BlockSpec((None, None, n, tc),
                                      lambda b, c, s: (b, s, 0, c))],
        out_shape=[jax.ShapeDtypeStruct((b, rows, ch), _F32),
                   jax.ShapeDtypeStruct((b, rows // chunk, n, ch), _F32)],
        scratch_shapes=[pltpu.VMEM((n, tc), _F32),
                        pltpu.VMEM((chunk * n, 128), _F32),
                        pltpu.VMEM((chunk * n, 128), _F32)],
        compiler_params=params(interpret,
                               ("parallel", "parallel", "arbitrary"),
                               VMEM_LIMIT),
        interpret=interpret,
    )(u, delta, A.astype(_F32).T, B, C)
    return y[:, :S], hs


@functools.partial(jax.jit, static_argnames=("interpret", "chunk", "lanes"))
def hetu_s6_bwd(u, delta, A, B, C, hs, dy, *, interpret, chunk=T,
                lanes=LANES):
    """``(du, ddelta [B, S, C] f32, dA [C, N] f32, dB, dC [B, S, N] f32)``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, S, ch = u.shape
    n = A.shape[1]
    rows, tc, grid = _plan(u, n, chunk, lanes)
    u_dtype = u.dtype
    u, delta, dy, B, C = _padded(
        S, rows, (u, delta.astype(_F32), dy.astype(_F32)), (B, C))
    last = grid[2] - 1
    wide = pl.BlockSpec((None, chunk, tc), lambda b, c, s: (b, last - s, c))
    narrow = pl.BlockSpec((None, chunk, 128),
                          lambda b, c, s: (b, last - s, 0))
    a_block = pl.BlockSpec((n, tc), lambda b, c, s: (0, c))
    sums = pl.BlockSpec((None, None, 1, chunk * n),
                        lambda b, c, s: (b, c, 0, last - s))
    partial_sums = jax.ShapeDtypeStruct((b, grid[1], 1, rows * n), _F32)
    du, dd, da, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, n=n), name="hetu_s6_bwd", grid=grid,
        in_specs=[wide, wide, a_block, narrow, narrow,
                  pl.BlockSpec((None, None, n, tc),
                               lambda b, c, s: (b, last - s, 0, c)), wide],
        out_specs=[wide, wide,
                   pl.BlockSpec((None, n, tc), lambda b, c, s: (b, 0, c)),
                   sums, sums],
        out_shape=[jax.ShapeDtypeStruct((b, rows, ch), u_dtype),
                   jax.ShapeDtypeStruct((b, rows, ch), _F32),
                   jax.ShapeDtypeStruct((b, n, ch), _F32),
                   partial_sums, partial_sums],
        scratch_shapes=[pltpu.VMEM((n, tc), _F32),
                        pltpu.VMEM(((chunk + 1) * n, tc), _F32)]
        + [pltpu.VMEM((chunk * n, 128), _F32)] * 4,
        compiler_params=params(interpret,
                               ("parallel", "parallel", "arbitrary"),
                               VMEM_LIMIT),
        interpret=interpret,
    )(u, delta, A.astype(_F32).T, B, C, hs, dy)
    db, dc = (t.sum(axis=(1, 2)).reshape(b, rows, n)[:, :S] for t in (db, dc))
    return du[:, :S], dd[:, :S], da.sum(0).T, db, dc


@jax.custom_vjp
def s6(u, delta, A, B, C):
    """``ops/selective_scan.py selective_scan`` through the kernel pair."""
    return hetu_s6_fwd(u, delta, A, B, C, interpret=dispatch.interpret())[0]


def _s6_fwd(u, delta, A, B, C):
    y, hs = hetu_s6_fwd(u, delta, A, B, C, interpret=dispatch.interpret())
    return y, (u, delta, A, B, C, hs)


def _s6_bwd(kept, dy):
    u, delta, A, B, C, hs = kept
    du, dd, da, db, dc = hetu_s6_bwd(u, delta, A, B, C, hs, dy,
                                     interpret=dispatch.interpret())
    return (du, dd.astype(delta.dtype), da.astype(A.dtype),
            db.astype(B.dtype), dc.astype(C.dtype))


s6.defvjp(_s6_fwd, _s6_bwd)
