"""A latent-attention layer's heads laid out on whole lane tiles, once, where
they are made: two Pallas kernel pairs behind ``layers/latent_attention.py``
(that file has the ``jax.numpy`` forms, ``_queries`` / ``_keys`` / ``_values``,
these are held to).

A head's query and key are ``d_n + d_r = 128 + 64`` wide: no whole lane tiles,
so a view by heads ``[B, S, H, 192]`` is a pass over HBM (a minor dimension of
192 is tiled to 256 lanes), and the norm a head, the rotation of its last 64
lanes, the broadcast of ``k^rope`` over the heads and the transposes around
flash were each one or more such passes, forward, recomputed and backward.
Here a head gets a STRIDE of 256 lanes on ``[B, S, H x 256]``:

    lanes [256 h, 256 h + 128)         the part without position   (``lo``)
    lanes [256 h + 128, 256 h + 192)   the rotary part             (``hi``)
    lanes [256 h + 192, 256 h + 256)   exact zeros

which the flash kernels read in place (``bshd`` with keys 256 and values 128 a
head: the zeros add nothing to a score, and a contraction over 192 costs the
matrix unit the two passes of 256 already).  A program holds ``[rows, ..]`` of
its operands in VMEM and walks them in chunks of rows; everything is done on
``[chunk, 128]`` f32 tiles: the norm a head (mean over the 192, one learned
weight, where the layer has one), rounded to the compute type, then the
rotation of ``hi``'s first 64 lanes in ``ops/pallas/rotary.py``'s form for a
part of a tile (``_pair_tables(dim=128, rotary_dim=64)``: ``y = x cos +
roll(x, 32) sA + roll(x, 96) sB``, ``cos = 1`` and both sines 0 from lane 64
on; here ``cos`` and ``sA + sB`` are the two halves of ONE table ``[S, 128]``,
``tables(..)``, and a lane mask picks the roll), rounded at the store: the two
roundings of ``_rms`` and ``_rotary``.

``hetu_mla_q_fwd``: ``x W_q [B, S, H x 192] -> q^ [B, S, H x 256]``.  Two
heads are three lane tiles ``t0 t1 t2`` of the operand: head 0 is ``t0`` and
the first half of ``t1``, head 1 the second half of ``t1``, ``t2``: its tiles
are ``t1`` and ``t2`` rotated by 64 lanes and merged by a lane mask.
``hetu_mla_q_bwd``: the rotation by the opposite angle, the norm's backward
pass from the operand (nothing is kept but the operands and the tables), and
the same re-spacing back to 192 a head; the norm weight's cotangent leaves as
one ``[8, 128]`` partial sum a program.

``hetu_mla_k_fwd``: ``c W_kvb [B, S, H x (128 + d_v)]`` and ``k^rope [B, S,
128]`` (64 lanes and 64 zeros) ``-> k^ [B, S, H x 256], v [B, S, H x d_v]``:
a head's ``lo`` is its lanes of ``c W_kvb`` as they lie, its ``hi`` the one
``k^rope`` all heads share (normed with the head where the layer norms), and
the values are the lane-aligned slice the same pass writes.
``hetu_mla_k_bwd`` takes both cotangents and writes ``d(c W_kvb)`` whole (the
keys' into their lanes, the values' into theirs) and ``d k^rope`` summed over
the heads in f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dispatch
from .common import fit, params

#: rows a chunk and a block are multiples of: a bf16 tile's sublanes
ROWS = 16
#: bytes of a block of the widest operand (``[rows, H x 256]``: 128 rows at 32
#: heads; the backward key program holds seven such blocks, double-buffered)
#: and the rows of a chunk: the whole block.  A chunk's step is a chain of
#: lane reductions and rolls whose latency only more rows hide: v5e, bf16,
#: 32 heads with the norm at 8,192 rows (calls 59.2 and 59.3, each kernel 40
#: times in one jitted loop; q forward / q backward / k forward / k backward,
#: ms): chunks of 16 rows 1.73 / 2.29 / 1.67 / 1.76, of 32 1.08 / 1.49 / 1.04 /
#: 1.18, of 64 0.65 / 0.88 / 0.64 / 0.72, of 128 0.43 / 0.59 / 0.55 / 0.67 (their
#: bytes at 819 GB/s: 0.29 / 0.41 / 0.41 / 0.57); blocks of 1, 2 and 4 MiB the
#: same within 2%, 8 MiB over the scoped VMEM; without the norm at 4,096 rows
#: 0.13 / 0.11 / 0.18 / 0.18 at 128 rows a chunk
TILE, CHUNK = 2 ** 21, 128
#: scoped VMEM the programs may use (Mosaic's default 16 MiB holds the forward
#: programs' blocks, not the backward ones')
VMEM_LIMIT = 48 * 2 ** 20
#: lanes of a tile, of a head's part without position, of its rotary part, and
#: the stride of a head in ``q^`` and ``k^``
LANES, D_NOPE, D_ROPE, STRIDE = 128, 128, 64, 256

_F32 = jnp.float32


def unsupported(q, *, heads, d_nope, d_rope, d_v):
    """Why the kernels do not take a layer whose query projection is ``q [B,
    S, H (d_nope + d_rope)]`` and whose values are ``d_v`` wide, or None when
    they do."""
    if d_nope != D_NOPE:
        return f"nope_dim_not_{D_NOPE}"
    if d_rope != D_ROPE:
        return f"rope_dim_not_{D_ROPE}"
    if d_v % LANES:
        return "v_dim_not_128_aligned"
    if heads % 2:
        return "odd_head_count"
    dtype = jnp.dtype(q.dtype)
    if dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return f"dtype:{dtype.name}"
    if q.shape[1] % ROWS:
        return f"seq_not_{ROWS}_aligned"
    return None


# -- what the four bodies share: values [chunk, 128] f32 ----------------------

def _turn(hi, cos, sin, low):
    """The rotation of ``hi``'s first ``D_ROPE`` lanes (HF's half-split
    form): ``sin`` carries ``rotate_half``'s sign (``-sin`` on the lanes
    ``low``, the first ``D_ROPE / 2``, which read the lane ``D_ROPE / 2``
    above them; ``+sin`` on the next, which read the one below); with ``sin``
    negated, its transpose."""
    from jax.experimental.pallas import tpu as pltpu
    return hi * cos + sin * jnp.where(
        low, pltpu.roll(hi, LANES - D_ROPE // 2, 1),
        pltpu.roll(hi, D_ROPE // 2, 1))


def _scale(lo, hi, eps):
    """``rsqrt(mean over the head's 192 of x^2 + eps)``, ``[chunk, 1]``;
    ``hi`` is zero from lane ``D_ROPE`` on."""
    ss = jnp.sum(lo * lo + hi * hi, axis=1, keepdims=True)
    return jax.lax.rsqrt(ss / (D_NOPE + D_ROPE) + eps)


def _normed(lo, hi, w, eps, dtype):
    """``_rms`` of the head ``[lo | hi]`` under the weight ``w [2, 128]``
    (zero from lane ``D_ROPE`` of its second row on), rounded to ``dtype``:
    ``lo`` in it, ``hi`` (which the rotation reads) back in f32."""
    r = _scale(lo, hi, eps)
    return ((lo * r * w[0:1]).astype(dtype),
            (hi * r * w[1:2]).astype(dtype).astype(_F32))


def _normed_bwd(lo, hi, glo, ghi, w, eps):
    """The cotangents of ``_normed``'s operands and, ``[chunk, 128]`` each,
    the terms of its weight's two rows."""
    r = _scale(lo, hi, eps)
    xlo, xhi = lo * r, hi * r
    alo, ahi = glo * w[0:1], ghi * w[1:2]
    m = jnp.sum(alo * xlo + ahi * xhi, axis=1, keepdims=True) / (
        D_NOPE + D_ROPE)
    return (r * (alo - xlo * m), r * (ahi - xhi * m), glo * xlo, ghi * xhi)


def _masks(chunk):
    """``(first, low)``: the lanes of a tile that hold a rotary part, the
    first ``D_ROPE``, and the first half of those."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (chunk, LANES), 1)
    return lane < D_ROPE, lane < D_ROPE // 2


def _swap(x):
    """A tile's two halves exchanged."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(x, LANES // 2, 1)


def _cos_sin(t_ref, at, first, sign=1.0):
    """A chunk's ``(cos, sin)`` out of the one table (``tables``): ``cos`` on
    its first ``D_ROPE`` lanes and 1 behind them, the signed sine (times
    ``sign``) out of its last ``D_ROPE`` lanes and 0 behind it."""
    t = t_ref[at, :]
    return jnp.where(first, t, 1.0), jnp.where(first, sign * _swap(t), 0.0)


def _tile(ref, at, n):
    return ref[at, n * LANES:(n + 1) * LANES]


def _pair_heads(ref, at, pair, first):
    """``((lo, hi), (lo, hi))`` of heads ``2 pair`` and ``2 pair + 1`` out of
    ``[rows, H x 192]``: three tiles, the second head's shifted by 64 lanes."""
    t0, t1, t2 = (_tile(ref, at, 3 * pair + n).astype(_F32) for n in range(3))
    r1, r2 = _swap(t1), _swap(t2)
    return ((t0, jnp.where(first, t1, 0.0)),
            (jnp.where(first, r1, r2), jnp.where(first, r2, 0.0)))


def _partial(ref, lo, hi):
    """The norm weight's cotangent of one program: the two rows' sums over the
    program's rows and heads as rows 0 and 1 of an ``[8, 128]`` block."""
    row = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 0)
    lo, hi = (jnp.sum(x, axis=0, keepdims=True) for x in (lo, hi))
    ref[...] = jnp.where(row == 0, lo, jnp.where(row == 1, hi, 0.0))


def _walk(rows, chunk, body, carry=0):
    import jax.experimental.pallas as pl

    def step(i, carry):
        return body(pl.ds(pl.multiple_of(i * chunk, chunk), chunk), carry)
    return jax.lax.fori_loop(0, rows // chunk, step, carry)


# -- the queries ----------------------------------------------------------------

def _q_fwd_kernel(t_ref, x_ref, *rest, heads, eps, chunk):
    *w_ref, o_ref = rest
    first, low = _masks(chunk)
    w = w_ref[0][...] if w_ref else None
    dtype = o_ref.dtype

    def body(at, carry):
        cos, sin = _cos_sin(t_ref, at, first)
        for pair in range(heads // 2):
            for h, (lo, hi) in enumerate(_pair_heads(x_ref, at, pair, first)):
                if w is not None:
                    lo, hi = _normed(lo, hi, w, eps, dtype)
                n = 2 * (2 * pair + h)
                o_ref[at, n * LANES:(n + 1) * LANES] = lo.astype(dtype)
                o_ref[at, (n + 1) * LANES:(n + 2) * LANES] = _turn(
                    hi, cos, sin, low).astype(dtype)
        return carry
    _walk(x_ref.shape[0], chunk, body)


def _q_bwd_kernel(t_ref, g_ref, *rest, heads, eps, chunk):
    normed = len(rest) == 4
    first, low = _masks(chunk)
    if normed:
        x_ref, w_ref, dx_ref, dw_ref = rest
        w = w_ref[...]
    else:
        dx_ref, = rest
    dtype = dx_ref.dtype
    zero = jnp.zeros((chunk, LANES), _F32)

    def body(at, acc):
        cos, sin = _cos_sin(t_ref, at, first, -1.0)
        for pair in range(heads // 2):
            out = []
            xs = _pair_heads(x_ref, at, pair, first) if normed else (None,) * 2
            for h, x in enumerate(xs):
                n = 2 * (2 * pair + h)
                glo = _tile(g_ref, at, n).astype(_F32)
                ghi = jnp.where(first, _turn(
                    _tile(g_ref, at, n + 1).astype(_F32), cos, sin, low), 0.0)
                if normed:
                    ghi = ghi.astype(dtype).astype(_F32)
                    glo, ghi, wlo, whi = _normed_bwd(*x, glo, ghi, w, eps)
                    acc = (acc[0] + wlo, acc[1] + whi)
                out.append((glo, ghi))
            (lo0, hi0), (lo1, hi1) = out
            s1, s2 = _swap(lo1), _swap(hi1)
            for n, t in enumerate((lo0, jnp.where(first, hi0, s1),
                                   jnp.where(first, s1, s2))):
                n += 3 * pair
                dx_ref[at, n * LANES:(n + 1) * LANES] = t.astype(dtype)
        return acc
    acc = _walk(g_ref.shape[0], chunk, body, (zero, zero))
    if normed:
        _partial(dw_ref, *acc)


# -- the keys and the values ----------------------------------------------------

def _k_fwd_kernel(t_ref, kvb_ref, kr_ref, *rest, heads, eps, chunk):
    *w_ref, k_ref, v_ref = rest
    first, low = _masks(chunk)
    w = w_ref[0][...] if w_ref else None
    dtype = k_ref.dtype
    d_v = v_ref.shape[1] // heads
    wide = D_NOPE + d_v

    def body(at, carry):
        cos, sin = _cos_sin(t_ref, at, first)
        rope = kr_ref[at, :].astype(_F32)
        if w is None:       # one rotary part for all heads
            turned = _turn(rope, cos, sin, low).astype(dtype)
        for h in range(heads):
            lo = kvb_ref[at, h * wide:h * wide + D_NOPE]
            if w is None:
                hi = turned
            else:
                lo, hi = _normed(lo.astype(_F32), rope, w, eps, dtype)
                hi = _turn(hi, cos, sin, low).astype(dtype)
            k_ref[at, h * STRIDE:h * STRIDE + D_NOPE] = lo
            k_ref[at, h * STRIDE + D_NOPE:(h + 1) * STRIDE] = hi
            v_ref[at, h * d_v:(h + 1) * d_v] = kvb_ref[
                at, h * wide + D_NOPE:(h + 1) * wide]
        return carry
    _walk(kvb_ref.shape[0], chunk, body)


def _k_bwd_kernel(t_ref, gk_ref, gv_ref, *rest, heads, eps, chunk):
    normed = len(rest) == 6
    first, low = _masks(chunk)
    if normed:
        kvb_ref, kr_ref, w_ref, dkvb_ref, dkr_ref, dw_ref = rest
        w = w_ref[...]
    else:
        dkvb_ref, dkr_ref = rest
    dtype = dkvb_ref.dtype
    d_v = gv_ref.shape[1] // heads
    wide = D_NOPE + d_v
    zero = jnp.zeros((chunk, LANES), _F32)

    def body(at, acc):
        cos, sin = _cos_sin(t_ref, at, first, -1.0)
        back = lambda g: jnp.where(first, _turn(g, cos, sin, low), 0.0)
        rope = kr_ref[at, :].astype(_F32) if normed else None
        d_rope = zero
        for h in range(heads):
            glo = gk_ref[at, h * STRIDE:h * STRIDE + D_NOPE]
            ghi = gk_ref[at, h * STRIDE + D_NOPE:(h + 1) * STRIDE].astype(_F32)
            if normed:
                lo = kvb_ref[at, h * wide:h * wide + D_NOPE].astype(_F32)
                glo, ghi, wlo, whi = _normed_bwd(
                    lo, rope, glo.astype(_F32),
                    back(ghi).astype(dtype).astype(_F32), w, eps)
                acc = (acc[0] + wlo, acc[1] + whi)
            d_rope = d_rope + ghi
            dkvb_ref[at, h * wide:h * wide + D_NOPE] = glo.astype(dtype)
            dkvb_ref[at, h * wide + D_NOPE:(h + 1) * wide] = gv_ref[
                at, h * d_v:(h + 1) * d_v]
        # without a norm the heads' rotary parts are one: the sum turns back
        dkr_ref[at, :] = (d_rope if normed else back(d_rope)).astype(dtype)
        return acc
    acc = _walk(gk_ref.shape[0], chunk, body, (zero, zero))
    if normed:
        _partial(dw_ref, *acc)


# -- the four calls -------------------------------------------------------------

def _call(name, kernel, operands, tables, weight, outs, *, heads, eps,
          interpret, tile, chunk, partial=False):
    """One program a block of ``rows`` positions of one batch row: ``tables
    [S, 128]`` by rows (the batch innermost, so a block of them is fetched
    once), ``operands`` and ``outs`` (shapes) ``[B, S, .]`` by rows and
    ``weight [2, 128]`` whole, in that order; with ``partial``, one more
    result ``[programs, 8, 128]`` f32."""
    import jax.experimental.pallas as pl
    B, S, _ = operands[0].shape
    widest = max(x.shape[2] * jnp.dtype(x.dtype).itemsize
                 for x in tuple(operands) + tuple(outs))
    ts = fit(S, max(tile // widest, ROWS), ROWS)
    rows = lambda x: pl.BlockSpec((None, ts, x.shape[2]),
                                  lambda s, b: (b, s, 0))
    in_specs = [pl.BlockSpec((ts, LANES), lambda s, b: (s, 0))] + [
        rows(x) for x in operands]
    out_specs, out_shape = [rows(x) for x in outs], list(outs)
    if weight is not None:
        in_specs.append(pl.BlockSpec((2, LANES), lambda s, b: (0, 0)))
    if partial:
        out_specs.append(pl.BlockSpec((None, 8, LANES),
                                      lambda s, b: (s * B + b, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((S // ts * B, 8, LANES), _F32))
    return pl.pallas_call(
        functools.partial(kernel, heads=heads, eps=eps,
                          chunk=fit(ts, max(chunk, ROWS), ROWS)),
        name=name, grid=(S // ts, B), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=params(interpret, ("parallel",) * 2, VMEM_LIMIT),
        interpret=interpret,
    )(tables, *operands, *(() if weight is None else (weight,)))


_STATIC = ("heads", "eps", "interpret", "tile", "chunk")


def _like(x, width):
    return jax.ShapeDtypeStruct(x.shape[:2] + (width,), x.dtype)


@functools.partial(jax.jit, static_argnames=_STATIC)
def hetu_mla_q_fwd(x, tables, weight=None, *, heads, eps, interpret,
                   tile=TILE, chunk=CHUNK):
    """``x [B, S, H x 192]``, the f32 ``tables [S, 128]`` and the norm's
    ``weight [2, 128]`` f32 (or None) -> ``q^ [B, S, H x 256]``."""
    return _call("hetu_mla_q_fwd", _q_fwd_kernel, [x], tables, weight,
                 [_like(x, heads * STRIDE)], heads=heads, eps=eps,
                 interpret=interpret, tile=tile, chunk=chunk)[0]


@functools.partial(jax.jit, static_argnames=_STATIC)
def hetu_mla_q_bwd(g, tables, x=None, weight=None, *, heads, eps, interpret,
                   tile=TILE, chunk=CHUNK):
    """``q^``'s cotangent -> ``[dx]``, and with a norm (``x``, ``weight``) the
    weight's partial sums ``[programs, 8, 128]`` behind it."""
    normed = weight is not None
    return _call("hetu_mla_q_bwd", _q_bwd_kernel, [g] + [x] * normed, tables,
                 weight, [_like(g, heads * (D_NOPE + D_ROPE))], heads=heads,
                 eps=eps, interpret=interpret, tile=tile, chunk=chunk,
                 partial=normed)


@functools.partial(jax.jit, static_argnames=_STATIC)
def hetu_mla_k_fwd(kvb, rope, tables, weight=None, *, heads, eps, interpret,
                   tile=TILE, chunk=CHUNK):
    """``c W_kvb [B, S, H x (128 + d_v)]``, ``k^rope [B, S, 128]`` (zero from
    lane 64 on), the tables and the norm's weight (or None) -> ``(k^ [B, S, H
    x 256], v [B, S, H x d_v])``."""
    d_v = kvb.shape[2] // heads - D_NOPE
    return tuple(_call(
        "hetu_mla_k_fwd", _k_fwd_kernel, [kvb, rope], tables, weight,
        [_like(kvb, heads * STRIDE), _like(kvb, heads * d_v)], heads=heads,
        eps=eps, interpret=interpret, tile=tile, chunk=chunk))


@functools.partial(jax.jit, static_argnames=_STATIC)
def hetu_mla_k_bwd(gk, gv, tables, kvb=None, rope=None, weight=None, *, heads,
                   eps, interpret, tile=TILE, chunk=CHUNK):
    """The cotangents of ``k^`` and ``v`` -> ``[d(c W_kvb), d k^rope [B, S,
    128]]`` and, with a norm, the weight's partial sums."""
    normed = weight is not None
    d_v = gv.shape[2] // heads
    return _call("hetu_mla_k_bwd", _k_bwd_kernel,
                 [gk, gv] + [kvb, rope] * normed, tables, weight,
                 [_like(gk, heads * (D_NOPE + d_v)), _like(gk, LANES)],
                 heads=heads, eps=eps, interpret=interpret, tile=tile,
                 chunk=chunk, partial=normed)


# -- the two entries --------------------------------------------------------------

def tables(three):
    """``[S, 128]`` f32, ``cos`` on the first 64 lanes and the signed sine on
    the last, out of ``ops/rotary.py _pair_tables(dim=128, rotary_dim=64)``'s
    ``[3, S, 128]`` (its two sines hold on lanes apart, and nothing of the
    three turns behind lane 64): one table, a third of the bytes."""
    return jnp.concatenate([three[0][:, :D_ROPE],
                            (three[1] + three[2])[:, :D_ROPE]], axis=1)


def _rows_of(weight):
    """The norm's weight ``[192]`` as the kernels read it: f32 ``[2, 128]``,
    zeros behind the 64 of its second row."""
    if weight is None:
        return None
    return jnp.pad(weight.astype(_F32), (0, STRIDE - weight.shape[0])
                   ).reshape(2, LANES)


def _weight_grad(partials, weight):
    return partials.sum(0)[:2].reshape(-1)[:weight.shape[0]].astype(
        weight.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def queries(x, tables, weight, heads, eps):
    """``x W_q [B, S, H x 192]`` -> ``q^ [B, S, H x 256]``: a head's norm
    (``weight [192]``; None: no norm) and rotation, at a stride of 256."""
    return hetu_mla_q_fwd(x, tables, _rows_of(weight), heads=heads, eps=eps,
                          interpret=dispatch.interpret())


def _queries_fwd(x, tables, weight, heads, eps):
    return queries(x, tables, weight, heads, eps), (x, tables, weight)


def _queries_bwd(heads, eps, kept, g):
    x, tables, weight = kept
    kw = dict(heads=heads, eps=eps, interpret=dispatch.interpret())
    if weight is None:
        return hetu_mla_q_bwd(g, tables, **kw)[0], None, None
    dx, partials = hetu_mla_q_bwd(g, tables, x, _rows_of(weight), **kw)
    return dx, None, _weight_grad(partials, weight)


queries.defvjp(_queries_fwd, _queries_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _keys_values(kvb, rope, tables, weight, heads, eps):
    return hetu_mla_k_fwd(kvb, rope, tables, _rows_of(weight), heads=heads,
                          eps=eps, interpret=dispatch.interpret())


def _keys_values_fwd(kvb, rope, tables, weight, heads, eps):
    return (_keys_values(kvb, rope, tables, weight, heads, eps),
            (kvb, rope, tables, weight))


def _keys_values_bwd(heads, eps, kept, g):
    kvb, rope, tables, weight = kept
    kw = dict(heads=heads, eps=eps, interpret=dispatch.interpret())
    if weight is None:
        dkvb, drope = hetu_mla_k_bwd(*g, tables, **kw)
        return dkvb, drope, None, None
    dkvb, drope, partials = hetu_mla_k_bwd(*g, tables, kvb, rope,
                                           _rows_of(weight), **kw)
    return dkvb, drope, None, _weight_grad(partials, weight)


_keys_values.defvjp(_keys_values_fwd, _keys_values_bwd)


def keys_values(kvb, rope, tables, weight, heads, eps):
    """``c W_kvb [B, S, H x (128 + d_v)]`` and ``k^rope [B, S, 64]`` -> ``(k^
    [B, S, H x 256], v [B, S, H x d_v])``: a head's key its lanes of ``c
    W_kvb`` with the one rotary part behind them, normed (``weight [192]``;
    None: no norm) and rotated; the values the slice the same pass writes."""
    rope = jnp.pad(rope, ((0, 0), (0, 0), (0, LANES - rope.shape[2])))
    return _keys_values(kvb, rope, tables, weight, heads, eps)
