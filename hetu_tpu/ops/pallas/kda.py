"""The chunked delta rule with a decay a key channel as two Pallas kernels
(``ops/kda.py`` has the mathematics and the ``jax.numpy`` form these are held
to; ``ops/pallas/gated_delta.py`` is the scalar-decay pair whose idiom and
helpers these share).

``hetu_kda_fwd``: grid (batch, block of heads, block of chunks), the last
axis sequential.  The states ``S [d_k, d_v]`` f32 of a program's ``HEADS``
heads stay in VMEM scratch from their first chunk to their last.  A program
walks ``CHUNKS`` chunks of 64 positions: it reads their q, k, v and g rows in
place from the ``[B, T, H * d]`` views (a ``[rows, HEADS * d]`` block at lane
offset ``h * d``), beta from ``[B, H, T / (n C), n, C]``, forms the chunks in
VMEM (``_chunks``) and writes ``o`` once and the state each chunk starts
from.

``hetu_kda_bwd``: the same grid with the chunks in reverse and ``dS`` in
VMEM scratch.  A program rebuilds its chunks from q, k, v, g, beta and the
kept chunk-start states and pulls ``do`` and ``dS`` back through them: the
backward pass of a chunk is ``jax.vjp`` of ``_chunks``, traced into the
kernel.  Two rules are given by hand so that what Mosaic is handed are the
three forms of product it lowers without a transposition (``_mm``) and no
walk back through the substitution (``_inverses``: ``dL = -T^T dT T^T``).
What the backward keeps is the chunk-start states and nothing else.

Inside a chunk the decays are taken sub-chunk by sub-chunk of 16 positions
(``_pair``): the diagonal ``[16, 16]`` blocks from one ``[64, 64]`` product
of rows and columns both taken relative to the middle of their sub-chunk,
the blocks below from one product a column sub-chunk (rows decayed from its
end, clamped at one where they lie before it; columns decayed to it).
``g >= -5`` a position keeps every factor within ``exp(+-40)``.

A chunk is one chain of dependent steps and Mosaic's scheduler stays close
to program order, so a program's heads run their chains in step as the
scalar rule's do (``_together``: ``_open`` and ``_close`` are generators that
yield between dependent stages, and the heads' triangular inverses are one
``custom_vjp`` that runs their substitutions in step); the backward pass,
being the transposition of that trace, is interleaved the same way.  On a
v5e a layer of 32 heads over 8,192 positions (my chip run, PR 40): one head
a program 10.3 ms forward and 25.7 forward and backward, two 6.4 and 17.4,
four 5.7 and 15.7.

Precision as ``ops/pallas/gated_delta.py``: the state, the decays, ``T`` and
every operand of a product with them are f32, multiplied as bf16 passes
over their three bf16 parts (``_dot32``); the pair matrices and ``P u`` take
their operands in the compute type.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import dispatch
from .gated_delta import (C, CHUNKS, VMEM_LIMIT, _NN, _NT, _TN, _dot, _dot32,
                          _iotas, _lanes, _pick, _put, _rows, _to_col,
                          _together, _unit_lower_inverse, _walk)
from ..kda import SUB

_F32 = jnp.float32
_BF16 = jnp.bfloat16

#: heads a program runs in step (``_together``); the module's docstring has
#: the times of 1, 2 and 4
HEADS = 4

#: the cotangents' products of ``c = a . b`` by form: (operands, form) of da
#: and of db, ``g`` the cotangent of ``c``
_TRANSPOSED = {
    _NN: (("g", "b", _NT), ("a", "g", _TN)),
    _NT: (("g", "b", _NN), ("g", "a", _TN)),
    _TN: (("b", "g", _NT), ("a", "g", _NN)),
}


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mm(a, b, dims, hi):
    """``a . b`` contracting ``dims`` with f32 sums; ``hi``: at f32 precision
    (``_dot32``), else operands as they are (the compute type).  Its
    cotangents are products of the same three forms, so no transposition
    reaches Mosaic."""
    return (_dot32 if hi else _dot)(a, b, dims)


def _mm_fwd(a, b, dims, hi):
    return _mm(a, b, dims, hi), (a, b)


def _mm_bwd(dims, hi, res, g):
    a, b = res
    if not hi:
        g = g.astype(a.dtype)
    vals = dict(a=a, b=b, g=g)
    (x, y, da_dims), (z, w, db_dims) = _TRANSPOSED[dims]
    return (_mm(vals[x], vals[y], da_dims, hi).astype(a.dtype),
            _mm(vals[z], vals[w], db_dims, hi).astype(b.dtype))


_mm.defvjp(_mm_fwd, _mm_bwd)


def _inverse_cotangent(T, dT):
    """``dL`` of ``T = (I + L)^-1``: ``-T^T dT T^T`` below the diagonal."""
    row, col = _iotas()
    return jnp.where(row > col,
                     -_mm(_mm(T, dT, _TN, True), T, _NT, True), 0.0)


def _row_of(x, j):
    """Row ``j`` (static) of ``x [C, d]`` as ``[1, d]``, by a masked sum
    (whose cotangent is a ``where``, not a pad)."""
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.sum(jnp.where(rows == j, x, 0.0), axis=0, keepdims=True)


def _pair(a, kf, G, ends, mid, to_end, ct):
    """``A[t, s] = sum_c a_t[c] k_s[c] exp(G_t[c] - G_s[c])`` where ``s <=
    t`` (``ops/kda.py _pair_decay``; 0 above the diagonal).  ``a, kf, G [C,
    d]`` f32; ``ends``: the running sum at each sub-chunk's last position,
    ``mid`` / ``to_end``: for each row the sum at the middle of its sub-chunk
    and ``exp(its sub-chunk's last sum - G)``."""
    row, col = _iotas()
    shift = SUB.bit_length() - 1
    rb, cb = row >> shift, col >> shift
    sub = rb[:, :1]                                          # [C, 1]
    inv = (kf * jnp.exp(mid - G)).astype(ct)
    out = jnp.where((rb == cb) & (row >= col),
                    _mm((a * jnp.exp(G - mid)).astype(ct), inv, _NT,
                        False), 0.0)
    cols = kf * to_end
    for j, end in enumerate(ends[:-1]):
        # rows after sub-chunk j, decayed from its end; before it the
        # exponent is positive, clamped, and the entry masked
        rows = (a * jnp.exp(jnp.minimum(G - end, 0.0))).astype(ct)
        block = _mm(rows, jnp.where(sub == j, cols, 0.0).astype(ct), _NT,
                    False)
        out = jnp.where((cb == j) & (rb > j), block, out)
    return out


@jax.custom_vjp
def _inverses(Ls):
    """``(I + L)^-1`` of each of a tuple of strictly lower triangular ``L [C,
    C]`` by the scalar rule's blocked substitution, the substitutions run in
    step (``_together``): one head's fifteen dependent row steps fill the
    gaps of another's."""
    eye = jnp.where(jnp.equal(*_iotas()), 1.0, 0.0).astype(_BF16)
    return tuple(_together(
        _unit_lower_inverse(L, _dot32(L, eye, _TN)) for L in Ls))


def _inverses_fwd(Ls):
    Ts = _inverses(Ls)
    return Ts, Ts


def _inverses_bwd(Ts, dTs):
    return (tuple(_inverse_cotangent(T, dT) for T, dT in zip(Ts, dTs)),)


_inverses.defvjp(_inverses_fwd, _inverses_bwd)


def _open(q, k, g, beta_row):
    """A chunk up to its triangle ``L``: what the state does not enter.  A
    generator, as ``_close``: it yields between stages that depend on each
    other and returns its value at the end (``_together``)."""
    ct = k.dtype
    row, col = _iotas()
    eye, lower = row == col, row >= col
    qf, kf = q.astype(_F32), k.astype(_F32)
    # the running sum as a product with the triangle of ones (exact in bf16)
    G = _mm(jnp.where(lower, 1.0, 0.0).astype(_BF16), g, _NN, True)
    yield
    n = C // SUB
    ends = [_row_of(G, SUB * (j + 1) - 1) for j in range(n)]
    sub = row[:, :1] >> (SUB.bit_length() - 1)
    mids = [_row_of(G, SUB * j + SUB // 2) for j in range(n)]
    mid, end = mids[0], ends[0]
    for j in range(1, n):
        mid = jnp.where(sub == j, mids[j], mid)
        end = jnp.where(sub == j, ends[j], end)
    to_end = jnp.exp(end - G)
    yield
    beta = _to_col(beta_row, eye)
    L = jnp.where(row > col,
                  _pair(kf, kf, G, ends, mid, to_end, ct) * beta, 0.0)
    yield
    P = _pair(qf, kf, G, ends, mid, to_end, ct).astype(ct)
    return dict(L=L, P=P, G=G, qf=qf, kf=kf, G_end=ends[-1])


def _close(c, T, v, beta_row, S):
    """The rest of a chunk from the state ``S`` it starts at: ``(o f32 [C,
    d_v], the next state)``."""
    ct, G, kf = v.dtype, c["G"], c["kf"]
    Tb = T * beta_row
    eG = jnp.exp(G)
    Vp = _mm(Tb, v, _NN, True)
    W = _mm(Tb, kf * eG, _NN, True)
    yield
    u = Vp - _mm(W, S, _NN, True)
    yield
    o = _mm(c["qf"] * eG, S, _NN, True) + _mm(c["P"], u.astype(ct), _NN,
                                               False)
    yield
    # the whole chunk's decay a channel, as a column: [1, d_k] -> [d_k, 1]
    dk = G.shape[1]
    eye_k = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
             == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    a_col = jnp.sum(jnp.where(eye_k, jnp.broadcast_to(jnp.exp(c["G_end"]),
                                                      (dk, dk)), 0.0),
                    axis=1, keepdims=True)
    S_next = S * a_col + _mm(kf * jnp.exp(c["G_end"] - G), u, _TN, True)
    return o, S_next


def _chunks(heads):
    """One chunk of each of a program's heads, their chains in step: ``heads``
    a tuple of ``(q, k, v, g, beta_row, S)`` (``q, k [C, d_k]``, ``v [C,
    d_v]`` in the compute type, ``g [C, d_k]`` f32, ``beta_row [1, C]`` f32,
    ``S [d_k, d_v]`` f32) -> a tuple of ``(o f32 [C, d_v], the next state)``.
    Its backward pass is ``jax.vjp`` of it, whose order is this one's
    reversed: interleaved as well."""
    opened = _together(_open(q, k, g, b) for q, k, _, g, b, _ in heads)
    Ts = _inverses(tuple(c["L"] for c in opened))
    return tuple(_together(
        _close(c, T, v, b, S)
        for c, T, (_, _, v, _, b, S) in zip(opened, Ts, heads)))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, last_ref, s0_ref,
                s_ref, *, nc, hb, dk, dv):
    import jax.experimental.pallas as pl
    i = pl.program_id(2)
    lanes = _lanes(hb, dk, dv)

    @pl.when(i == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def body(j):
        rows = _rows(j)
        for h in range(hb):
            s0_ref[h, j] = s_ref[h]
        outs = _chunks(tuple(
            (q_ref[rows, kl], k_ref[rows, kl], v_ref[rows, vl],
             g_ref[rows, kl], _pick(b_ref[h], j), s_ref[h])
            for h, (kl, vl) in enumerate(lanes)))
        for h, (o, S) in enumerate(outs):
            o_ref[rows, lanes[h][1]] = o.astype(o_ref.dtype)
            s_ref[h] = S
    _walk(nc, body)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = s_ref[...]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, do_ref, dlast_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *, nc, hb, dk,
                dv):
    import jax.experimental.pallas as pl
    lanes = _lanes(hb, dk, dv)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = dlast_ref[...]

    def body(n):
        j = nc - 1 - n
        rows = _rows(j)
        _, pull = jax.vjp(_chunks, tuple(
            (q_ref[rows, kl], k_ref[rows, kl], v_ref[rows, vl],
             g_ref[rows, kl], _pick(b_ref[h], j), s0_ref[h, j])
            for h, (kl, vl) in enumerate(lanes)))
        (grads,) = pull(tuple(
            (do_ref[rows, vl].astype(_F32), ds_ref[h])
            for h, (_, vl) in enumerate(lanes)))
        for h, (dq, dk_, dv_, dg, dbeta, dS) in enumerate(grads):
            kl, vl = lanes[h]
            dq_ref[rows, kl] = dq.astype(dq_ref.dtype)
            dk_ref[rows, kl] = dk_.astype(dk_ref.dtype)
            dv_ref[rows, vl] = dv_.astype(dv_ref.dtype)
            dg_ref[rows, kl] = dg
            _put(db_ref.at[h], j, dbeta)
            ds_ref[h] = dS
    _walk(nc, body)


def _params(interpret):
    from jax.experimental.pallas import tpu as pltpu
    if interpret:
        return None
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


def _plan(beta, q, v, reverse):
    """Grid, the kernels' static sizes and the block specs of q / k / g, v /
    o, beta, the kept states and a state; ``reverse``: the blocks of chunks
    from the last to the first."""
    import jax.experimental.pallas as pl
    B, H, groups, nc, _ = beta.shape
    dk, dv = q.shape[2] // H, v.shape[2] // H
    hb = math.gcd(H, HEADS)
    at = (lambda i: groups - 1 - i) if reverse else (lambda i: i)
    seq = lambda d: pl.BlockSpec((None, nc * C, hb * d),
                                 lambda b, h, i: (b, at(i), h))
    gate = pl.BlockSpec((None, hb, None, nc, C),
                        lambda b, h, i: (b, h, at(i), 0, 0))
    kept = pl.BlockSpec((None, hb, None, nc, dk, dv),
                        lambda b, h, i: (b, h, at(i), 0, 0, 0))
    state = pl.BlockSpec((None, hb, dk, dv), lambda b, h, i: (b, h, 0, 0))
    return ((B, H // hb, groups), dict(nc=nc, hb=hb, dk=dk, dv=dv),
            (seq(dk), seq(dv), gate, kept, state))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fwd_call(q, k, v, g, beta, *, interpret):
    """``q, k, g [B, T, H dk]``, ``v [B, T, H dv]``, ``beta [B, H, T / (n C),
    n, C]`` f32 (``n`` chunks a program): ``(o [B, T, H dv], last state [B, H,
    dk, dv], chunk-start states [B, H, T / (n C), n, dk, dv])``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    grid, dims, (qk, vo, gate, kept, state) = _plan(beta, q, v, False)
    B, H, groups, nc, _ = beta.shape
    dk, dv, hb = dims["dk"], dims["dv"], dims["hb"]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **dims),
        name="hetu_kda_fwd", grid=grid,
        in_specs=[qk, qk, vo, qk, gate], out_specs=[vo, state, kept],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((B, H, dk, dv), _F32),
                   jax.ShapeDtypeStruct((B, H, groups, nc, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=_params(interpret), interpret=interpret,
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_call(q, k, v, g, beta, states, do, dlast, *, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    grid, dims, (qk, vo, gate, kept, state) = _plan(beta, q, v, True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, **dims),
        name="hetu_kda_bwd", grid=grid,
        in_specs=[qk, qk, vo, qk, gate, kept, vo, state],
        out_specs=[qk, qk, vo, qk, gate],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((dims["hb"], dims["dk"], dims["dv"]),
                                   _F32)],
        compiler_params=_params(interpret), interpret=interpret,
    )(q, k, v, g, beta, states, do, dlast)


@jax.custom_vjp
def _rule(q, k, v, g, beta):
    return _rule_fwd(q, k, v, g, beta)[0]


def _rule_fwd(q, k, v, g, beta):
    o, last, states = _fwd_call(q, k, v, g, beta,
                                interpret=dispatch.interpret())
    return (o, last), (q, k, v, g, beta, states)


def _rule_bwd(res, grads):
    do, dlast = grads
    return tuple(_bwd_call(*res, do, dlast, interpret=dispatch.interpret()))


_rule.defvjp(_rule_fwd, _rule_bwd)


def unsupported(q, k, v, g, chunk):
    """Why the kernels do not take ``chunk_kda``'s operands, or None when
    they do."""
    if chunk != C:
        return f"chunk!={C}"
    if q.shape[-1] % 128 or v.shape[-1] % 128:
        return "head_dim_not_128_aligned"
    if not q.dtype == k.dtype == v.dtype:
        return "dtype:mixed"
    if jnp.dtype(v.dtype) not in (jnp.dtype(_BF16), jnp.dtype(_F32)):
        return f"dtype:{jnp.dtype(v.dtype).name}"
    if jnp.dtype(g.dtype) != jnp.dtype(_F32):
        return f"gate_dtype:{jnp.dtype(g.dtype).name}"
    return None


def kda(q, k, v, g, beta):
    """``chunk_kda`` at chunk 64 through the kernel pair: ``q, k [B, T, H,
    d_k]``, ``v [B, T, H, d_v]``, ``g [B, T, H, d_k]`` f32, ``beta [B, T, H]``
    -> ``(o [B, T, H, d_v]`` in ``v``'s type, the last state ``[B, H, d_k,
    d_v]`` f32)``.  Any ``T``: positions of padding write nothing (beta 0),
    decay nothing (g 0) and their outputs are cut off."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    nc = min(CHUNKS, -(-T // C))
    groups = -(-T // (nc * C))
    pad = groups * nc * C - T

    def rows(x):                       # [B, T, H, d] -> [B, T', H d]
        x = x.reshape(B, T, -1)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    beta = beta.astype(_F32)
    if pad:
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    beta = jnp.moveaxis(beta, 2, 1).reshape(B, H, groups, nc, C)
    o, last = _rule(rows(q), rows(k), rows(v), rows(g), beta)
    return o[:, :T].reshape(B, T, H, dv), last
