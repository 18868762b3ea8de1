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
VMEM scratch.  A program rebuilds its chunks from its operands, the kept
chunk-start states and the kept inverses and pulls ``do`` and ``dS`` back
through them: the backward pass of a chunk is ``jax.vjp`` of ``_chunks``,
traced into the kernel.  Two rules are given by hand so that what Mosaic is
handed are the three forms of product it lowers without a transposition
(``_mm``) and no walk back through the substitution (``_inverses``: ``dL =
-T^T dT T^T``).  The backward kernel does not solve at all: ``_chunks`` takes
the heads' ``T`` as the forward kernel wrote them (``_kept_inverses``: the
kept value, ``_inverses``' cotangent), so neither the substitution, its two
merges nor the product that forms ``L^T`` is traced there
(``hetu_delta_inverse_total{rule="kda", source}`` counts a forward call
traced as ``solved`` and a backward one as ``kept``).  What the backward
keeps is the operands, the chunk-start states and the chunks' inverses ``T
[B, H, T / (n C), n, 64, 64]`` f32, exactly what ``unit_lower_inverse``
returned (16 KiB a chunk and head; 64 MiB a layer of the Ling-3.0 cell and
128 MiB in HBM, whose tiles are 128 lanes wide; alive, as the states, only
while that layer's backward pass runs; ``gated_delta.py`` has why the tiles
stay half empty; ``hetu_kda_bwd`` 9.07 -> 6.70 ms a call on a v5e, PERF.md,
PR 66).

Two entries of the one kernel pair, by what the caller holds (counted in
``hetu_kda_entry_total{form}``, ``entries()``).  ``kda`` (``plain``) takes
``chunk_kda``'s ``q, k, v, g, beta``.  ``kda_in_place`` (``in_place``, PR
41) takes what the layer has: the convolution's output ``mixed [B, T, 3 H
d]`` (``q~ | k~ | v``) and the projection ``proj [B, T, 5 H d]`` (``.. | f |
z``), each window a block spec whose lane-block index starts at the window's
(every offset a multiple of ``HEADS d`` lanes; no slice exists in HBM), and
the small parameters as rows.  The head's norms and its gate are then a
prologue of ``_open`` on the ``[64, 128]`` chunk in VMEM (``q = l2norm(q~) /
sqrt(d)``, ``k = l2norm(k~)``, ``g = lower_bound sigmoid(rate (f + bias))``,
f32) and the gated RMS norm an epilogue of ``_close`` on the f32 ``o``
before its one cast, so the layer forms no ``[B, T, H, d]`` view, which on a
TPU is another tiling and a pass over HBM each way.  Their cotangents come
from the same ``jax.vjp``: the kernel writes ``dq~, dk~, dv, df, dz`` in the
compute type and the small parameters' as f32 partial sums a batch row and
head (output blocks resident over the sequence; XLA adds them).  No f32 ``[B,
T, H d]`` array reaches HBM in either pass, and the residuals are ``mixed``,
``proj``, ``beta``, the chunk-start states and the chunks' inverses.

Inside a chunk the decays are taken sub-chunk by sub-chunk of 16 positions
(``_pair``): the diagonal ``[16, 16]`` blocks from one ``[64, 64]`` product
of rows and columns both taken relative to the middle of their sub-chunk,
the blocks below from one product a column sub-chunk (rows decayed from its
end, clamped at one where they lie before it; columns decayed to it).
``g >= -5`` a position keeps every factor within ``exp(+-40)``.

What a chunk of one head asks of the matrix unit (``chunk_products``: the
``dot_general``s of ``_chunks`` and of ``jax.vjp`` of it; ``passes``: one a
product and 128 of its contraction; ``tests/test_kda_passes.py`` holds both
and the gauge ``hetu_kda_chunk_passes{kernel}`` shows the passes).  A product
at f32 precision multiplies all three bf16 parts of an f32 operand: three
``dot_general``s where the other operand is bf16, six where both are f32
(``dot32``), or one over the parts stacked along the contraction
(``dot32_stacked``).  PR 64 changed two things and no part of any operand:
``u = T beta (v - (k e^G) S)`` is one product behind the inverse where ``T
beta v - (T beta (k e^G)) S`` was two in a chain, and the f32 products whose
contraction is whole lane tiles or runs down the rows are one product each:

    stage                            forward          backward kernel
                                     products passes  products  passes
    G = triangle of ones x g          3 ->  3  3 ->  3   12 ->  5  12 ->  11
    the pair matrices (_pair)         8     8  8     8   24    24  24     24
    L^T (through the matrix unit)     3     3  3     3    3     -   3      -
    the inverse's two merges         24    24 24    24   24     -  24      -
    the inverse's cotangent           -     -  -     -   12     7  12      9
    products with S                  12     2 12    12   36     6  36     30
    products with T beta              9     6  9     6   30     8  30     15
    P u                               1     1  1     1    3     3   3      3
    the next state                    6     1  6     3   18     3  18     15
    a chunk                          66    48 66    60  162    56 162    107

(The backward columns' right-hand sides were 83 products and 134 passes until
PR 66: the kernel solved for the inverse again, ``L^T`` and the two merges,
where it now reads the forward kernel's.)

The time does not follow the passes: the body of the chunk walk is bound
by the vector unit (splitting f32 operands into parts, adding partial
products, the masks of the inverse and of the pair matrices: the forward
body of four heads compiled for a v5e is 18,500 vector operations, 4,600
bundles' worth on four slots, in the 6,600 bundles the scheduler packs, a
count and not a time), so a product's sum moved into the matrix unit is
worth more than a pass saved.  The pair alone at the Ling-3.0
layer's shape (``[1, 8192, 32 x 128]`` bf16, ``in_place``; my chip run, PR
64, call 64.1, ten calls in a row by the host's clock; ms a forward and a
backward call): as PR 41 left them 4.87 and 10.25; ``u`` from one product
with ``T beta`` 4.87 and 9.83; with ``q e^G`` and ``k e^G`` stacked by rows
into one product with ``S`` 4.89 and 9.37 (a pass of 128 rows costs the
forward kernel two of 64; the backward one gains where ONE contraction down
128 rows stands for two down 64) and with ``[k; q]`` through one ``_pair``
4.94 and 9.26; the state products in a stage of their own before the
inverses 5.09 and 9.92 (slower forward: they stay in ``_close``); ``u`` from
one product and the stacked contraction, as this file has them, **4.51 and
9.18**, and then the products of 128 rows are worth nothing more (4.65 and
9.30 with the one with ``S``, 4.57 and 9.03 with ``_pair``'s: under 1% of
the two kernels, so absent).  ``plain``: 4.73 and 9.50 -> 4.39 and 8.42.

A chunk is one chain of dependent steps and Mosaic's scheduler stays close
to program order, so a program's heads run their chains in step as the
scalar rule's do (``together``: ``_open`` and ``_close`` are generators that
yield between dependent stages, and the heads' triangular inverses are one
``custom_vjp`` that runs their substitutions in step); the backward pass,
being the transposition of that trace, is interleaved the same way.  On a
v5e a layer of 32 heads over 8,192 positions (my chip run, PR 40): one head
a program 10.3 ms forward and 25.7 forward and backward, two 6.4 and 17.4,
four 5.7 and 15.7.  In the Ling-3.0 step (my chip run, PR 41): ``plain`` 4.61
ms a forward and 9.38 a backward call with 64 ms a step of XLA's norms,
gates and re-tilings around them, ``in_place`` 4.75 and 10.05 with 3.7.

Precision as ``ops/pallas/gated_delta.py``: the state, the decays, ``T`` and
every operand of a product with them are f32, multiplied as bf16 passes
over their three bf16 parts (``dot32``, ``dot32_stacked``: the same pairs of
parts, f32 sums); the pair matrices and ``P u`` take
their operands in the compute type.
"""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp

from . import common, dispatch
from ... import telemetry
from .common import (C, NN, NT, TN, VMEM_LIMIT, WALK, chunk_rows, dot, dot32,
                     dot32_stacked, head_lanes, iotas, params, pick, put,
                     to_col, together, unit, unit_lower_inverse, walk)
from ..kda import SUB

_F32 = jnp.float32
_BF16 = jnp.bfloat16

#: heads a program runs in step (``together``); the module's docstring has
#: the times of 1, 2 and 4
HEADS = 4

#: the cotangents' products of ``c = a . b`` by form: (operands, form) of da
#: and of db, ``g`` the cotangent of ``c``
_TRANSPOSED = {
    NN: (("g", "b", NT), ("a", "g", TN)),
    NT: (("g", "b", NN), ("g", "a", TN)),
    TN: (("b", "g", NT), ("a", "g", NN)),
}


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mm(a, b, dims, hi):
    """``a . b`` contracting ``dims`` with f32 sums; ``hi``: at f32 precision
    (all parts of an f32 operand: ``dot32``, and as ONE product over the
    parts put one behind the other, ``dot32_stacked``, where the contraction
    is whole lane tiles or runs down the rows, so the parts are stacked
    without a copy), else operands as they are (the compute type).  Its
    cotangents are products of the same three forms, so no transposition
    reaches Mosaic."""
    if not hi:
        return dot(a, b, dims)
    whole = dims == TN or a.shape[dims[0][0]] % 128 == 0
    return (dot32_stacked if whole else dot32)(a, b, dims)


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
    row, col = iotas()
    return jnp.where(row > col,
                     -_mm(_mm(T, dT, TN, True), T, NT, True), 0.0)


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
    row, col = iotas()
    shift = SUB.bit_length() - 1
    rb, cb = row >> shift, col >> shift
    sub = rb[:, :1]                                          # [C, 1]
    inv = (kf * jnp.exp(mid - G)).astype(ct)
    out = jnp.where((rb == cb) & (row >= col),
                    _mm((a * jnp.exp(G - mid)).astype(ct), inv, NT,
                        False), 0.0)
    cols = kf * to_end
    for j, end in enumerate(ends[:-1]):
        # rows after sub-chunk j, decayed from its end; before it the
        # exponent is positive, clamped, and the entry masked
        rows = (a * jnp.exp(jnp.minimum(G - end, 0.0))).astype(ct)
        block = _mm(rows, jnp.where(sub == j, cols, 0.0).astype(ct), NT,
                    False)
        out = jnp.where((cb == j) & (rb > j), block, out)
    return out


@jax.custom_vjp
def _inverses(Ls):
    """``(I + L)^-1`` of each of a tuple of strictly lower triangular ``L [C,
    C]`` by the scalar rule's blocked substitution, the substitutions run in
    step (``together``): one head's fifteen dependent row steps fill the
    gaps of another's."""
    eye = jnp.where(jnp.equal(*iotas()), 1.0, 0.0).astype(_BF16)
    return tuple(together(
        unit_lower_inverse(L, dot32(L, eye, TN)) for L in Ls))


def _inverses_fwd(Ls):
    Ts = _inverses(Ls)
    return Ts, Ts


def _inverses_bwd(Ts, dTs):
    return (tuple(_inverse_cotangent(T, dT) for T, dT in zip(Ts, dTs)),)


_inverses.defvjp(_inverses_fwd, _inverses_bwd)


@jax.custom_vjp
def _kept_inverses(Ls, Ts):
    """``_inverses(Ls)`` where the forward kernel kept them: ``Ts`` as they
    are, with ``_inverses``' cotangent for ``Ls``; no substitution and no
    ``L^T`` is traced."""
    return Ts


def _kept_inverses_fwd(Ls, Ts):
    return Ts, Ts


def _kept_inverses_bwd(Ts, dTs):
    return _inverses_bwd(Ts, dTs) + (tuple(jnp.zeros_like(T) for T in Ts),)


_kept_inverses.defvjp(_kept_inverses_fwd, _kept_inverses_bwd)


def _open(q, k, g, beta_row, gate=None):
    """A chunk up to its triangle ``L``: what the state does not enter.  A
    generator, as ``_close``: it yields between stages that depend on each
    other and returns its value at the end (``together``).  ``gate``: None,
    or ``(rate, bias [1, d_k] f32, lower_bound)`` where ``q, k`` are the
    convolution's ``q~, k~`` and ``g`` the projection's ``f``: the head's
    norms and its gate are then taken here, on the chunk (rounded to the
    compute type where the layer's ``jax.numpy`` form rounds them)."""
    ct = k.dtype
    if gate is not None:
        rate, bias, lower = gate
        q = (unit(q)[0] * q.shape[1] ** -0.5).astype(ct)
        k = unit(k)[0].astype(ct)
        g = lower * jax.nn.sigmoid(rate * (g.astype(_F32) + bias))
        yield
    row, col = iotas()
    eye, lower = row == col, row >= col
    qf, kf = q.astype(_F32), k.astype(_F32)
    # the running sum as a product with the triangle of ones (exact in bf16)
    G = _mm(jnp.where(lower, 1.0, 0.0).astype(_BF16), g, NN, True)
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
    beta = to_col(beta_row, eye)
    L = jnp.where(row > col,
                  _pair(kf, kf, G, ends, mid, to_end, ct) * beta, 0.0)
    yield
    P = _pair(qf, kf, G, ends, mid, to_end, ct).astype(ct)
    return dict(L=L, P=P, G=G, qf=qf, kf=kf, G_end=ends[-1])


def _close(c, T, v, beta_row, S, norm=None):
    """The rest of a chunk from the state ``S`` it starts at: ``(o f32 [C,
    d_v], the next state)``.  ``norm``: None, or ``(z [C, d_v], scale [1,
    d_v] f32, eps)``: ``o`` is then the head's gated RMS norm of it, ``o
    rsqrt(mean(o^2) + eps) scale sigmoid(z)``, taken on the f32 chunk."""
    ct, G, kf = v.dtype, c["G"], c["kf"]
    eG = jnp.exp(G)
    # u = T beta (v - (k e^G) S): one product behind T, where T beta v - (T
    # beta (k e^G)) S was two in a chain; what reads S waits for G alone
    X = v.astype(_F32) - _mm(kf * eG, S, NN, True)
    QS = _mm(c["qf"] * eG, S, NN, True)
    yield
    u = _mm(T * beta_row, X, NN, True)
    yield
    o = QS + _mm(c["P"], u.astype(ct), NN, False)
    yield
    if norm is not None:
        z, scale, eps = norm
        o = (o * jax.lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
             * scale * jax.nn.sigmoid(z.astype(_F32)))
    # the whole chunk's decay a channel, as a column: [1, d_k] -> [d_k, 1]
    dk = G.shape[1]
    eye_k = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
             == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
    a_col = jnp.sum(jnp.where(eye_k, jnp.broadcast_to(jnp.exp(c["G_end"]),
                                                      (dk, dk)), 0.0),
                    axis=1, keepdims=True)
    S_next = S * a_col + _mm(kf * jnp.exp(c["G_end"] - G), u, TN, True)
    return o, S_next


def _chunks(heads, gate=None, kept=None):
    """One chunk of each of a program's heads, their chains in step: ``heads``
    a tuple of ``(q, k, v, g, beta_row, S)`` (``q, k [C, d_k]``, ``v [C,
    d_v]`` in the compute type, ``g [C, d_k]`` f32, ``beta_row [1, C]`` f32,
    ``S [d_k, d_v]`` f32) -> a tuple of ``(o f32 [C, d_v], the next state)``
    and, beside it, the heads' inverses ``T [C, C]`` f32 (for ``jax.vjp``,
    ``has_aux``).  ``gate = (lower_bound, eps)`` (static): a head is ``(q~,
    k~, v, f, beta_row, S, z, rate, bias, scale)`` as the layer has them and
    ``o`` what its output product reads (``_open``, ``_close``).  ``kept``:
    the heads' inverses as the forward kernel wrote them, or None to solve
    for them.  Its backward pass is ``jax.vjp`` of it, whose order is this
    one's reversed: interleaved as well."""
    def small(h):
        if gate is None:
            return None, None
        z, rate, bias, scale = h[6:]
        return (rate, bias, gate[0]), (z, scale, gate[1])
    opened = together(_open(h[0], h[1], h[3], h[4], small(h)[0])
                       for h in heads)
    Ls = tuple(c["L"] for c in opened)
    Ts = _inverses(Ls) if kept is None else _kept_inverses(Ls, kept)
    return tuple(together(
        _close(c, T, h[2], h[4], h[5], small(h)[1])
        for c, T, h in zip(opened, Ts, heads))), Ts


def _head(ins, rows, j, h, kl, vl, S):
    """Head ``h``'s operands of ``_chunks`` at chunk ``j`` from a kernel's
    input refs: ``q, k, v, g, beta`` and, in place, ``z, rate, bias, scale``
    behind them."""
    q_ref, k_ref, v_ref, g_ref, b_ref, *small = ins
    head = (q_ref[rows, kl], k_ref[rows, kl], v_ref[rows, vl],
            g_ref[rows, kl], pick(b_ref[h], j), S)
    if small:
        z_ref, rate_ref, bias_ref, scale_ref = small
        head += (z_ref[rows, vl], rate_ref[:, kl], bias_ref[:, kl],
                 scale_ref[...])
    return head


def _inputs(gate):
    """How many of a kernel's refs ``_head`` reads."""
    return 5 if gate is None else 9


def _fwd_kernel(*refs, nc, hb, dk, dv, gate):
    import jax.experimental.pallas as pl
    n = _inputs(gate)
    ins, (o_ref, last_ref, s0_ref, t_ref, s_ref) = refs[:n], refs[n:]
    i = pl.program_id(2)
    lanes = head_lanes(hb, dk, dv)

    @pl.when(i == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def body(j):
        rows = chunk_rows(j, C)
        for h in range(hb):
            s0_ref[h, j] = s_ref[h]
        outs, Ts = _chunks(tuple(_head(ins, rows, j, h, kl, vl, s_ref[h])
                                 for h, (kl, vl) in enumerate(lanes)), gate)
        for h, ((o, S), T) in enumerate(zip(outs, Ts)):
            o_ref[rows, lanes[h][1]] = o.astype(o_ref.dtype)
            s_ref[h] = S
            t_ref[h, j] = T
    walk(nc, body)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = s_ref[...]


def _bwd_kernel(*refs, nc, hb, dk, dv, gate):
    import jax.experimental.pallas as pl
    n = _inputs(gate)
    ins, (s0_ref, t_ref, do_ref, dlast_ref) = refs[:n], refs[n:n + 4]
    (dq_ref, dk_ref, dv_ref, dg_ref, db_ref, *dsmall), ds_ref = (
        refs[n + 4:-1], refs[-1])
    lanes = head_lanes(hb, dk, dv)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = dlast_ref[...]
        for ref in dsmall[1:]:           # the small parameters' partial sums
            ref[...] = jnp.zeros_like(ref)

    def body(m):
        j = nc - 1 - m
        rows = chunk_rows(j, C)
        kept = tuple(t_ref[h, j] for h in range(hb))
        _, pull, _ = jax.vjp(
            functools.partial(_chunks, gate=gate, kept=kept),
            tuple(_head(ins, rows, j, h, kl, vl, s0_ref[h, j])
                  for h, (kl, vl) in enumerate(lanes)), has_aux=True)
        (grads,) = pull(tuple(
            (do_ref[rows, vl].astype(_F32), ds_ref[h])
            for h, (_, vl) in enumerate(lanes)))
        for h, (dq, dk_, dv_, dg, dbeta, dS, *rest) in enumerate(grads):
            kl, vl = lanes[h]
            dq_ref[rows, kl] = dq.astype(dq_ref.dtype)
            dk_ref[rows, kl] = dk_.astype(dk_ref.dtype)
            dv_ref[rows, vl] = dv_.astype(dv_ref.dtype)
            dg_ref[rows, kl] = dg.astype(dg_ref.dtype)
            put(db_ref.at[h], j, dbeta)
            ds_ref[h] = dS
            if rest:
                dz_ref, *sums = dsmall
                dz_ref[rows, vl] = rest[0].astype(dz_ref.dtype)
                for ref, part in zip(sums, rest[1:]):
                    ref[:, kl] += part
    walk(nc, body)


def _plan(ops, gate, reverse):
    """Grid, the kernels' static sizes, their inputs with a block spec each,
    and the block specs by name of a ``[B, T, H d]`` array (``qk``, ``vo``),
    beta, the kept states, the kept inverses, a state and a small parameter's
    partial sums;
    ``reverse``: the blocks of chunks from the last to the first.  ``ops``:
    ``q, k, v, g, beta``, or with a ``gate`` ``mixed, proj, beta, rate, bias,
    scale``: ``mixed [B, T, 3 H d]`` is then read as its three and ``proj
    [B, T, 5 H d]`` as its last two windows of ``H d`` lanes, each a block
    whose lane index starts at the window's."""
    import jax.experimental.pallas as pl
    beta = ops[4 if gate is None else 2]
    B, H, groups, nc, _ = beta.shape
    if gate is None:
        dk, dv = ops[0].shape[2] // H, ops[2].shape[2] // H
    else:
        dk = dv = ops[0].shape[2] // (3 * H)
    hb = math.gcd(H, HEADS)
    block, kept, state = common.blocks(nc, C, groups, reverse)
    # a window of ``H d`` lanes is ``H / hb`` blocks of a program's
    seq = lambda d, window=0: block(hb * d, window * (H // hb))
    rows = kept(hb, C)
    names = dict(
        qk=seq(dk), vo=seq(dv), rows=rows, kept=kept(hb, dk, dv),
        inverse=kept(hb, C, C), state=state(hb, dk, dv),
        sums=pl.BlockSpec((None, 1, hb * dk), lambda b, h, i: (b, 0, h)))
    if gate is None:
        args, specs = ops, [seq(dk), seq(dk), seq(dv), seq(dk), rows]
    else:
        mixed, proj, beta, rate, bias, scale = ops
        lane = pl.BlockSpec((1, hb * dk), lambda b, h, i: (0, h))
        args = (mixed, mixed, mixed, proj, beta, proj, rate, bias, scale)
        specs = [seq(dk), seq(dk, 1), seq(dv, 2), seq(dk, 3), rows,
                 seq(dv, 4), lane, lane,
                 pl.BlockSpec((1, dv), lambda b, h, i: (0, 0))]
    return ((B, H // hb, groups), dict(nc=nc, hb=hb, dk=dk, dv=dv, gate=gate),
            args, specs, names)


@functools.partial(jax.jit, static_argnames=("gate", "interpret"))
def _fwd_call(*ops, gate, interpret):
    """``q, k, g [B, T, H dk]``, ``v [B, T, H dv]``, ``beta [B, H, T / (n C),
    n, C]`` f32 (``n`` chunks a program), or with a ``gate`` the operands
    ``_plan`` names: ``(o [B, T, H dv], last state [B, H, dk, dv],
    chunk-start states [B, H, T / (n C), n, dk, dv], the chunks' inverses
    [B, H, T / (n C), n, C, C] f32)``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    grid, dims, args, specs, at = _plan(ops, gate, False)
    nc, dk, dv, hb = (dims[n] for n in ("nc", "dk", "dv", "hb"))
    B, H, groups = grid[0], grid[1] * hb, grid[2]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **dims),
        name="hetu_kda_fwd", grid=grid, in_specs=specs,
        out_specs=[at["vo"], at["state"], at["kept"], at["inverse"]],
        out_shape=[jax.ShapeDtypeStruct(args[0].shape[:2] + (H * dv,),
                                        args[2].dtype),
                   jax.ShapeDtypeStruct((B, H, dk, dv), _F32),
                   jax.ShapeDtypeStruct((B, H, groups, nc, dk, dv), _F32),
                   jax.ShapeDtypeStruct((B, H, groups, nc, C, C), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=params(interpret, WALK, VMEM_LIMIT),
        interpret=interpret,
    )(*args)


@functools.partial(jax.jit, static_argnames=("gate", "interpret"))
def _bwd_call(*ops, gate, interpret):
    """``_fwd_call``'s operands, the kept states and inverses, ``do`` and the
    last state's cotangent: ``dq, dk, dv, dg, dbeta`` and, with a ``gate`` (where
    they are ``dq~, dk~, dv, df``, all ``[B, T, H d]`` in the compute type),
    ``dz`` and the partial sums ``[B, 1, H d]`` f32 of ``rate``'s, ``bias``'s
    and, a head, ``scale``'s."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    *ops, states, inverses, do, dlast = ops
    grid, dims, args, specs, at = _plan(ops, gate, True)
    qk, vo = at["qk"], at["vo"]
    dk, dv, H = dims["dk"], dims["dv"], states.shape[1]
    like = lambda x, d, dtype=None: jax.ShapeDtypeStruct(
        x.shape[:2] + (H * d,), dtype or x.dtype)
    q, k, v, g, beta = args[:5]
    sums = jax.ShapeDtypeStruct((grid[0], 1, H * dk), _F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, **dims),
        name="hetu_kda_bwd", grid=grid,
        in_specs=specs + [at["kept"], at["inverse"], vo, at["state"]],
        out_specs=[qk, qk, vo, qk, at["rows"]] + (
            [] if gate is None else [vo] + [at["sums"]] * 3),
        out_shape=[like(q, dk), like(k, dk), like(v, dv),
                   like(g, dk, _F32 if gate is None else None),
                   jax.ShapeDtypeStruct(beta.shape, _F32)] + (
                       [] if gate is None else [like(v, dv)] + [sums] * 3),
        scratch_shapes=[pltpu.VMEM((dims["hb"], dk, dv), _F32)],
        compiler_params=params(interpret, WALK, VMEM_LIMIT),
        interpret=interpret,
    )(*args, states, inverses, do, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rule(gate, *ops):
    return _rule_fwd(gate, *ops)[0]


def _rule_fwd(gate, *ops):
    dispatch.count_inverse("kda", "solved")
    o, last, states, inverses = _fwd_call(*ops, gate=gate,
                                          interpret=dispatch.interpret())
    return (o, last), ops + (states, inverses)


def _rule_bwd(gate, res, grads):
    dispatch.count_inverse("kda", "kept")
    out = _bwd_call(*res, *grads, gate=gate, interpret=dispatch.interpret())
    if gate is None:
        return tuple(out)
    dq, dk, dv, df, dbeta, dz, drate, dbias, dscale = out
    proj, scale = res[1], res[5]
    # the windows' cotangents in the arrays': nothing reads the first three
    # windows of ``proj`` here
    rest = jnp.zeros(proj.shape[:2] + (proj.shape[2] - 2 * df.shape[2],),
                     proj.dtype)
    return (jnp.concatenate([dq, dk, dv], -1),
            jnp.concatenate([rest, df, dz], -1), dbeta, drate.sum(0),
            dbias.sum(0),
            dscale.reshape(-1, scale.shape[1]).sum(0, keepdims=True))


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


def _products(jaxpr, into):
    """Count the ``dot_general``s of ``jaxpr`` and of every jaxpr under it
    ``into`` a counter, by ``(rows, contraction, columns)``."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (ca, cb), _ = eqn.params["dimension_numbers"]
            a, b = (v.aval.shape for v in eqn.invars)
            into[a[1 - ca[0]], a[ca[0]], b[1 - cb[0]]] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _products(sub, into)
    return into


@functools.lru_cache(maxsize=None)
def chunk_products(form):
    """The products of one chunk of one head (of 128 channels, bf16
    operands), traced and not run: ``{"fwd": counter, "bwd": counter}`` of
    the ``dot_general``s of ``_chunks`` and of ``jax.vjp`` of it as
    ``_bwd_kernel`` takes it (the forward again with the inverse kept, then
    its transposition), each keyed ``(rows, contraction, columns)``.  ``form``: ``plain`` or
    ``in_place``.  The module's docstring has the table this is held to
    (``tests/test_kda_passes.py``)."""
    d = 128
    of = lambda rows, cols, t: jax.ShapeDtypeStruct((rows, cols), t)
    wide, state = of(C, d, _BF16), of(d, d, _F32)
    head = (wide, wide, wide, of(C, d, _F32), of(1, C, _F32), state)
    gate = None
    if form == "in_place":
        small = of(1, d, _F32)
        head = (wide,) * 4 + head[4:] + (wide, small, small, small)
        gate = (-5.0, 1e-6)
    def pulled(heads, kept, cotangents):
        chunks = functools.partial(_chunks, gate=gate, kept=kept)
        return jax.vjp(chunks, heads, has_aux=True)[1](cotangents)
    traced = dict(
        fwd=jax.make_jaxpr(functools.partial(_chunks, gate=gate))((head,)),
        bwd=jax.make_jaxpr(pulled)((head,), (of(C, C, _F32),),
                                   ((of(C, d, _F32), state),)))
    return {kernel: _products(closed.jaxpr, collections.Counter())
            for kernel, closed in traced.items()}


def passes(products):
    """The passes of the matrix unit behind a counter of products: one for
    each 128 of a product's contraction (its operands are bf16 and no wider
    than 128 columns)."""
    return sum(n * -(-contraction // 128)
               for (_, contraction, _), n in products.items())


def _count_entry(form):
    """Trace-time count of the entry taken, beside ``dispatch.record``'s
    count of the kernel-versus-jnp choice, and what a chunk of it asks of the
    matrix unit (``passes`` of ``chunk_products``: a gauge, traced only while
    telemetry is on)."""
    registry = telemetry.get_registry()
    registry.counter(
        "hetu_kda_entry_total",
        "Trace-time calls of the delta rule's kernels by what they are "
        "handed: the layer's arrays read in place with its norms and gates "
        "in the kernel, or q, k, v and g",
        labels=("form",),
    ).labels(form=form).inc()
    if telemetry.enabled():
        gauge = registry.gauge(
            "hetu_kda_chunk_passes",
            "Passes of the matrix unit (one a product and 128 of its "
            "contraction) in one chunk of one head of the delta rule's "
            "forward kernel and of its backward kernel (the forward again "
            "with the inverse kept, then its transposition), at the last "
            "call traced",
            labels=("kernel",))
        for kernel, products in chunk_products(form).items():
            gauge.labels(kernel=kernel).set(passes(products))


def entries():
    """``{form: count}`` of the calls traced so far, ``form`` ``in_place`` or
    ``plain`` (empty while telemetry is disabled)."""
    return {lab["form"]: n
            for lab, n in dispatch.counted("hetu_kda_entry_total")}


def kda(q, k, v, g, beta):
    """``chunk_kda`` at chunk 64 through the kernel pair: ``q, k [B, T, H,
    d_k]``, ``v [B, T, H, d_v]``, ``g [B, T, H, d_k]`` f32, ``beta [B, T, H]``
    -> ``(o [B, T, H, d_v]`` in ``v``'s type, the last state ``[B, H, d_k,
    d_v]`` f32)``.  Any ``T``: positions of padding write nothing (beta 0),
    decay nothing (g 0) and their outputs are cut off."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    cut = common.cut(T)
    _count_entry("plain")
    o, last = _rule(None, *(common.rows(x, cut[2]) for x in (q, k, v, g)),
                    common.by_chunk(beta, *cut))
    return o[:, :T].reshape(B, T, H, dv), last


#: the projection's value at positions of padding: ``f`` there shuts the
#: gate (``sigmoid`` of it times any rate is 0 and so is its slope: g 0, as
#: ``kda``'s padding) and ``z`` the output's
_SHUT = -1e9


def kda_in_place(mixed, proj, beta, rate, bias, scale, *, lower_bound, eps):
    """The mixer between its convolution and its output product through the
    same kernel pair: ``mixed [B, T, 3 H d]`` (``q~ | k~ | v``, the
    convolution's output) and ``proj [B, T, 5 H d]`` (of which ``f`` at lane
    ``3 H d`` and ``z`` at ``4 H d``) are read in place, a window a block
    spec; ``beta [B, T, H]``; ``rate`` and ``bias`` one number a channel ``[H
    d]``, ``scale [d]`` -> ``y [B, T, H d]`` in ``mixed``'s type, with ``q =
    l2norm(q~) / sqrt(d)``, ``k = l2norm(k~)``, ``g = lower_bound
    sigmoid(rate (f + bias))`` and ``y = o rsqrt(mean(o^2) + eps) scale
    sigmoid(z)`` a head, all on the chunk in VMEM.  Any ``T`` (a padded copy
    where 512 does not divide it)."""
    T = beta.shape[1]
    cut = common.cut(T)
    _count_entry("in_place")
    row = lambda x: x.astype(_F32).reshape(1, -1)
    y, _ = _rule((float(lower_bound), float(eps)), common.rows(mixed, cut[2]),
                 common.rows(proj, cut[2], constant_values=_SHUT),
                 common.by_chunk(beta, *cut), row(rate), row(bias), row(scale))
    return y[:, :T]
