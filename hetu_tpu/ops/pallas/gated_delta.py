"""The chunked gated delta rule as two Pallas kernels (``ops/gated_delta.py``
has the mathematics and the ``jax.numpy`` form these are held to).

``hetu_gdn_fwd``: grid (batch, block of value heads, block of chunks), the
last axis sequential.  The states ``S [d_k, d_v]`` f32 of a program's
``HEADS`` heads stay in VMEM scratch from their first chunk to their last.  A
program walks ``CHUNKS`` chunks of 64 positions: it reads their q, k, v rows
in place from the ``[B, T, H * d]`` view (a ``[rows, HEADS * d]`` block at
lane offset ``h * d``: no ``[B, H, N, C, d]`` copy exists), g and beta from
``[B, H, T / (n C), n, C]``.  Two entries hand it its operands.
``gated_delta_rule`` takes q and k normalised, scaled and repeated a value
head by the caller.  ``gated_delta_rule_in_place`` (PR 69, what the layer
runs) takes ``mixed [B, T, 2 key_dim + value_dim]`` as the convolution wrote
it: the three windows ``q~ | k~ | v`` are blocks of the one array whose lane
index starts at the window's, a program's four value heads read their two
KEY heads' ``[rows, 2 d_k]`` once (``rep`` value heads a key head: half the
q and k bytes at 2), and the kernel forms ``q = round(l2norm(q~) d_k^-1/2)``,
``k = round(l2norm(k~))`` on the ``[64, 128]`` chunk in VMEM (``_operands``:
f32 sum of squares, 1e-6 under the root, rounded to the compute type where
the layer's ``jax.numpy`` prologue rounds, so every product takes the passes
it took and the values are that prologue's but for the order of a 128-lane
sum).  No normalised or repeated ``[B, T, H d_k]`` reaches HBM, forward,
recomputed or as a cotangent.  For each chunk the kernel forms in VMEM the
running sum
``G``, the masked decays, ``K K^T``, ``L``, ``T = (I + L)^-1``, ``V' = T (beta
V)``, ``W = T (beta exp(G) K)``, ``u = V' - W S``, ``o = (q exp(G)) S + P u``
and the next state; it writes ``o`` once, the state each chunk starts from
and the chunk's ``T``.

``hetu_gdn_bwd``: the same grid with the chunks in reverse and ``dS [d_k,
d_v]`` f32 in VMEM scratch.  A program reads its chunk's ``T`` as the forward
kernel wrote it, rebuilds ``W, V', u, P`` from it, q, k, v, g, beta and the
kept chunk-start state, and writes dq, dk, dv, dg and dbeta once; in place
it sums a key head's dq and dk over its value heads in f32, takes them
through the scale and the norm by hand (``common.unit_bwd``: ``dt~ = r (dt^ - t^
sum(dt^ t^))``, one lane sum a row) and writes ``dq~, dk~ [B, T, key_dim]``:
half the cotangent bytes, and ``d mixed`` is one concatenation with ``dv``.
One kernel: the reverse walk and the gradients inside a chunk share every
rebuilt matrix.  Nothing is solved here: the triangle ``L``, its transpose
and the substitution are the forward kernel's alone
(``hetu_delta_inverse_total{rule="gdn", source}`` counts a forward call
traced as ``solved`` and a backward one as ``kept``).

What the backward keeps: the chunk-start states (``d_k x d_v`` f32 a chunk
and head, 268 MB for a layer of the Qwen3-Next cell) and the chunks'
inverses ``T [B, H, T / (n C), n, 64, 64]`` f32, exactly the value
``unit_lower_inverse`` returned (16 KiB a chunk and head, 64 MiB a layer of
that cell and 128 MiB in HBM, whose tiles are 128 lanes wide); no ``W``,
``V'`` or ``u`` reaches HBM.  Since PR 69 ``_rule_fwd`` names both and the
output (``dispatch.KEPT["gdn"]``: 64 MB of bf16 output a layer of that cell,
466 MB with them), so a recomputed mixer keeps what its first forward call
wrote, its backward pass runs no second ``hetu_gdn_fwd`` (three a step where
six ran) and all three layers' residuals are alive from a layer's forward
pass to its backward pass (``peak_hbm_share`` 69 -> 77%).  A step used to solve every chunk's
system three times (forward, recomputed forward, and again inside the
backward kernel, 37% of that kernel's bundles: 6.08 -> 3.88 ms a call on a
v5e; PERF.md, PR 66).  The tiles stay half empty: a program's heads side by
side in whole ``[64, 128]`` tiles halve the kept bytes but the odd heads then
sit at lane offset 64 (the backward body 9% more bundles with a load there,
1.6% with an aligned load and a roll; in the Qwen3-Next step 0.05 ms a step
more for each kernel and the same peak, since that cell's peak lies
elsewhere; KDA's forward body 1.2% more bundles, over the 1% a forward
kernel was allowed), and the padding costs 0.78 points of HBM where a
mixer's backward pass is the peak (Ling-3.0: 86.67 -> 87.45%).  Writing the
states and ``T`` costs the forward kernel nothing that a run can see (2.437
ms a layer with the states, 2.438 without: PERF.md, PR 32; 20.88 -> 20.91 ms
a step in six calls with ``T``, PR 66), so there is one forward kernel and a
call that wants no gradient drops them; walking the chunks of a program
again in the backward pass would add two state products a chunk.

A chunk is one chain of dependent steps (the solve, ``T rhs``, ``W S``, the
state), and Mosaic's scheduler stays close to program order.  So a program
holds several heads and runs their chains in step (``together``: the chunk
functions are generators that yield between dependent stages): this alone
took a layer's forward pass from 6.4 to 3.3 ms on a v5e.  A stage is a
jitted function of values, so the heads, the two kernels and every call
share one trace of it (tracing is Python time that every run's set-up pays).

The unit triangular system is solved by substitution, in blocks: inside the
four diagonal ``[16, 16]`` blocks row by row (fifteen steps on the vector
unit, all four blocks a step, exact: row ``i`` of the inverse is ``e_i -
sum_c L[i, c] row_c``), then two exact merges ``T <- T - T B T`` with ``B``
the off-diagonal blocks of the next size.  No power of ``L`` is formed.

Precision.  The state, the decays, ``T`` and every operand of a product
with them stay f32; ``K K^T``, ``Q K^T``, ``P u`` and their gradient products
take operands in the compute type and add in f32, as the ``jax.numpy`` form.
An f32 product runs as bf16 passes over the operands' three bf16 parts
(``dot32``): all 24 bits of an f32 mantissa, six passes, what XLA's
``HIGHEST`` gives the ``jax.numpy`` form, whatever the inputs' type; an
operand that is bf16 already (q, k, v, do) is one part, exactly, and its
product with an f32 operand three passes.  (Two parts, 16 bits in three
passes, were 2.7 ms a layer faster and moved the last state from 1.7e-6 to
8.6e-6 of the ``jax.numpy`` form's: not taken, PERF.md, PR 32.  Mosaic's own
fp32 contraction takes six passes on every operand and was 1.3 times
slower.)
"""

from __future__ import annotations

import functools
import math
import operator

import jax
import jax.numpy as jnp

from . import common, dispatch
from .common import (C, NN, NT, TN, VMEM_LIMIT, WALK, chunk_rows, dot, dot32,
                     head_lanes, iotas, params, pick, put, to_col, to_row,
                     together, unit, unit_bwd, unit_lower_inverse, walk)

#: heads a program runs in step (at two parts an f32 operand: 1: 7.2 + 9.2
#: ms a layer forward + backward on a v5e, 2: 4.2 + 5.9, 4: 3.3 + 4.7, 8:
#: 3.4 + 4.0 and four times the time to compile; at three parts 4: 4.4 + 6.9)
HEADS = 4

_F32 = jnp.float32
_BF16 = jnp.bfloat16


# A chunk's chain is cut into stages, each a jitted function of values: a
# program's heads, both kernels and every call (the layers' and the probe's)
# then share one trace of a stage.  Tracing the chain once a head and kernel
# put 2.9 s on every run's set-up (the chip's host is slow at Python); the
# kernels' traces now take 2.3 s together and the set-up is the parent's
# (PERF.md, PR 32).  Mosaic lowers the stages inline, so the kernel is the
# one the plain functions would give.  The generators below yield between
# stages (``together``).



@functools.partial(jax.jit, static_argnames="solve")
def _chunk_open(k, g_row, beta_row, *, solve):
    """The running sum ``G``, the masked decays, ``K_beta`` and ``K_beta
    K^T``; ``solve``: also the triangle ``L = strict(K_beta K^T D)`` with its
    transpose, which the inverse alone reads."""
    row, col = iotas()
    eye, lower = row == col, row >= col
    G = jnp.sum(jnp.where(lower, jnp.broadcast_to(g_row, (C, C)), 0.0),
                axis=1, keepdims=True)                       # [C, 1]
    beta = to_col(beta_row, eye)
    G_row = to_row(G, eye)
    diff = G - G_row                                         # G_t - G_s
    # masked before the exp: above the diagonal the difference is positive
    D = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    kb = (k.astype(_F32) * beta).astype(k.dtype)
    KK = dot(kb, k, NT)
    c = dict(G=G, G_row=G_row, beta=beta, D=D, kb=kb, KK=KK)
    if solve:
        DT = jnp.exp(jnp.where(row <= col, -diff, -jnp.inf))
        c.update(L=jnp.where(row > col, KK * D, 0.0),
                 LT=jnp.where(row < col, dot(k, kb, NT) * DT, 0.0))
    return c


@jax.jit
def _chunk_rhs(T, k, v, beta_row, G_row):
    """``[V' | W] = T [beta v | beta exp(G) k]``: the scales go to T's
    columns, so that v and k enter as they are (one part where they are
    bf16)."""
    Tb = T * beta_row
    return dot32(Tb, v, NN), dot32(Tb * jnp.exp(G_row), k, NN)


@jax.jit
def _chunk_close(q, k, G, D):
    row, col = iotas()
    G_end = G[C - 1:C]                                       # [1, 1]
    QK = dot(q, k, NT)
    return dict(QK=QK, P=jnp.where(row >= col, QK * D, 0.0).astype(k.dtype),
                eG=jnp.exp(G), e_end=jnp.exp(G_end - G), a=jnp.exp(G_end))


def _chunk(q, k, v, g_row, beta_row, T=None):
    """What a chunk's forward and backward passes share and the state does
    not enter; ``T``: the chunk's kept inverse, or None to solve for it.  A
    generator, as the three functions around it: it yields between stages
    that depend on each other and returns its value at the end
    (``together``)."""
    c = _chunk_open(k, g_row, beta_row, solve=T is None)
    yield
    if T is None:
        T = yield from unit_lower_inverse(c["L"], c["LT"])
    Vp, W = _chunk_rhs(T, k, v, beta_row, c["G_row"])
    yield
    return dict({n: c[n] for n in ("beta", "D", "kb", "KK")}, T=T, Vp=Vp,
                W=W, **_chunk_close(q, k, c["G"], c["D"]))


@jax.jit
def _u(Vp, W, S):
    return Vp - dot32(W, S, NN)


@jax.jit
def _fwd_close(q, k, S, u, c):
    o = dot32(q, S, NN) * c["eG"] + dot(c["P"], u.astype(k.dtype), NN)
    return o, S * c["a"] + dot32(k, u * c["e_end"], TN)


def _chunk_fwd(q, k, v, g_row, beta_row, S):
    """One chunk from the state ``S`` it starts at: ``(o f32, next state,
    the chunk's inverse T)``."""
    c = yield from _chunk(q, k, v, g_row, beta_row)
    u = _u(c["Vp"], c["W"], S)
    yield
    return _fwd_close(q, k, S, u, c) + (c["T"],)


# o = (q exp(G)) S + P u;  S_next = a S + (k e_end)^T u;  u = V' - W S
@jax.jit
def _bwd_u(k, do, dS, u, c):
    dP = dot(do, u.astype(k.dtype), NT)
    du = dot(c["P"], do, TN) + dot32(k, dS, NN) * c["e_end"]
    return dP, du, dot32(u, dS, NT)                          # .., dk_end


@jax.jit
def _bwd_state(q, do, S, dS, du, c):
    da = jnp.sum(jnp.sum(dS * S, axis=1, keepdims=True), axis=0,
                 keepdims=True)
    dQe = dot32(do, S, NT)
    dW = -dot32(du, S, NT)
    dS0 = (dS * c["a"] + dot32(q, do.astype(_F32) * c["eG"], TN)
           - dot32(c["W"], du, TN))
    return da, dQe, dW, dS0


@jax.jit
def _bwd_rhs(T, du, dW):
    # [V' | W] = T [beta v | beta exp(G) k]
    return dot32(T, du, TN), dot32(T, dW, TN)


@jax.jit
def _bwd_solve(dRv, dRw, Vp, W):
    row, col = iotas()
    return jnp.where(row > col, -(dot32(dRv, Vp, NT)
                                  + dot32(dRw, W, NT)), 0.0)


@jax.jit
def _bwd_close(q, k, v, dP, dk_end, da, dQe, dRv, dRw, dL, c):
    ct, beta, eG, D, e_end = k.dtype, c["beta"], c["eG"], c["D"], c["e_end"]
    row, col = iotas()
    eye = row == col
    qf, kf, vf = (t.astype(_F32) for t in (q, k, v))
    dv = dRv * beta
    kRw = jnp.sum(dRw * kf, axis=1, keepdims=True)
    dk = dRw * (beta * eG)
    # L = strict(K_beta K^T D), P = lower(Q K^T D)
    dPD = jnp.where(row >= col, dP, 0.0)
    dKK, dQK = (dL * D).astype(ct), (dPD * D).astype(ct)
    dkb = dot(dKK, k, NN)
    dq = dot(dQK, k, NN) + dQe * eG
    dk = (dk + dot(dKK, c["kb"], TN) + dot(dQK, q, TN) + dkb * beta
          + dk_end * e_end)
    dbeta = (jnp.sum(dRv * vf + dkb * kf, axis=1, keepdims=True)
             + kRw * eG)
    deG = kRw * beta + jnp.sum(dQe * qf, axis=1, keepdims=True)
    dE = jnp.sum(dk_end * kf, axis=1, keepdims=True) * e_end
    # D = exp(G_t - G_s): the gradient of the difference, then of G
    dDiff = (dL * c["KK"] + dPD * c["QK"]) * D
    dG = (deG * eG - dE + jnp.sum(dDiff, axis=1, keepdims=True)
          - to_col(jnp.sum(dDiff, axis=0, keepdims=True), eye))
    dG_end = jnp.sum(dE, axis=0, keepdims=True) + da * c["a"]
    dG = dG + jnp.where(row[:, :1] == C - 1, dG_end, 0.0)
    # G is g's running sum: dg_t = sum of dG from t on
    dg = jnp.sum(jnp.where(row >= col, jnp.broadcast_to(dG, (C, C)), 0.0),
                 axis=0, keepdims=True)
    return dq, dk, dv, dg, to_row(dbeta, eye)


def _chunk_bwd(q, k, v, g_row, beta_row, S, T, do, dS):
    """One chunk's gradients from ``do`` and the gradient ``dS`` of the state
    it ends at, with the chunk-start state ``S`` and the inverse ``T`` its
    forward pass kept: ``(dq, dk, dv f32 [C, d]; dg, dbeta [1, C]; the
    gradient of the state it starts at)``."""
    c = yield from _chunk(q, k, v, g_row, beta_row, T)
    Vp, W = c["Vp"], c["W"]
    u = _u(Vp, W, S)
    yield
    dP, du, dk_end = _bwd_u(k, do, dS, u, c)
    yield
    da, dQe, dW, dS0 = _bwd_state(q, do, S, dS, du, c)
    yield
    dRv, dRw = _bwd_rhs(T, du, dW)
    yield
    dL = _bwd_solve(dRv, dRw, Vp, W)
    yield
    return _bwd_close(q, k, v, dP, dk_end, da, dQe, dRv, dRw, dL, c) + (dS0,)


def _operands(q_ref, k_ref, rows, hb, dk, rep):
    """``(q, k)`` in the compute type of each of a program's value heads at a
    chunk's ``rows``.  ``rep`` None: a head's lanes of both blocks as they
    are.  Else the blocks hold the convolution's ``q~, k~`` a KEY head, read
    once for its ``rep`` value heads: ``q = round(l2norm(q~) dk^-1/2)``, ``k =
    round(l2norm(k~))`` formed here, rounded where the layer's ``jax.numpy``
    form rounds them; beside them, a key head, ``(q^, r_q, k^, r_k)`` f32 for
    the norms' backward pass."""
    if rep is None:
        return [(q_ref[rows, kl], k_ref[rows, kl])
                for kl, _ in head_lanes(hb, dk, dk)], None
    ct = q_ref.dtype
    norms = [unit(q_ref[rows, kl]) + unit(k_ref[rows, kl])
             for kl, _ in head_lanes(hb // rep, dk, dk)]
    return [((qn * dk ** -0.5).astype(ct), kn.astype(ct))
            for qn, _, kn, _ in norms for _ in range(rep)], norms


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, last_ref, s0_ref,
                t_ref, s_ref, *, nc, hb, dk, dv, rep):
    import jax.experimental.pallas as pl
    i = pl.program_id(2)
    lanes = head_lanes(hb, dk, dv)

    @pl.when(i == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def body(j):
        rows = chunk_rows(j, C)
        for h in range(hb):
            s0_ref[h, j] = s_ref[h]
        qk, _ = _operands(q_ref, k_ref, rows, hb, dk, rep)
        outs = together(
            _chunk_fwd(q, k, v_ref[rows, vl], pick(g_ref[h], j),
                       pick(b_ref[h], j), s_ref[h])
            for h, ((q, k), (_, vl)) in enumerate(zip(qk, lanes)))
        for h, (o, S, T) in enumerate(outs):
            o_ref[rows, lanes[h][1]] = o.astype(o_ref.dtype)
            s_ref[h] = S
            t_ref[h, j] = T
    walk(nc, body)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = s_ref[...]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s0_ref, t_ref, do_ref,
                dlast_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *,
                nc, hb, dk, dv, rep):
    import jax.experimental.pallas as pl
    lanes = head_lanes(hb, dk, dv)

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = dlast_ref[...]

    def body(n):
        j = nc - 1 - n
        rows = chunk_rows(j, C)
        qk, norms = _operands(q_ref, k_ref, rows, hb, dk, rep)
        outs = together(
            _chunk_bwd(q, k, v_ref[rows, vl], pick(g_ref[h], j),
                       pick(b_ref[h], j), s0_ref[h, j], t_ref[h, j],
                       do_ref[rows, vl], ds_ref[h])
            for h, ((q, k), (_, vl)) in enumerate(zip(qk, lanes)))
        for h, (dq, dk_, dv_, dg, dbeta, dS) in enumerate(outs):
            kl, vl = lanes[h]
            if rep is None:
                dq_ref[rows, kl] = dq.astype(dq_ref.dtype)
                dk_ref[rows, kl] = dk_.astype(dk_ref.dtype)
            dv_ref[rows, vl] = dv_.astype(dv_ref.dtype)
            put(dg_ref.at[h], j, dg)
            put(db_ref.at[h], j, dbeta)
            ds_ref[h] = dS
        # a key head's dq, dk summed over its value heads in f32, then
        # through the scale and the norm: dq~, dk~ a key head
        for n_, (qn, rq, kn, rk) in enumerate(norms or ()):
            kl = lanes[n_][0]
            mine = outs[n_ * rep:(n_ + 1) * rep]
            dq, dk_ = (functools.reduce(operator.add, (o[n] for o in mine))
                       for n in range(2))
            dq_ref[rows, kl] = unit_bwd(dq * dk ** -0.5, qn,
                                        rq).astype(dq_ref.dtype)
            dk_ref[rows, kl] = unit_bwd(dk_, kn, rk).astype(dk_ref.dtype)
    walk(nc, body)


def _plan(g, widths, reverse):
    """Grid, the kernels' static sizes and the block specs by name: ``q``
    (and ``dq``, ``dk`` as the backward kernel writes them), ``k``, ``v`` as
    the kernels read them, ``o`` (and ``do``, ``dv``), ``gate`` (g and beta),
    the kept states, the kept inverses and a state; ``reverse``: the blocks
    of chunks from the last to the first.  ``widths = (dk, dv, rep)``.
    ``rep`` None: q, k ``[B, T, H dk]`` and v ``[B, T, H dv]`` are arrays of
    their own, a value head's lanes each.  Else all three are windows of
    ``mixed [B, T, 2 key_dim + value_dim]``, each a block whose lane index
    starts at the window's: a program's ``hb`` value heads read their ``hb /
    rep`` key heads' ``q~`` and ``k~`` once, and ``dq~``, ``dk~`` are ``[B,
    T, key_dim]``, blocked as ``q~`` is in its window."""
    B, H, groups, nc, _ = g.shape
    dk, dv, rep = widths
    hb = math.gcd(H, HEADS)
    keys = hb * dk // (rep or 1)        # lanes of a program's q and k blocks
    seq, kept, state = common.blocks(nc, C, groups, reverse)
    # the windows' first blocks: k~ behind the H / hb blocks of q~, v behind
    # both (``in_place_unsupported`` has refused a v that starts inside one)
    k_at, v_at = (0, 0) if rep is None else (
        H // hb, 2 * (H // hb) * keys // (hb * dv))
    return ((B, H // hb, groups), dict(nc=nc, hb=hb, dk=dk, dv=dv, rep=rep),
            dict(q=seq(keys), k=seq(keys, k_at), v=seq(hb * dv, v_at),
                 o=seq(hb * dv), gate=kept(hb, C), kept=kept(hb, dk, dv),
                 inverse=kept(hb, C, C), state=state(hb, dk, dv)))


def _read(ops, widths):
    """``(q, k, v, g, beta)`` as the kernels are handed them and ``widths``
    whole: ``ops`` is those five, or with ``widths`` ``(mixed, g, beta)``,
    ``mixed`` then read three times."""
    if widths is None:
        q, _, v, g, _ = ops
        H = g.shape[1]
        return ops, (q.shape[2] // H, v.shape[2] // H, None)
    mixed, g, beta = ops
    return (mixed, mixed, mixed, g, beta), widths


@functools.partial(jax.jit, static_argnames=("widths", "interpret"))
def _fwd_call(*ops, interpret, widths=None):
    """``q, k [B, T, H dk]``, ``v [B, T, H dv]``, ``g, beta [B, H, T / (n C),
    n, C]`` f32 (``n`` chunks a program), or with ``widths = (dk, dv, rep)``
    ``mixed [B, T, 2 key_dim + value_dim]``, ``g, beta``: ``(o [B, T, H dv],
    last state [B, H, dk, dv], chunk-start states [B, H, T / (n C), n, dk,
    dv], the chunks' inverses [B, H, T / (n C), n, C, C] f32)``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    ops, widths = _read(ops, widths)
    g = ops[3]
    B, H, groups, nc, _ = g.shape
    grid, dims, at = _plan(g, widths, False)
    dk, dv, hb = dims["dk"], dims["dv"], dims["hb"]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **dims),
        name="hetu_gdn_fwd", grid=grid,
        in_specs=[at["q"], at["k"], at["v"], at["gate"], at["gate"]],
        out_specs=[at["o"], at["state"], at["kept"], at["inverse"]],
        out_shape=[jax.ShapeDtypeStruct(ops[2].shape[:2] + (H * dv,),
                                        ops[2].dtype),
                   jax.ShapeDtypeStruct((B, H, dk, dv), _F32),
                   jax.ShapeDtypeStruct((B, H, groups, nc, dk, dv), _F32),
                   jax.ShapeDtypeStruct((B, H, groups, nc, C, C), _F32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), _F32)],
        compiler_params=params(interpret, WALK, VMEM_LIMIT),
        interpret=interpret,
    )(*ops)


@functools.partial(jax.jit, static_argnames=("widths", "interpret"))
def _bwd_call(*ops, interpret, widths=None):
    """``_fwd_call``'s operands, the kept states and inverses, ``do`` and the
    last state's cotangent: ``dq, dk, dv, dg, dbeta``; with ``widths`` the
    first two are ``dq~, dk~ [B, T, key_dim]``, a key head's summed over its
    value heads and taken through its norm."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    *ops, states, inverses, do, dlast = ops
    ops, widths = _read(ops, widths)
    q, _, _, g, _ = ops
    grid, dims, at = _plan(g, widths, True)
    dk, dv, rep = widths
    keys = jax.ShapeDtypeStruct(
        q.shape[:2] + (g.shape[1] * dk // (rep or 1),), q.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, **dims),
        name="hetu_gdn_bwd", grid=grid,
        in_specs=[at["q"], at["k"], at["v"], at["gate"], at["gate"],
                  at["kept"], at["inverse"], at["o"], at["state"]],
        out_specs=[at["q"], at["q"], at["o"], at["gate"], at["gate"]],
        out_shape=[keys, keys, jax.ShapeDtypeStruct(do.shape, q.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(g.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((dims["hb"], dk, dv), _F32)],
        compiler_params=params(interpret, WALK, VMEM_LIMIT),
        interpret=interpret,
    )(*ops, states, inverses, do, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rule(widths, *ops):
    # traced wherever the call is: a program that differentiates it lowers
    # the forward rule's trace and drops this one, and where a group keeps
    # that rule's names (``dispatch.keeps``) no second kernel stands for it
    return _forward(widths, ops, count=not dispatch.keeps())[:2]


def _forward(widths, ops, count=True):
    if count:
        dispatch.count_inverse("gdn", "solved")
    return _fwd_call(*ops, widths=widths, interpret=dispatch.interpret())


def _rule_fwd(widths, *ops):
    o, last, states, inverses = _forward(widths, ops)
    # named HERE, on the values the backward rule and the mixer's norm read: a
    # recomputed group keeps them (``dispatch.KEPT``) and its backward pass
    # runs no second forward kernel
    o, states, inverses = dispatch.named("gdn", o, states, inverses)
    return (o, last), ops + (states, inverses)


def _rule_bwd(widths, res, grads):
    dispatch.count_inverse("gdn", "kept")
    dq, dk, dv, dg, dbeta = _bwd_call(*res, *grads, widths=widths,
                                      interpret=dispatch.interpret())
    if widths is None:
        return dq, dk, dv, dg, dbeta
    return jnp.concatenate([dq, dk, dv], -1), dg, dbeta


_rule.defvjp(_rule_fwd, _rule_bwd)


def unsupported(q, k, v, chunk):
    """Why the kernels do not take ``chunk_gated_delta_rule``'s operands, or
    None when they do."""
    if chunk != C:
        return f"chunk!={C}"
    if q.shape[-1] % 128 or v.shape[-1] % 128:
        return "head_dim_not_128_aligned"
    if not q.dtype == k.dtype == v.dtype:
        return "dtype:mixed"
    if jnp.dtype(v.dtype) not in (jnp.dtype(_BF16), jnp.dtype(_F32)):
        return f"dtype:{jnp.dtype(v.dtype).name}"
    return None


def in_place_unsupported(key_heads, dk, dv, rep):
    """Why ``gated_delta_rule_in_place`` does not read ``mixed`` where the
    kernels take its heads (``unsupported``), or None when it does: a
    program's value heads must be whole key heads', and the window of v must
    start at a block of a program's v."""
    hb = math.gcd(key_heads * rep, HEADS)
    if hb % rep:
        return "key_head_split_across_programs"
    if 2 * key_heads * dk % (hb * dv):
        return "value_window_not_block_aligned"
    return None


def _kept(o, dk, dv):
    """Tell the group that recomputes this call (``dispatch.kept``) what
    ``_rule_fwd`` named of it: ``o [B, T', H dv]`` and, f32 a chunk and head,
    the start state ``[dk, dv]`` and the inverse ``[C, C]``."""
    chunk_heads = o.size // (C * dv)
    dispatch.kept("gdn", o.size * o.dtype.itemsize
                  + 4 * chunk_heads * (dk * dv + C * C))


def gated_delta_rule(q, k, v, g, beta):
    """``chunk_gated_delta_rule`` at chunk 64 through the kernel pair: ``q, k
    [B, T, H, d_k]``, ``v [B, T, H, d_v]``, ``g, beta [B, T, H]`` -> ``(o [B,
    T, H, d_v]`` in ``v``'s type, the last state ``[B, H, d_k, d_v]`` f32)``.
    Any ``T`` (``common.cut``: beta and g are 0 at the padding)."""
    B, T, H, dk = q.shape
    cut = common.cut(T)
    o, last = _rule(None, *(common.rows(x, cut[2]) for x in (q, k, v)),
                    common.by_chunk(g, *cut), common.by_chunk(beta, *cut))
    _kept(o, dk, v.shape[-1])
    return o[:, :T].reshape(B, T, H, v.shape[-1]), last


def gated_delta_rule_in_place(mixed, g, beta, *, dk, dv, rep):
    """The rule from the convolution's output: ``mixed [B, T, 2 key_dim +
    value_dim]`` (``q~ | k~ | v``, a key head's ``rep`` value heads side by
    side), ``g, beta [B, T, H]`` -> ``o [B, T, H d_v]`` in ``mixed``'s type,
    what ``gated_delta_rule`` gives on ``q = round(l2norm(q~) d_k^-1/2)``,
    ``k = round(l2norm(k~))`` each repeated for its value heads, neither of
    which reaches HBM; the cotangent of ``mixed`` comes back whole."""
    T = mixed.shape[1]
    cut = common.cut(T)
    o, _ = _rule((dk, dv, rep), common.rows(mixed, cut[2]),
                 common.by_chunk(g, *cut), common.by_chunk(beta, *cut))
    _kept(o, dk, dv)
    return o[:, :T]
