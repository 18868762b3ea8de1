"""The chunked state-space scan as two Pallas kernels (``ops/ssd.py`` has the
mathematics and the ``jax.numpy`` form these are held to).

``hetu_ssd_fwd``: grid (batch, group, block of chunks), the last axis
sequential.  A program holds the ``R = H / G`` heads of one group where they
are at most ``HEADS`` = 8 (Nemotron-H).  A wider group (Granite 4.0-H: all 64
heads read ONE ``B`` and ``C``; held whole, its blocks would be 68 MiB of the
64 MiB limit) is cut into ``wide`` blocks of ``R`` heads, the most up to
eight that divide it, and the grid's second axis runs over blocks of heads:
``wide`` neighbours read the same rows of ``B`` and ``C`` in place (their
block index is the group's), and in the backward pass each writes its own
part of ``dB`` and ``dC``, which XLA adds up over the group's blocks in f32.
(Broadcasting ``B`` and ``C`` to ``wide`` sub-groups outside, so that the
kernels see eight heads a group, is the same but for 2 x 16 MiB written and
read again at the Granite cell's shape: 3.31 against 3.25 ms a mixer forward
and backward, v5e, PERF.md, PR 37.)  Below, "group" is what a program holds:
its states stay in VMEM scratch from the first chunk to the last, transposed and
side by side, ``S^T [N, R P]`` f32, so that what all heads share is one
product (``C S^T`` for the group's ``R P`` lanes at once).  It walks
``CHUNKS`` chunks of 128 positions: it reads their ``x`` rows in place from
the ``[b, T, H P]`` view (a group's heads are ``R P`` contiguous lanes), ``B``
and ``C`` rows from ``[b, T, G N]`` once for the group's heads, and ``dt`` and
``a = dt A`` f32 with a chunk's positions along the lanes (``[R, 128]``: one
register for eight heads).  A chunk: the running sum of ``a`` by seven rolls
and adds on that register, its transpose for the decays' rows, ``C B^T``
once for the group; a head: the masked differences and their ``exp``, ``scores
= (C B^T * L * dt)`` in the compute type, ``y = scores x + (C S^T) exp(a_0 +
.. + a_t)``, and ``S^T <- exp(sum a) S^T + (B^T * dt * to_end) x``.  It writes
``y`` once and the state each chunk starts from.

``dt`` enters on the scores' and on ``B``'s side, where a chunk's positions
lie along the lanes and a scale a position is a row that broadcasts down the
sublanes, so that ``x`` goes to the matrix unit as it is (Mamba-2's published
kernels scale the same operands).  Each of the four products still takes
operands in the compute type and adds in f32; the one rounding a product
(its scaled operand's) falls on ``C B^T L dt`` and ``B dt to_end`` where the
``jax.numpy`` form rounds ``C B^T L``, ``dt x`` and ``dt x to_end``.

``hetu_ssd_bwd``: the same grid with the chunks in reverse and the gradient
``dS^T [N, R P]`` f32 of the state a chunk ends at in VMEM scratch.  A program
rebuilds its chunk's decays and scores from ``x, dt, a, B, C`` and the kept
chunk-start state and writes ``dx``, the gradients of ``dt`` (where it scales
a product's operand) and of ``a``, and ``dB`` and ``dC`` summed over the
group's heads in VMEM, each once.  ``dA`` and the rest of ``ddt`` follow
from ``a = dt A`` in XLA.  The gradient of ``a`` is the reverse running sum,
inside the chunk, of the gradient of its running sum, which is taken term by
term from the decays it enters (the ``[128, 128]`` matrix as a row and as a
column, the decays from the chunk's start and to its end), and beyond the
chunk's last position what the recurrence itself gives, ``<dS, S>`` of the
state the chunk ends at, carried from chunk to chunk.  (The recurrence also
gives ``da_t - da_(t+1) = dy_t . y_t - dt_t ddt_t``, which needs no decay
matrix at all; in bf16 its two terms are rounded apart and what is left
after they cancel was 3% off in ``dA`` where the ``jax.numpy`` form is
0.08%.)  Every sum over a chunk's positions runs down the sublanes, after a
transposition where it has to: a sum along the lanes costs a register's
rotations, and took a third of the kernel's time.

What the backward keeps: the chunk-start states (``N x P`` f32 a chunk and
head, 134 MB for a mixer of the Nemotron-H cell, alive only while that
mixer's backward pass runs since the mixer is recomputed) and the last
state, and nothing else: nothing ``[.., 128, 128]`` reaches HBM in either
pass.  One forward kernel; a call that wants no gradient drops the states.

Precision.  ``dt``, ``a``, the running sum, every decay, the state, the kept
states and ``dS`` are f32.  The decays come from differences of a chunk's own
running sum, masked before the ``exp``: the sum is bounded by ``128 dt |A|``,
whose f32 spacing is far under the bf16 the scores are cast to.  Products
take operands in the compute type and add in f32; with f32 operands they run
at the highest precision.

A head's chain is short (two products forward, seven backward) and the heads
of a program do not depend on each other: written one after another they ran
as fast as in step (1.47 ms a mixer forward against 1.50-1.52, v5e, PERF.md,
PR 34), so there is no ``_together`` here.  A head's chain is a jitted
function of values, so that the heads, the layers and the probe share one
trace of it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ... import telemetry
from . import dispatch
from .common import (NN, NT, TN, VMEM_LIMIT, WALK, chunk_rows, params,
                     walk)

#: positions a chunk (``ops.ssd.CHUNK``; the kernels are written for it)
L = 128
#: chunks a program walks (4 and 16 measured the same)
CHUNKS = 8
#: heads a program holds at most: ``dt`` and ``a`` of eight heads are one f32
#: register ``[8, 128]``, and eight heads of 64 are what the blocks below were
#: sized and measured at.  A group of more heads (Granite 4.0-H: all 64 heads
#: read ONE ``B`` and ``C``) is walked as blocks of heads, a program each.
HEADS = 8
# (scoped VMEM: the backward program's x, dy, dx blocks and eight kept states,
# double-buffered, are about 12 MiB of ``common.VMEM_LIMIT``'s 64)

_F32 = jnp.float32


def _dot(a, b, dims):
    """``a . b`` contracting ``dims``, f32 sums; f32 operands at the highest
    precision, operands in a lower type as they are.  (In interpret mode the
    latter are widened first, which changes no product: XLA's CPU runtime
    has no bf16 x bf16 -> f32 product where it folds a transposition into an
    operand.)"""
    if dispatch.interpret():
        a, b = a.astype(_F32), b.astype(_F32)
    full = jax.lax.Precision.HIGHEST if a.dtype == _F32 else None
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=full,
                               preferred_element_type=_F32)


def _roll(x, shift):
    """``jnp.roll`` along the lanes."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.roll(x, shift, 1)


@jax.jit
def _running_sum(a):
    """``a_0 + .. + a_t`` along the lanes of ``[R, L]``, by doubling."""
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    s = 1
    while s < L:
        a = a + jnp.where(lane >= s, _roll(a, s), 0.0)
        s *= 2
    return a


@jax.jit
def _sum_from(q):
    """``q_t + .. + q_(L-1)`` along the lanes of ``[R, L]``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1)
    s = 1
    while s < L:
        q = q + jnp.where(lane < L - s, _roll(q, L - s), 0.0)
        s *= 2
    return q


@jax.jit
def _decays(cs_c, cs_r):
    """``exp(a_(s+1) + .. + a_t)`` for ``s <= t`` and 0 above the diagonal,
    ``[L, L]`` with ``t`` down the rows, from the running sum as a column
    and as a row; masked before the ``exp``."""
    row = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    return jnp.exp(jnp.where(row >= col, cs_c - cs_r, -jnp.inf))


@jax.jit
def _open(Bm, Cm, dt_r, a_r):
    """What a chunk's heads share in both passes: the running sum of ``a`` as
    rows ``[R, L]`` and as columns ``[L, R]``, the decay to the chunk's end
    (``te``), the whole sum (``last [R, 1]``), ``C B^T`` and ``B^T`` in
    f32."""
    cs_r = _running_sum(a_r)
    last = cs_r[:, L - 1:]
    return dict(dt_r=dt_r, cs_r=cs_r, cs_c=cs_r.T, last=last,
                te_r=jnp.exp(last - cs_r), CB=_dot(Cm, Bm, NT),
                BT=Bm.astype(_F32).T)


def _of_head(c, h, names):
    """Head ``h``'s row ``[1, L]`` (or ``[1, 1]``) of the ``_r`` values and
    column ``[L, 1]`` of the ``_c`` values among ``names``."""
    return [c[n][:, h:h + 1] if n.endswith("_c") else c[n][h:h + 1]
            for n in names]


def _through(last, x):
    """The decay through a chunk, ``exp(a_0 + .. + a_(L-1))``, as ``[1, P]``
    from ``[1, 1]``: along the lanes before the ``exp`` and down the sublanes
    where it is used (Mosaic has no broadcast over both at once)."""
    return jnp.exp(jnp.broadcast_to(last, (1, x.shape[1])))


# y = (CB L dt) x + (C S0^T) fs;  S1^T = th S0^T + (B^T dt te) x
@jax.jit
def _head_fwd(x, CB, CS, ST, BT, cs_c, cs_r, dt_r, te_r, fs_c, last):
    """One head of a chunk from the state ``S^T [N, P]`` it starts at: ``(y
    f32 [L, P], next state)``."""
    ct = x.dtype
    sc = (CB * _decays(cs_c, cs_r) * dt_r).astype(ct)
    y = _dot(sc, x, NN) + CS * fs_c
    return y, ST * _through(last, x) + _dot(
        (BT * (dt_r * te_r)).astype(ct), x, NN)


@jax.jit
def _head_bwd(x, dy, CB, S0T, dST, BT, CT, cs_c, cs_r, dt_r, te_r, fs_r,
              last):
    """One head of a chunk from the gradient ``dS^T [N, P]`` of the state it
    ends at: ``dx [L, P]``; this head's parts of the gradients of ``C B^T [L,
    L]`` and of ``B^T``, ``C^T [N, L]``; ``ddt [1, L]``; the gradient of the
    running sum where it is a row of the decays or the decay from the chunk's
    start (``dcs [1, L]``; where it is a column of the decays or the decay to
    the chunk's end it is ``-dt ddt``); the gradient of the state the chunk
    starts at.  Sums over a chunk's positions are taken down the sublanes (a
    sum along the lanes costs a register's rotations; the transposition
    before it is free)."""
    ct = x.dtype
    Lm = _decays(cs_c, cs_r)
    sc = (CB * Lm * dt_r).astype(ct)
    U = _dot(dy, x, NT) * Lm                                     # [L, L]
    W = U * CB
    Z = _dot(S0T.astype(ct), dy, NT)                             # [N, L]
    dcs = (jnp.sum((W * dt_r).T, axis=0, keepdims=True)
           + jnp.sum(CT * Z, axis=0, keepdims=True) * fs_r)
    w_r = dt_r * te_r
    dSc = dST.astype(ct)
    dx = _dot(sc, dy, TN) + _dot((BT * w_r).astype(ct), dSc, TN)
    M = _dot(dSc, x, NT)                                         # [N, L]
    state = jnp.sum(BT * M, axis=0, keepdims=True) * te_r
    return (dx, U * dt_r, M * w_r, Z * fs_r,
            jnp.sum(W, axis=0, keepdims=True) + state, dcs,
            dST * _through(last, x) + _dot((CT * fs_r).astype(ct), dy, NN))


@functools.partial(jax.jit, static_argnames="heads")
def _state_dot(dST, ST, *, heads):
    """``<dS, S>`` a head of ``[N, R P]`` states, ``[R, L]`` with a head's
    value in every lane of its row: the sum over ``N`` down the sublanes, the
    sum over a head's lanes and the broadcast by one small product with
    ones, at f32 precision."""
    e = jnp.sum(dST * ST, axis=0, keepdims=True)             # [1, R P]
    rp = e.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (heads, rp), 1)
    lo = jax.lax.broadcasted_iota(jnp.int32, (heads, rp), 0) * (rp // heads)
    mine = (lane >= lo) & (lane < lo + rp // heads)
    return _dot(jnp.where(mine, e, 0.0), jnp.ones((rp, L), _F32), NN)


@jax.jit
def _bwd_close(Bm, Cm, BT, CT, dCB, dBT, dCT, dcs, ddt, end, dt_r):
    """The group's ``dB`` and ``dC`` ``[L, N]`` from the heads' sums, and the
    gradient of ``a`` from that of its running sum (``dcs`` less ``dt ddt``
    a position, and ``end`` at the chunk's last, all ``[R, L]``): ``a_t`` is
    in every sum from ``t`` on.  ``end`` is what the recurrence gives ``da``
    just after the chunk, ``<dS, S>`` of the state the chunk ends at."""
    ct = Bm.dtype
    dCBc = dCB.astype(ct)
    dBT = dBT + _dot(CT.astype(ct), dCBc, NN)
    dCT = dCT + _dot(BT.astype(ct), dCBc, NT)
    return dBT.T, dCT.T, _sum_from(dcs - dt_r * ddt) + end


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, y_ref, last_ref, s0_ref,
                s_ref, *, nc, heads, p):
    import jax.experimental.pallas as pl
    i = pl.program_id(2)
    lanes = [slice(h * p, (h + 1) * p) for h in range(heads)]

    @pl.when(i == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    def body(j):
        rows = chunk_rows(j, L)
        ST = s_ref[...]
        s0_ref[j] = ST
        Cm = c_ref[rows, :]
        c = _open(b_ref[rows, :], Cm, dt_ref[j], a_ref[j])
        c["fs_c"] = jnp.exp(c["cs_c"])     # the decay from the chunk's start
        CS = _dot(Cm, ST.astype(Cm.dtype), NN)        # [L, R P]: every head's
        for h, at in enumerate(lanes):
            y, S = _head_fwd(
                x_ref[rows, at], c["CB"], CS[:, at], ST[:, at], c["BT"],
                *_of_head(c, h, ("cs_c", "cs_r", "dt_r", "te_r", "fs_c",
                                 "last")))
            y_ref[rows, at] = y.astype(y_ref.dtype)
            s_ref[:, at] = S
    walk(nc, body)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = s_ref[...]


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, s0_ref, last_ref, dy_ref,
                dlast_ref, dx_ref, db_ref, dc_ref, ddt_ref, da_ref, ds_ref,
                end_ref, *, nc, heads, p):
    import jax.experimental.pallas as pl
    lanes = [slice(h * p, (h + 1) * p) for h in range(heads)]

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = dlast_ref[...]
        end_ref[...] = _state_dot(dlast_ref[...], last_ref[...], heads=heads)

    def body(n):
        j = nc - 1 - n
        rows = chunk_rows(j, L)
        Bm, Cm, S0T, dST = b_ref[rows, :], c_ref[rows, :], s0_ref[j], \
            ds_ref[...]
        c = _open(Bm, Cm, dt_ref[j], a_ref[j])
        c["fs_r"] = jnp.exp(c["cs_r"])
        CT = Cm.astype(_F32).T
        sub = jax.lax.broadcasted_iota(jnp.int32, (heads, L), 0)
        dcs = ddt = jnp.zeros((heads, L), _F32)
        dCB = dBT = dCT = 0.0
        for h, at in enumerate(lanes):
            dx, dCB_h, dBT_h, dCT_h, ddt_h, dcs_h, dS0T = _head_bwd(
                x_ref[rows, at], dy_ref[rows, at], c["CB"], S0T[:, at],
                dST[:, at], c["BT"], CT,
                *_of_head(c, h, ("cs_c", "cs_r", "dt_r", "te_r", "fs_r",
                                 "last")))
            dx_ref[rows, at] = dx.astype(dx_ref.dtype)
            ds_ref[:, at] = dS0T
            dCB, dBT, dCT = dCB + dCB_h, dBT + dBT_h, dCT + dCT_h
            dcs = jnp.where(sub == h, dcs_h, dcs)
            ddt = jnp.where(sub == h, ddt_h, ddt)
        dB, dC, da = _bwd_close(Bm, Cm, c["BT"], CT, dCB, dBT, dCT, dcs, ddt,
                                end_ref[...], c["dt_r"])
        # the state this chunk starts at is the one the chunk before ends at
        end_ref[...] = _state_dot(ds_ref[...], S0T, heads=heads)
        db_ref[rows, :] = dB.astype(db_ref.dtype)
        dc_ref[rows, :] = dC.astype(dc_ref.dtype)
        ddt_ref[j] = ddt
        da_ref[j] = da
    walk(nc, body)


def _plan(x, Bm, dt, reverse, wide):
    """Grid, the kernels' static sizes and the block specs of x / y, B / C
    (and dB / dC), dt / a, the kept states and a state; ``reverse``: the
    blocks of chunks from the last to the first.  The grid's second axis is
    over blocks of heads (``dt``'s second dimension), ``wide`` of them to a
    group: they read the one ``B`` and ``C`` of their group and each writes
    its own part of ``dB`` and ``dC``."""
    import jax.experimental.pallas as pl
    b, G, blocks, nc, R, _ = dt.shape
    rp, N = x.shape[2] // G, Bm.shape[2] * wide // G
    at = (lambda i: blocks - 1 - i) if reverse else (lambda i: i)
    seq = lambda d: pl.BlockSpec((None, nc * L, d),
                                 lambda b, g, i: (b, at(i), g))
    group = seq(N) if wide == 1 else pl.BlockSpec(
        (None, nc * L, N), lambda b, g, i: (b, at(i), g // wide))
    gate = pl.BlockSpec((None, None, None, nc, R, L),
                        lambda b, g, i: (b, g, at(i), 0, 0, 0))
    kept = pl.BlockSpec((None, None, None, nc, N, rp),
                        lambda b, g, i: (b, g, at(i), 0, 0, 0))
    state = pl.BlockSpec((None, None, N, rp), lambda b, g, i: (b, g, 0, 0))
    return ((b, G, blocks), dict(nc=nc, heads=R, p=rp // R),
            (seq(rp), group, seq(N), gate, kept, state))


@functools.partial(jax.jit, static_argnames=("interpret", "wide"))
def _fwd_call(x, dt, a, Bm, Cm, *, interpret, wide):
    """``x [b, T, H P]``, ``B, C [b, T, G N]``, ``dt, a [b, G', T / (n L), n,
    R, L]`` f32 (``n`` chunks a program; ``G' = wide G`` blocks of ``R`` heads,
    ``wide`` to a group): ``(y [b, T, H P], last state^T [b, G', N, R P],
    chunk-start states^T [b, G', T / (n L), n, N, R P])``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, G, blocks, nc = dt.shape[:4]
    grid, dims, (xy, bc, _, gate, kept, state) = _plan(x, Bm, dt, False, wide)
    rp, N = x.shape[2] // G, Bm.shape[2] * wide // G
    return pl.pallas_call(
        functools.partial(_fwd_kernel, **dims),
        name="hetu_ssd_fwd", grid=grid,
        in_specs=[xy, bc, bc, gate, gate], out_specs=[xy, state, kept],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, G, N, rp), _F32),
                   jax.ShapeDtypeStruct((b, G, blocks, nc, N, rp), _F32)],
        scratch_shapes=[pltpu.VMEM((N, rp), _F32)],
        compiler_params=params(interpret, WALK, VMEM_LIMIT),
        interpret=interpret,
    )(x, Bm, Cm, dt, a)


@functools.partial(jax.jit, static_argnames=("interpret", "wide"))
def _bwd_call(x, dt, a, Bm, Cm, states, last, dy, dlast, *, interpret,
              wide):
    """``dx``, ``dB`` and ``dC`` (``[b, T, G' N]``: a block of heads' own part,
    which ``_scan_bwd`` adds up over a group's ``wide`` blocks), ``ddt`` and
    ``da``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    grid, dims, (xy, bc, dbc, gate, kept, state) = _plan(x, Bm, dt, True,
                                                         wide)
    parts = Bm.shape[:2] + (Bm.shape[2] * wide,)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, **dims),
        name="hetu_ssd_bwd", grid=grid,
        in_specs=[xy, bc, bc, gate, gate, kept, state, xy, state],
        out_specs=[xy, dbc, dbc, gate, gate],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(parts, Bm.dtype),
                   jax.ShapeDtypeStruct(parts, Cm.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(dt.shape, _F32)],
        scratch_shapes=[pltpu.VMEM(states.shape[-2:], _F32),
                        pltpu.VMEM((dims["heads"], L), _F32)],
        compiler_params=params(interpret, WALK, VMEM_LIMIT),
        interpret=interpret,
    )(x, Bm, Cm, dt, a, states, last, dy, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, dt, a, Bm, Cm, wide):
    return _scan_fwd(x, dt, a, Bm, Cm, wide)[0]


def _scan_fwd(x, dt, a, Bm, Cm, wide):
    y, last, states = _fwd_call(x, dt, a, Bm, Cm, wide=wide,
                                interpret=dispatch.interpret())
    return (y, last), (x, dt, a, Bm, Cm, states, last)


def _scan_bwd(wide, res, grads):
    x, dt, a, Bm, Cm, states, last = res
    dy, dlast = grads
    dx, dB, dC, ddt, da = _bwd_call(x, dt, a, Bm, Cm, states, last, dy, dlast,
                                    wide=wide, interpret=dispatch.interpret())
    if wide > 1:            # a group's dB, dC: the sum of its blocks' parts
        N = Bm.shape[2] * wide // dt.shape[1]
        dB, dC = (t.reshape(t.shape[:2] + (-1, wide, N)).sum(3, dtype=_F32)
                  .astype(t.dtype).reshape(Bm.shape) for t in (dB, dC))
    return dx, ddt, da, dB, dC


_scan.defvjp(_scan_fwd, _scan_bwd)


def heads_a_program(R, P):
    """How many of a group's ``R`` heads of ``P`` channels one program holds:
    the most, up to ``HEADS``, that divide ``R`` and fill whole 128-lane
    tiles; 0 where none do."""
    return next((r for r in range(min(R, HEADS), 0, -1)
                 if R % r == 0 and (r * P) % 128 == 0), 0)


def _block_bytes(r, P, N, itemsize, nc=CHUNKS):
    """Bytes of VMEM the backward program's blocks take, each with the
    pipeline's second buffer (the forward's are fewer): x, dy and dx, B, C
    and their gradients, dt, a and theirs, the kept states, the last state
    and its gradient, and the two scratch arrays."""
    rows, rp = nc * L, r * P
    blocks = (3 * rows * rp * itemsize + 4 * rows * N * itemsize
              + 4 * nc * max(r, 8) * L * 4 + (nc + 2) * N * rp * 4)
    return 2 * blocks + N * rp * 4 + max(r, 8) * L * 4


def unsupported(x, Bm, Cm, chunk):
    """Why the kernels do not take ``chunk_ssd``'s operands, or None when
    they do."""
    P, R, N = x.shape[-1], x.shape[-2] // Bm.shape[-2], Bm.shape[-1]
    r = heads_a_program(R, P)
    if chunk != L:
        return f"chunk!={L}"
    if P % 64 or not r:
        return "head_dim_not_64_aligned"
    if N % 128:
        return "state_not_128_aligned"
    if not x.dtype == Bm.dtype == Cm.dtype:
        return "dtype:mixed"
    if jnp.dtype(x.dtype) not in (jnp.dtype(jnp.bfloat16), jnp.dtype(_F32)):
        return f"dtype:{jnp.dtype(x.dtype).name}"
    # what one program holds however a group is cut: the rest of the limit
    # is the chains' own values
    if _block_bytes(r, P, N, jnp.dtype(x.dtype).itemsize) > VMEM_LIMIT // 2:
        return "blocks_over_vmem"
    return None


def _count_entry(R, r):
    """Trace-time count of the cut taken, beside ``dispatch.record``'s count
    of the kernel-versus-jnp choice."""
    telemetry.get_registry().counter(
        "hetu_ssd_entry_total",
        "Trace-time calls of the state-space scan's kernels by the heads of "
        "a group and the heads one program holds",
        labels=("heads_a_group", "heads_a_program"),
    ).labels(heads_a_group=str(R), heads_a_program=str(r)).inc()


def entries():
    """``{(heads_a_group, heads_a_program): count}`` of the calls traced so
    far (empty while telemetry is disabled)."""
    return {(int(lab["heads_a_group"]), int(lab["heads_a_program"])): n
            for lab, n in dispatch.counted("hetu_ssd_entry_total")}


def ssd(x, dt, A, Bm, Cm):
    """``chunk_ssd`` at chunk 128 through the kernel pair: ``x [b, T, H,
    P]``, ``dt [b, T, H]``, ``A [H]``, ``B, C [b, T, G, N]`` -> ``(y [b, T, H,
    P]`` in ``x``'s type, the last state ``[b, H, P, N]`` f32)``.  Any ``T``:
    positions of padding write nothing and decay nothing (dt 0) and their
    outputs are cut off.  A group of more than ``HEADS`` heads runs as
    ``wide`` blocks of ``R`` heads, each a program of its own on the grid's
    second axis (below, ``G`` counts those blocks): a group's blocks read its
    ``B`` and ``C`` rows in place, once a block, and the group's ``dB`` and
    ``dC`` are the sum of what its blocks write."""
    b, T, H, P = x.shape
    N = Bm.shape[3]
    R = heads_a_program(H // Bm.shape[2], P)
    G, wide = H // R, H // Bm.shape[2] // R
    _count_entry(wide * R, R)
    nc = min(CHUNKS, -(-T // L))
    blocks = -(-T // (nc * L))
    pad = blocks * nc * L - T

    def rows(t):                       # [b, T, .., d] -> [b, T', .. d]
        t = t.reshape(b, T, -1)
        return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t

    # [b, T, H] -> [b, G, T' / (n L), n, R, L]: a chunk along the lanes
    dt = rows(dt.astype(_F32)).reshape(b, blocks, nc, L, G, R)
    dt = dt.transpose(0, 4, 1, 2, 5, 3)
    a = dt * A.astype(_F32).reshape(G, 1, 1, R, 1)
    y, last = _scan(rows(x), dt, a, rows(Bm), rows(Cm), wide)
    last = last.reshape(b, G, N, R, P).transpose(0, 1, 3, 4, 2)
    return y[:, :T].reshape(b, T, H, P), last.reshape(b, H, P, N)
