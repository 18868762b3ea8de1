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
group's heads in VMEM, each once (in place also the skip's part of ``dx`` and
``dD``'s partial sums, below).  ``dA`` and the rest of ``ddt`` follow
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

Two entries hand the pair its operands (as ``kda`` / ``kda_in_place`` and
``gated_delta_rule`` / ``gated_delta_rule_in_place`` do), chosen by what the
caller holds and counted in ``hetu_ssd_form_total{form}`` (``forms()``).
``ssd`` (``plain``) takes ``x``, ``B`` and ``C`` as arrays of their own:
``chunk_ssd``'s operands, the benchmark's probe.  ``ssd_in_place`` (PR 71,
what the layer runs) takes ``xBC [b, T, d + 2 G N]`` as the convolution wrote
it, three times: the three windows are blocks of the ONE array whose
lane-block index starts at the window's (``_plan``): ``x`` blocks of ``R P``
lanes at ``g``, ``B`` blocks of ``N`` lanes at ``d / N + g // wide`` and ``C``
at ``d / N + G + g // wide`` (Granite's 4,352 lanes are eight and a half ``x``
blocks: the half is never indexed), so no slice of ``xBC`` reaches HBM,
forward, recomputed or backward (a Pallas operand is a whole array: XLA wrote
each slice and the kernel read it back, 0.17 ms a call for ``x`` alone at
8,192 x 4,096 bf16).  The rule that admits it is read from the shapes
(``in_place_unsupported``: ``d`` whole blocks of ``N`` lanes, as it is of ``R
P``; ``T`` whole blocks of ``CHUNKS x 128`` positions, or fewer chunks in one
program, since padding ``xBC`` is the copy it saves); where it refuses the
caller slices as before.  In place the skip ``D x`` is the kernels' too.
Forward, on the chunk the program holds: ``y = round(y)``, then ``round(f32(y)
+ D_h f32(x))``, rounded where the layer's ``jax.numpy`` lines round, so the
values are theirs bit for bit (v5e, my chip run, PR 71); ``D [H]`` enters f32
on its heads' lanes, ``[G', 1, R P]``, a block a program.  Backward by hand:
``dx += D_h dy`` in f32 before ``dx``'s one rounding, and ``dD`` as f32
partial sums ``sum(dy x)`` over a program's positions in an output block ``[b,
G', 1, R P]`` that stays in VMEM along the sequence axis; XLA adds the batch
rows and a head's lanes up.  ``d xBC`` is ONE concatenation ``dx | dB | dC``
by XLA.  (Slices, kernels and XLA's f32 skip 5.54 ms a mixer forward and
backward at the Granite cell's shape, in place 3.70; 5.56 and 3.68 at the
Nemotron-H cell's: v5e, my chip run, PR 71.)

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
from . import common, dispatch
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


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, *refs, nc, heads, p,
                skip):
    import jax.experimental.pallas as pl
    d_ref = refs[0] if skip else None            # ``D`` on its heads' lanes
    y_ref, last_ref, s0_ref, s_ref = refs[-4:]
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
            x = x_ref[rows, at]
            y, S = _head_fwd(
                x, c["CB"], CS[:, at], ST[:, at], c["BT"],
                *_of_head(c, h, ("cs_c", "cs_r", "dt_r", "te_r", "fs_c",
                                 "last")))
            y = y.astype(y_ref.dtype)
            if skip:        # rounded where the layer's jax.numpy lines round
                y = (y.astype(_F32) + d_ref[:, at] * x.astype(_F32)
                     ).astype(y_ref.dtype)
            y_ref[rows, at] = y
            s_ref[:, at] = S
    walk(nc, body)

    @pl.when(i == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = s_ref[...]


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, s0_ref, last_ref, dy_ref,
                dlast_ref, *refs, nc, heads, p, skip):
    import jax.experimental.pallas as pl
    if skip:            # ``D``'s row in, its gradient's partial sums out
        d_ref, dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dd_ref, ds_ref, \
            end_ref = refs
    else:
        dx_ref, db_ref, dc_ref, ddt_ref, da_ref, ds_ref, end_ref = refs
    lanes = [slice(h * p, (h + 1) * p) for h in range(heads)]

    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = dlast_ref[...]
        end_ref[...] = _state_dot(dlast_ref[...], last_ref[...], heads=heads)
        if skip:
            dd_ref[...] = jnp.zeros_like(dd_ref)

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
            dy = dy_ref[rows, at]
            dx, dCB_h, dBT_h, dCT_h, ddt_h, dcs_h, dS0T = _head_bwd(
                x_ref[rows, at], dy, c["CB"], S0T[:, at],
                dST[:, at], c["BT"], CT,
                *_of_head(c, h, ("cs_c", "cs_r", "dt_r", "te_r", "fs_r",
                                 "last")))
            if skip:
                dx = dx + d_ref[:, at] * dy.astype(_F32)
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
        if skip:    # dD: sum(dy x) over the chunk's rows, every head's lanes
            dd_ref[...] += jnp.sum(
                dy_ref[rows, :].astype(_F32) * x_ref[rows, :].astype(_F32),
                axis=0, keepdims=True)
    walk(nc, body)


def _plan(dt, rp, N, reverse, wide, d=None):
    """Grid, the kernels' static sizes and the block specs by name: ``x`` (and
    y, dy, dx), ``B`` and ``C`` as the kernels read them, ``part`` (dB and dC
    as the backward kernel writes them), ``gate`` (dt and a), the ``kept``
    states, a ``state``, ``skip`` (``D`` on a program's lanes) and ``dskip``
    (its gradient's partial sums, a row a sequence: the block stays where it
    is along the grid's last axis); ``reverse``: the blocks of chunks from the
    last to the first.  The grid's second axis is over blocks of heads
    (``dt``'s second dimension), ``wide`` of them to a group: they read the
    one ``B`` and ``C`` of their group and each writes its own part of ``dB``
    and ``dC``.  ``d`` None: ``x [b, T, H P]`` and ``B``, ``C [b, T, G N]``
    are arrays of their own.  Else all three are windows of ``xBC [b, T, d +
    2 G N]``, each a block whose lane index starts at the window's: ``x``
    blocks of ``R P`` lanes from 0, ``B`` blocks of ``N`` lanes from ``d / N``
    and ``C`` behind its ``G`` blocks (``in_place_unsupported`` has refused a
    ``d`` that is not whole blocks of ``N``; a last, partial block of ``R P``
    lanes is never indexed)."""
    import jax.experimental.pallas as pl
    b, G, blocks, nc, R, _ = dt.shape
    seq, kept, state = common.blocks(nc, L, blocks, reverse)
    # a group's ``wide`` blocks of heads read its one block of ``B``, ``C``
    group = (lambda g: g // wide) if wide > 1 else (lambda g: g)
    b_at, c_at = (0, 0) if d is None else (d // N, d // N + G // wide)
    return ((b, G, blocks), dict(nc=nc, heads=R, p=rp // R),
            dict(x=seq(rp), B=seq(N, b_at, group), C=seq(N, c_at, group),
                 part=seq(N), gate=kept(None, R, L), kept=kept(None, N, rp),
                 state=state(None, N, rp),
                 skip=pl.BlockSpec((None, 1, rp), lambda b, g, i: (g, 0, 0)),
                 dskip=state(None, 1, rp)))


def _read(ops, lanes):
    """``(x, B, C, dt, a)`` as the kernels are handed them, the rest of
    ``ops``, the lanes ``R P`` of a program's ``x`` block and ``d`` for
    ``_plan``: ``ops`` starts with ``x, dt, a, B, C``, or with ``lanes = (P,
    N)`` with ``xBC, dt, a, D``, ``xBC`` then read three times and ``D [H]``
    f32 spread over its heads' lanes, ``[G', 1, R P]``, first of the rest."""
    if lanes is None:
        x, dt, a, Bm, Cm, *rest = ops
        G = dt.shape[1]
        return (x, Bm, Cm, dt, a), rest, x.shape[2] // G, None
    xbc, dt, a, D, *rest = ops
    G, R = dt.shape[1], dt.shape[4]
    skip = jnp.repeat(D.astype(_F32), lanes[0]).reshape(G, 1, -1)
    rp = R * lanes[0]
    return (xbc, xbc, xbc, dt, a), [skip] + rest, rp, G * rp


@functools.partial(jax.jit, static_argnames=("interpret", "wide", "lanes"))
def _fwd_call(*ops, interpret, wide, lanes=None):
    """``x [b, T, H P]``, ``dt, a [b, G', T / (n L), n, R, L]`` f32 (``n``
    chunks a program; ``G' = wide G`` blocks of ``R`` heads, ``wide`` to a
    group), ``B, C [b, T, G N]``; or with ``lanes = (P, N)`` ``xBC [b, T, H P
    + 2 G N]``, ``dt, a``, ``D [H]``, and ``y`` is then ``round(round(y) + D
    x)``: ``(y [b, T, H P], last state^T [b, G', N, R P], chunk-start
    states^T [b, G', T / (n L), n, N, R P])``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    ops, skip, rp, d = _read(ops, lanes)
    x, Bm, dt = ops[0], ops[1], ops[3]
    b, G, blocks, nc = dt.shape[:4]
    N = lanes[1] if lanes else Bm.shape[2] * wide // G
    grid, dims, at = _plan(dt, rp, N, False, wide, d)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, skip=bool(lanes), **dims),
        name="hetu_ssd_fwd", grid=grid,
        in_specs=[at["x"], at["B"], at["C"], at["gate"], at["gate"]]
        + [at["skip"]] * len(skip),
        out_specs=[at["x"], at["state"], at["kept"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape[:2] + (G * rp,), x.dtype),
                   jax.ShapeDtypeStruct((b, G, N, rp), _F32),
                   jax.ShapeDtypeStruct((b, G, blocks, nc, N, rp), _F32)],
        scratch_shapes=[pltpu.VMEM((N, rp), _F32)],
        compiler_params=params(interpret, WALK, VMEM_LIMIT),
        interpret=interpret,
    )(*ops, *skip)


@functools.partial(jax.jit, static_argnames=("interpret", "wide", "lanes"))
def _bwd_call(*ops, interpret, wide, lanes=None):
    """``_fwd_call``'s operands, the kept states, the last state, ``dy`` and
    the last state's cotangent: ``dx``, ``dB`` and ``dC`` (``[b, T, G' N]``: a
    block of heads' own part, which ``_scan_bwd`` adds up over a group's
    ``wide`` blocks), ``ddt`` and ``da``; with ``lanes`` ``dx`` holds the
    skip's ``D dy`` too, added in f32 before its one rounding, and a sixth
    output is ``sum(dy x)`` over a program's positions, f32 ``[b, G', 1, R
    P]``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    ops, (*skip, states, last, dy, dlast), rp, d = _read(ops, lanes)
    x, Bm, dt = ops[0], ops[1], ops[3]
    b, G = dt.shape[:2]
    N = states.shape[-2]
    grid, dims, at = _plan(dt, rp, N, True, wide, d)
    part = jax.ShapeDtypeStruct(x.shape[:2] + (G * N,), x.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, skip=bool(lanes), **dims),
        name="hetu_ssd_bwd", grid=grid,
        in_specs=[at["x"], at["B"], at["C"], at["gate"], at["gate"],
                  at["kept"], at["state"], at["x"], at["state"]]
        + [at["skip"]] * len(skip),
        out_specs=[at["x"], at["part"], at["part"], at["gate"], at["gate"]]
        + [at["dskip"]] * len(skip),
        out_shape=[jax.ShapeDtypeStruct(dy.shape, x.dtype), part, part,
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(dt.shape, _F32)]
        + [jax.ShapeDtypeStruct((b, G, 1, rp), _F32)] * len(skip),
        scratch_shapes=[pltpu.VMEM((N, rp), _F32),
                        pltpu.VMEM((dims["heads"], L), _F32)],
        compiler_params=params(interpret, WALK, VMEM_LIMIT),
        interpret=interpret,
    )(*ops, states, last, dy, dlast, *skip)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _scan(wide, lanes, *ops):
    return _scan_fwd(wide, lanes, *ops)[0]


def _scan_fwd(wide, lanes, *ops):
    y, last, states = _fwd_call(*ops, wide=wide, lanes=lanes,
                                interpret=dispatch.interpret())
    return (y, last), ops + (states, last)


def _scan_bwd(wide, lanes, res, grads):
    dx, dB, dC, ddt, da, *dD = _bwd_call(*res, *grads, wide=wide, lanes=lanes,
                                         interpret=dispatch.interpret())
    if wide > 1:            # a group's dB, dC: the sum of its blocks' parts
        b, T, GN = dB.shape
        N = res[-2].shape[-2]
        dB, dC = (t.reshape(b, T, -1, wide, N).sum(3, dtype=_F32)
                  .astype(t.dtype).reshape(b, T, GN // wide)
                  for t in (dB, dC))
    if lanes is None:
        return dx, ddt, da, dB, dC
    # d xBC is ONE concatenation; dD the batch rows' and a head's lanes' sum
    D = res[3]
    dD = dD[0].reshape(dx.shape[0], D.shape[0], lanes[0]).sum((0, 2))
    return (jnp.concatenate([dx, dB, dC], -1), ddt, da, dD.astype(D.dtype))


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


def in_place_unsupported(T, d, N):
    """Why ``ssd_in_place`` does not read ``xBC [b, T, d + 2 G N]`` where the
    kernels take its heads (``unsupported``), or None when it does: the
    windows of ``B`` and ``C`` must start at whole blocks of ``N`` lanes
    (``x``'s blocks of ``R P`` lanes start at 0, and ``d = H P`` is whole
    blocks of them since ``R`` divides a group's heads), and ``T`` must be
    whole blocks of a program's chunks, since padding ``xBC`` is the copy this
    entry is there to save."""
    if d % N:
        return "bc_window_not_block_aligned"
    if common.cut(T, L, CHUNKS)[2]:
        return "positions_not_whole_blocks"
    return None


def _count_entry(R, r, form):
    """Trace-time counts beside ``dispatch.record``'s count of the
    kernel-versus-jnp choice: the cut taken, and the entry (``form``:
    ``plain``, the caller's ``x``, ``B``, ``C``, or ``in_place``, the windows
    of ``xBC`` and the skip)."""
    registry = telemetry.get_registry()
    registry.counter(
        "hetu_ssd_entry_total",
        "Trace-time calls of the state-space scan's kernels by the heads of "
        "a group and the heads one program holds",
        labels=("heads_a_group", "heads_a_program"),
    ).labels(heads_a_group=str(R), heads_a_program=str(r)).inc()
    registry.counter(
        "hetu_ssd_form_total",
        "Trace-time calls of the state-space scan's kernels by entry: plain "
        "(x, B and C arrays of their own, the skip the caller's) or in_place "
        "(the three windows of the convolution's output read where they are, "
        "the skip inside the kernels)",
        labels=("form",)).labels(form=form).inc()


def entries():
    """``{(heads_a_group, heads_a_program): count}`` of the calls traced so
    far (empty while telemetry is disabled)."""
    return {(int(lab["heads_a_group"]), int(lab["heads_a_program"])): n
            for lab, n in dispatch.counted("hetu_ssd_entry_total")}


def forms():
    """``{form: count}`` of the calls traced so far, ``form`` ``plain`` or
    ``in_place`` (empty while telemetry is disabled)."""
    return {lab["form"]: n
            for lab, n in dispatch.counted("hetu_ssd_form_total")}


def _gates(dt, A, G, R, cut):
    """``dt [b, T, H]``, ``A [H]`` -> ``dt``, ``a = dt A`` as ``[b, G, T' / (n
    L), n, R, L]`` f32: a chunk along the lanes."""
    nc, blocks, pad = cut
    dt = common.rows(dt.astype(_F32), pad)
    dt = dt.reshape(dt.shape[0], blocks, nc, L, G, R).transpose(
        0, 4, 1, 2, 5, 3)
    return dt, dt * A.astype(_F32).reshape(G, 1, 1, R, 1)


def ssd(x, dt, A, Bm, Cm):
    """``chunk_ssd`` at chunk 128 through the kernel pair: ``x [b, T, H,
    P]``, ``dt [b, T, H]``, ``A [H]``, ``B, C [b, T, G, N]`` -> ``(y [b, T, H,
    P]`` in ``x``'s type, the last state ``[b, H, P, N]`` f32)``.  Any ``T``
    (``common.cut``: positions of padding write nothing and decay nothing, dt
    0, and their outputs are cut off).  A group of more than ``HEADS`` heads
    runs as ``wide`` blocks of ``R`` heads, each a program of its own on the
    grid's second axis (below, ``G`` counts those blocks): a group's blocks
    read its ``B`` and ``C`` rows in place, once a block, and the group's
    ``dB`` and ``dC`` are the sum of what its blocks write."""
    b, T, H, P = x.shape
    N = Bm.shape[3]
    R = heads_a_program(H // Bm.shape[2], P)
    G, wide = H // R, H // Bm.shape[2] // R
    _count_entry(wide * R, R, "plain")
    cut = common.cut(T, L, CHUNKS)
    dt, a = _gates(dt, A, G, R, cut)
    x, Bm, Cm = (common.rows(t, cut[2]) for t in (x, Bm, Cm))
    y, last = _scan(wide, None, x, dt, a, Bm, Cm)
    last = last.reshape(b, G, N, R, P).transpose(0, 1, 3, 4, 2)
    return y[:, :T].reshape(b, T, H, P), last.reshape(b, H, P, N)


def ssd_in_place(xbc, dt, A, D, *, heads, head_dim, groups, state):
    """The scan and its skip from the convolution's output: ``xBC [b, T, H P
    + 2 G N]`` (``x | B | C``), ``dt [b, T, H]`` (after its softplus), ``A,
    D [H]`` -> ``y [b, T, H P]`` in ``xBC``'s type, what ``ssd`` gives on the
    three slices followed by ``round(f32(y) + D f32(x))``, none of which
    reaches HBM; the cotangent of ``xBC`` comes back whole.  ``T`` whole
    blocks of a program's chunks (``in_place_unsupported``)."""
    R = heads_a_program(heads // groups, head_dim)
    G, wide = heads // R, heads // groups // R
    _count_entry(wide * R, R, "in_place")
    dt, a = _gates(dt, A, G, R, common.cut(xbc.shape[1], L, CHUNKS))
    return _scan(wide, (head_dim, state), xbc, dt, a, D.astype(_F32))[0]
