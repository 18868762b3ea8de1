"""What the recurrent mixers' kernel files share and none of them owns: the
chunk walk of the delta rules and the state-space scan (``gated_delta.py``,
``kda.py``, ``ssd.py``) and the tiles of the row-wise kernels around them
(``causal_conv.py``, ``gated_norm.py``).  A kernel file imports from here
and not from another kernel file.

The chunk walk: a program holds several heads and runs their chains of
dependent stages in step (``together``: the chunk functions are generators
that yield between stages), ``walk``s its chunks, and reads a chunk's
``chunk_rows`` and a head's ``head_lanes`` in place.  A product with an f32
operand runs as bf16 passes over the operands' bf16 ``parts`` (``dot32``), and
a chunk's unit triangular system is inverted by substitution in blocks
(``unit_lower_inverse``); ``gated_delta.py``'s docstring has the reasons and
the measurements of both.  The stages here are jitted functions of values,
as that file's are, so every kernel and call shares one trace of each.

The host side of the walk is here once as well: how a ``[B, T, ..]`` array
is cut into programs of chunks and padded (``cut``, ``rows``, ``by_chunk``),
how a program's blocks and a window of a mixed array are addressed
(``blocks``), and a chunk's L2 norm (``unit``, ``unit_bwd``).  A file's
``_plan`` is the one place that names its operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: positions a chunk (``ops.gated_delta.CHUNK``: the kernels are written for it)
C = 64
#: chunks a program walks: amortises the cost of a grid step over eight
#: chunks (4 and 16 measured the same)
CHUNKS = 8
#: rows of a diagonal block solved row by row before the merges (8 and 32
#: measured 1-5% slower)
DIAG = 16
#: scoped VMEM the kernels may use: the backward program's seven [512, 512]
#: blocks and 32 kept states, double-buffered, are about 11 MiB, and its
#: body spills f32 [64, 128] and [128, 128] values beside them
VMEM_LIMIT = 64 * 2 ** 20

_F32 = jnp.float32
_BF16 = jnp.bfloat16
NN, NT, TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def dot(a, b, dims):
    """``a . b`` contracting ``dims``, operands as they are, f32 sums."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=_F32)


#: bf16 parts of an f32 operand: 3 x 8 bits, all of its mantissa
PARTS = 3


def parts(x):
    """``x`` as bf16 arrays that add up to it: itself where it is bf16, else
    the ``PARTS`` leading bf16 parts of an f32 ``x`` (8 bits of mantissa
    each)."""
    if x.dtype == _BF16:
        return [x]
    out = []
    for _ in range(PARTS - 1):
        out.append(x.astype(_BF16))
        x = x - out[-1].astype(_F32)
    return out + [x.astype(_BF16)]


def spread(heads, d, times=1, dtype=_BF16):
    """``[times * heads, heads * d]`` of 0 and 1: row ``i`` holds 1 on the
    ``d`` lanes of head ``i % heads``."""
    return (jnp.arange(heads * d)[None, :] // d
            == jnp.arange(times * heads)[:, None] % heads).astype(dtype)


def widen(x, d):
    """``x [B, S, H]`` f32 -> f32 ``[B, S, H d]``, a head's number on each of
    its ``d`` lanes, EXACTLY: the three bf16 parts of ``x`` (all 24 bits of
    it) side by side times ``spread``, f32 sums of at most three terms that
    are bits of one number.  A product on the matrix unit, because the other
    way, ``x[..., None]`` on an ``[.., H, d]`` view, is a pass over HBM in
    f32 (PRs 41 and 48)."""
    return jnp.matmul(jnp.concatenate(parts(x), axis=-1),
                      spread(x.shape[-1], d, PARTS),
                      preferred_element_type=_F32)


def dot32(a, b, dims):
    """A product with f32 operands on the matrix unit, at f32 precision: bf16
    passes over the pairs of parts whose indices add up to less than
    ``PARTS`` (six for two f32 operands, as XLA's ``HIGHEST``; what is left
    out is under 2^-24 of the product), f32 sums, least terms first.  An
    operand that is bf16 is one part: three passes."""
    ap, bp = parts(a), parts(b)
    out = None
    for i in reversed(range(len(ap))):
        for j in reversed(range(len(bp))):
            if i + j < PARTS:
                t = dot(ap[i], bp[j], dims)
                out = t if out is None else out + t
    return out


def dot32_stacked(a, b, dims):
    """``dot32`` as ONE product: the parts of each operand put one behind the
    other along the contraction, ``[a0 a0 a1 a0 a1 a2] . [b2; b1; b1; b0; b0;
    b0]`` for two f32 operands, so the same pairs of parts are multiplied,
    least terms first, and the matrix unit adds them up in f32 where
    ``dot32`` pops each pair's product and adds on the vector unit.  For a
    contraction that is whole lane tiles or runs down the rows (``TN``):
    stacking the parts then moves no data."""
    ap, bp = parts(a), parts(b)
    pairs = [(i, j) for i in reversed(range(len(ap)))
             for j in reversed(range(len(bp))) if i + j < PARTS]
    return dot(jnp.concatenate([ap[i] for i, _ in pairs], axis=dims[0][0]),
               jnp.concatenate([bp[j] for _, j in pairs], axis=dims[1][0]),
               dims)


def iotas():
    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    return row, col


def to_col(r, eye):
    """``[1, C] -> [C, 1]``, exactly (one term a sum)."""
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(r, (C, C)), 0.0), axis=1,
                   keepdims=True)


def to_row(c, eye):
    """``[C, 1] -> [1, C]``, exactly."""
    return jnp.sum(jnp.where(eye, jnp.broadcast_to(c, (C, C)), 0.0), axis=0,
                   keepdims=True)


def _solve_masks():
    """``(rel, pick)``, int32 ``[C, C]``: the column's offset from the start
    of the row's diagonal block, and the row's index inside its block where
    the column is in that block too (else -1)."""
    row, col = iotas()
    shift = DIAG.bit_length() - 1
    same = (row >> shift) == (col >> shift)
    return (col - ((row >> shift) << shift),
            jnp.where(same, row & (DIAG - 1), -1))


@jax.jit
def _solve_open(L, LT):
    rel, pick = _solve_masks()
    return (jnp.where(pick >= 0, -L, 0.0), jnp.where(pick >= 0, -LT, 0.0),
            rel, pick)


@functools.partial(jax.jit, static_argnames="i")
def _solve_row(A, ATn, rel, pick, *, i):
    # coefficient of row j for row i of j's block: -L[block(j) + i, j]
    c = jnp.sum(jnp.where(rel == i, ATn, 0.0), axis=1, keepdims=True)
    r = jnp.sum(c * A, axis=0, keepdims=True)
    return jnp.where(pick == i, A + r, A)


@functools.partial(jax.jit, static_argnames="size")
def _merge_left(T, L, *, size):
    row, col = iotas()
    s = size.bit_length() - 1
    off = ((row >> (s + 1)) == (col >> (s + 1))) & ((row >> s) != (col >> s))
    return dot32(T, jnp.where(off, L, 0.0), NN)


@jax.jit
def _merge_right(T, TB):
    return T - dot32(TB, T, NN)


def unit_lower_inverse(L, LT):
    """``(I + L)^-1`` for a strictly lower triangular ``L [C, C]`` given with
    its transpose; see the module's docstring.  The delta rules' forward
    kernels call it and write the result out; their backward kernels read
    that (PR 66) and do not come here.  (Walking the rows by sublane
    tiles of 8, so that a step touches only the tiles it reads and writes,
    was 0.8% of the cell's step faster and seven times the stages.)"""
    A, ATn, rel, pick = _solve_open(L, LT)
    for i in range(1, DIAG):
        A = _solve_row(A, ATn, rel, pick, i=i)
        yield
    row, col = iotas()
    T = A + jnp.where(row == col, 1.0, 0.0)
    size = DIAG
    while size < C:
        TB = _merge_left(T, L, size=size)
        yield
        T = _merge_right(T, TB)
        yield
        size *= 2
    return T


def together(gens):
    """Run generators in step, one stage each in turn, and return their
    values: the chains of a program's heads are then interleaved in program
    order, which Mosaic's scheduler stays close to."""
    gens, out = list(gens), {}
    live = list(range(len(gens)))
    while live:
        for i in list(live):
            try:
                next(gens[i])
            except StopIteration as stop:
                out[i] = stop.value
                live.remove(i)
    return [out[i] for i in range(len(gens))]


def pick(block, j):
    """Row ``j`` (traced) of a small ``[n, C]`` block, ``[1, C]``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, block.shape, 0)
    return jnp.sum(jnp.where(rows == j, block, 0.0), axis=0, keepdims=True)


def put(ref, j, row):
    """Write ``row [1, C]`` as row ``j`` (traced) of a small block."""
    rows = jax.lax.broadcasted_iota(jnp.int32, ref.shape, 0)
    ref[...] = jnp.where(rows == j, jnp.broadcast_to(row, ref.shape),
                         ref[...])


def walk(nc, body):
    """``body(j)`` for the program's chunks ``j``."""
    def step(j, carry):
        body(j)
        return carry
    jax.lax.fori_loop(0, nc, step, 0)


def chunk_rows(j, n):
    """The ``n`` rows of chunk ``j`` (traced)."""
    import jax.experimental.pallas as pl
    return pl.ds(pl.multiple_of(j * n, n), n)


def cut(T, chunk=C, chunks=CHUNKS):
    """Chunks a program, programs along the sequence and the positions of
    padding behind ``T``: those write nothing, decay nothing (the callers'
    gates are 0 there) and their outputs are cut off."""
    nc = min(chunks, -(-T // chunk))
    groups = -(-T // (nc * chunk))
    return nc, groups, groups * nc * chunk - T


def rows(x, pad, **how):
    """``[B, T, ..] -> [B, T', lanes]``: ``pad`` positions of 0 (or of
    ``jnp.pad``'s ``constant_values``) behind ``T``."""
    x = x.reshape(x.shape[:2] + (-1,))
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0)), **how) if pad else x


def by_chunk(x, nc, groups, pad):
    """``[B, T, H] -> [B, H, T' / (n C), n, C]`` f32, 0 at the padding: a
    head's number a position of the delta rules (g, beta), ``cut`` before."""
    B, _, H = x.shape
    return jnp.moveaxis(rows(x.astype(_F32), pad), 2, 1).reshape(
        B, H, groups, nc, C)


def unit(t):
    """The rows of ``t [C, d]`` over their norms, f32, and the norms' inverses
    ``[C, 1]`` (the layers' ``l2norm``: 1e-6 under the root).  Not jitted:
    ``kda.py`` differentiates through it inside its kernel."""
    t = t.astype(_F32)
    r = jax.lax.rsqrt(jnp.sum(t * t, axis=1, keepdims=True) + 1e-6)
    return t * r, r


def unit_bwd(dt, t, r):
    """``unit``'s cotangent from that of its rows ``dt``, the rows ``t`` and
    the norms' inverses ``r``: one lane sum a row."""
    return r * (dt - t * jnp.sum(dt * t, axis=1, keepdims=True))


def blocks(nc, chunk, groups, reverse):
    """The block specs of a plan whose grid is (batch, block of heads, block
    of chunks), ``nc`` chunks of ``chunk`` rows a program and ``groups``
    programs a sequence (``reverse``: from the last to the first), as three
    functions: ``seq(lanes, first=0, head=..)`` a program's ``nc * chunk``
    rows of a ``[B, T', ..]`` array, ``lanes`` wide at lane block ``first +
    head(h)`` (``first``: where a window of a mixed array starts, in blocks);
    ``kept(lead, *tail)`` what every chunk has of its own, ``[B, H', groups,
    nc, *tail]``; ``state(lead, *tail)`` what a sequence has, ``[B, H',
    *tail]``; ``lead`` of the heads' axis a program (None: one)."""
    import jax.experimental.pallas as pl
    at = (lambda i: groups - 1 - i) if reverse else (lambda i: i)

    def seq(lanes, first=0, head=lambda h: h):
        return pl.BlockSpec((None, nc * chunk, lanes),
                            lambda b, h, i: (b, at(i), first + head(h)))

    def kept(lead, *tail):
        return pl.BlockSpec((None, lead, None, nc) + tail,
                            lambda b, h, i: (b, h, at(i), 0) + (0,) * len(tail))

    def state(lead, *tail):
        return pl.BlockSpec((None, lead) + tail,
                            lambda b, h, i: (b, h) + (0,) * len(tail))
    return seq, kept, state


def head_lanes(hb, dk, dv):
    """The lanes of each of a program's heads in its q / k and v / o blocks."""
    return [(slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv))
            for h in range(hb)]


#: the grid of a walk: two axes in parallel, the last (the chunks) in turn
WALK = ("parallel", "parallel", "arbitrary")


def params(interpret, order, vmem_limit=None):
    """A ``pallas_call``'s ``compiler_params``: the grid axes' ``order``
    and the scoped VMEM it may use (Mosaic's own limit where None); nothing
    in interpret mode."""
    from jax.experimental.pallas import tpu as pltpu
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=order, vmem_limit_bytes=vmem_limit)


def fit(n, most, unit):
    """The largest multiple of ``unit`` up to ``most`` that divides ``n``
    (0 where none does)."""
    return next((t for t in range(min(most, n) // unit * unit, 0, -unit)
                 if n % t == 0), 0)
