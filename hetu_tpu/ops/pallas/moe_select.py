"""The router's choice: the ``k`` largest of each row of ``[T, E]`` f32 scores
by ``k`` masked maxima, not by a sort of all ``E``.

``jax.lax.top_k`` lowers on this TPU to a full sort of every row: a router
that needs 8 of 512 orders 512 numbers a token (0.68 ms a call at ``[8192,
512]``, PR 59's traced Ling-3.0 step).  ``select`` has ``top_k``'s indices
exactly (the largest first, ties to the lower index, ``-inf`` entries last
and by index among themselves) from ``k`` passes over a tile that stays in
VMEM: the row's maximum, the lowest position that attains it, that entry
taken out.

``hetu_moe_select``: grid (token tiles).  A program reads ``[tt, E]`` and
turns it, so that the experts lie down the sublanes and ``tt`` tokens along
the lanes: a maximum over the experts is then an elementwise chain over the
``E / 8`` registers of a lane tile and ONE sublane reduction, where rows on
sublanes pay two lane reductions a pass and eight tokens (on a v5e the two
forms took the same time at E = 512 and the turned one two thirds at 128:
``TOKENS``).  A pass keeps two arrays, the scores ``x`` and the positions
``pos`` (f32: exact below 2^24, and the minimum is one vector operation), and
is

    m = max_e x;  c = where(x == m, pos, E);  i = min_e c        [1, tt]
    hit = c == i;  x = where(hit, -inf, x);  pos = where(hit, E, pos)

``pos`` is what makes ``-inf`` entries exact: a taken entry reads ``-inf``
too, but its position reads ``E`` and loses to every entry still there.  The
indices leave as ``[k, T]`` (tokens along the lanes: whole stores) and XLA
turns the small result; ``k`` is rounded up to whole sublanes there and the
rows past it hold nothing.  ``E`` is padded to whole lane tiles with ``-inf``
(never chosen while ``k <= E``) and ``T`` to whole token tiles.  Compared as
floats compare: ``-0.0`` ties with ``0.0`` where ``top_k`` (the CPU's, the
tests' oracle) puts it below; no sigmoid or softmax makes one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import dispatch
from .common import params

#: tokens a program, the lanes of the turned tile.  On a v5e at ``[8192, E]``
#: (PR 61, a call inside a program that makes its operand): 128 / 256 / 512 /
#: 1,024 tokens took 0.116 / 0.097 / 0.096 / 0.096 ms at E = 512, k = 8 (the
#: sort 0.743) and 0.030 / 0.021 / 0.018 / 0.013 at E = 128, k = 6 (0.116); at
#: 1,024 a program's arrays at E = 512 are 2 MiB each.  Rows on sublanes
#: (two lane reductions a pass) measured 0.098-0.101 and 0.028-0.036
TOKENS = 512


def unsupported(experts, k, dtype):
    """Why ``select`` does not take a router's scores, or None."""
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        return f"dtype:{jnp.dtype(dtype).name}"
    if not 1 <= k <= experts:
        return f"k_not_in_1..{experts}:{k}"
    return None


@functools.partial(jax.jit, static_argnames=("k", "tt"))
def select(x, k, tt=None):
    """``idx [T, k]`` int32 with ``jax.lax.top_k(x, k)[1]``'s values for
    ``x [T, E]`` f32; ``tt``: the tokens a program (``TOKENS``, or fewer
    tokens' whole lane tiles)."""
    import jax.experimental.pallas as pl
    T, E = x.shape
    tt = tt or min(TOKENS, -(-T // 128) * 128)
    ep, tp, kp = -(-E // 128) * 128, -(-T // tt) * tt, -(-k // 8) * 8
    if (ep, tp) != (E, T):
        x = jnp.pad(x, ((0, tp - T), (0, ep - E)), constant_values=-jnp.inf)

    def kernel(x_ref, o_ref):
        x = x_ref[...].T                                       # [ep, tt]
        pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0).astype(
            jnp.float32)
        for j in range(k):
            m = jnp.max(x, axis=0, keepdims=True)
            c = jnp.where(x == m, pos, float(ep))
            i = jnp.min(c, axis=0, keepdims=True)
            o_ref[j:j + 1, :] = i.astype(jnp.int32)
            if j < k - 1:
                hit = c == i
                x = jnp.where(hit, -jnp.inf, x)
                pos = jnp.where(hit, float(ep), pos)

    out = pl.pallas_call(
        kernel, name="hetu_moe_select", grid=(tp // tt,),
        in_specs=[pl.BlockSpec((tt, ep), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((kp, tt), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((kp, tp), jnp.int32),
        compiler_params=params(dispatch.interpret(), ("parallel",)),
        interpret=dispatch.interpret(),
    )(x)
    return out[:k, :T].T
