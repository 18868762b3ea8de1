"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
arXiv:2302.04542) as EvaByte runs it: exact softmax terms for the keys of a
query's own aligned window, one summary a chunk for everything before the
window, ONE normaliser over both.

Chunk ``j`` of ``c`` keys of head ``h`` is summarised by two learned vectors
``phi_h``, ``mu_h`` (``chunk_summaries``)::

    alpha_m = softmax over the chunk's m of (scale phi_h . k_m)
    v^_j = sum_m alpha_m v_m          k^_j = mean_m k_m + mu_h

and the query at ``t`` of window ``w = t // W`` attends to the keys ``W w ..
t`` and to the summaries of the chunks ``j < (W / c) w`` (``eva_attention``)::

    o_t = softmax over both of (scale q_t . [k_m | k^_j]) @ [v_m | v^_j]

Two forms behind one entry, as every kernel family: on a TPU the flash
kernels' third static plan (``ops/pallas/flash_attention.py``, ``hetu_eva_fwd``
/ ``hetu_eva_bwd``: no pair outside a query's sets is formed), elsewhere and
where ``unsupported`` refuses the ``jax.numpy`` form under a dense ``[S, S +
S / c]`` mask.  ``dispatch.take`` counts the choice under ``eva``.  The
summaries are ``jax.numpy`` everywhere: a reduction over a chunk's 16 rows,
f32 inside whatever the operands' type, on the projections' ``[B, S, H d]``
as they lie.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import simple_op
from .pallas.common import spread, widen
from .rotary import pair_item_op

#: below this the dense form is cheaper than the kernels' set-up, as
#: ``ops/attention.py _FLASH_MIN_SEQ``
_MIN_SEQ = 256


@jax.custom_vjp
def _weigh(alpha, v):
    """``alpha [B, S, H]`` f32 on the lanes of its head times ``v [B, S, H d]``,
    f32: the weights spread EXACTLY by a product with 0 and 1 on the matrix
    unit (``ops/pallas/common.py widen``), because the other way, ``alpha[..., None]`` on an ``[.., H, d]`` view, is a pass over HBM
    that re-tiles ``v`` (heads to sublanes): 2.7% of the EvaByte cell's step."""
    return widen(alpha, v.shape[-1] // alpha.shape[-1]) * v.astype(jnp.float32)


def _weigh_fwd(alpha, v):
    return _weigh(alpha, v), (alpha, v)


def _weigh_bwd(kept, g):
    alpha, v = kept
    heads, d = alpha.shape[-1], v.shape[-1] // alpha.shape[-1]
    # a head's lanes of g * v added up: the spread's transpose, on an f32
    # operand and d terms a sum, so at the highest precision
    d_alpha = jnp.matmul(g * v.astype(jnp.float32),
                         spread(heads, d, dtype=jnp.float32).T,
                         precision=jax.lax.Precision.HIGHEST)
    # (the f32 product, not ``widen``: 1.0 ms a step less in the EvaByte cell)
    wide = jnp.matmul(alpha, spread(heads, d, dtype=jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)
    return d_alpha, (g * wide).astype(v.dtype)


_weigh.defvjp(_weigh_fwd, _weigh_bwd)


def summarised(seq, window):
    """The positions whose chunks some query reads through summaries: every
    window but the last."""
    return (seq - 1) // window * window


def chunk_summaries(k, v, phi, mu, *, chunk, scale=None, upto=None):
    """``(k^, v^) [B, upto // chunk, H d]`` of the first ``upto`` positions
    (all whole chunks where None; a layer hands ``summarised(S, window)``: the
    last window's chunks are never read) of ``k``, ``v [B, S, H d]`` under
    ``phi``, ``mu [H, d]``: a chunk's keys averaged plus ``mu``, its values
    under the softmax over the chunk of ``scale phi . k`` (``scale``: ``d **
    -0.5``).  Scores, softmax and both sums in f32.  Everything stays on ``[B, S, H d]`` as it lies
    (rows by chunks is a free view): a head's score is a product with ``phi``
    on its own lanes, a head's weight reaches its lanes through ``_weigh``."""
    b, s, width = k.shape
    heads, d = phi.shape
    n = (s if upto is None else upto) // chunk
    scale = d ** -0.5 if scale is None else scale
    k, v = k[:, :n * chunk], v[:, :n * chunk]
    # [H d, H]: phi_h on the lanes of head h in column h, zeros elsewhere
    onto_heads = (spread(heads, d, dtype=k.dtype)
                  * phi.reshape(1, width).astype(k.dtype)).T
    scores = jnp.matmul(k, onto_heads,
                        preferred_element_type=jnp.float32) * scale
    alpha = jax.nn.softmax(scores.reshape(b, n, chunk, heads), axis=2)

    def by_chunk(x):
        return x.reshape(b, n, chunk, width)
    vs = jnp.sum(by_chunk(_weigh(alpha.reshape(b, n * chunk, heads), v)),
                 axis=2)
    ks = (jnp.mean(by_chunk(k.astype(jnp.float32)), axis=2)
          + mu.reshape(width).astype(jnp.float32))
    return ks.astype(k.dtype), vs.astype(k.dtype)


def eva_mask(seq, window, chunk, summaries=None):
    """``[S, S + summaries]`` bool (``summaries``: ``S // chunk`` where
    None), query on key and then on summary: the keys of the query's own
    window up to itself, the summaries of the chunks of every window before
    it."""
    t = jnp.arange(seq)[:, None]
    count = seq // chunk if summaries is None else summaries
    m, j = jnp.arange(seq)[None, :], jnp.arange(count)[None, :]
    first = t // window * window
    return jnp.concatenate([(m >= first) & (m <= t), j * chunk < first],
                           axis=1)


def dense_eva_attention(q, k, v, ks, vs, *, window, chunk, num_heads,
                        scale=None):
    """The ``jax.numpy`` form on ``[B, S, H d]``: scores against the keys and
    the summaries side by side under ``eva_mask``, one softmax in f32."""
    b, s, _ = q.shape
    q, k, v, ks, vs = (
        x.reshape(b, x.shape[1], num_heads, x.shape[-1] // num_heads)
        for x in (q, k, v, ks, vs))       # (a single window has no summary)
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.concatenate([k, ks], 1),
                        preferred_element_type=jnp.float32) * scale
    probs = jax.nn.softmax(
        jnp.where(eva_mask(s, window, chunk, ks.shape[1]), scores, -1e9),
        axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype),
                     jnp.concatenate([v, vs], 1),
                     preferred_element_type=jnp.float32).astype(v.dtype)
    return out.reshape(b, s, -1)


def unsupported(q, k, v, num_heads, window, chunk):
    """Why the kernels do not take ``[B, S, H d]`` operands under ``eva =
    (window, chunk)``, or None: ``flash_attention.unsupported``'s reasons and
    the attention op's (a short sequence, heads that are not lane-aligned
    groups)."""
    from .pallas import flash_attention as fa
    if not fa.heads_per_program(num_heads, q.shape[-1] // num_heads):
        return "heads_not_lane_aligned"
    why = fa.unsupported(*fa.heads_views(q, k, v, num_heads),
                         eva=(window, chunk))
    if why is None and q.shape[1] < _MIN_SEQ:
        why = f"seq<{_MIN_SEQ}"
    return why


def eva_attention(q, k, v, ks, vs, *, window, chunk, num_heads, scale=None,
                  mesh=None):
    """EVA attention of ``q, k, v [B, S, H d]`` and the summaries ``ks, vs``
    (of at least the ``summarised(S, window)`` first positions) -> ``[B, S, H
    d]``; the kernels where ``dispatch.take``
    says so (label ``eva``; under a mesh never: the plan has no per-shard
    form), else ``dense_eva_attention``."""
    from .pallas import dispatch
    from .pallas.flash_attention import flash_attention
    if dispatch.take("eva", mesh,
                     unsupported(q, k, v, num_heads, window, chunk)):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               num_heads=num_heads, eva=(window, chunk),
                               summaries=(ks, vs))
    return dense_eva_attention(q, k, v, ks, vs, window=window, chunk=chunk,
                               num_heads=num_heads, scale=scale)


_summaries_op = simple_op(chunk_summaries, "eva_chunk_summaries")


def chunk_summaries_op(k, v, phi, mu, *, chunk, scale=None, upto=None):
    """The nodes of ``k^`` and ``v^``, both from one node."""
    pair = _summaries_op(k, v, phi, mu, chunk=chunk, scale=scale, upto=upto)
    return pair_item_op(pair, index=0), pair_item_op(pair, index=1)
