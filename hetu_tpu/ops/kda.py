"""The delta rule with a decay of its own for every key channel (Kimi Delta
Attention, Kimi Linear, arXiv:2510.26692; the linear-attention layers of
Ling-3.0).

Per head, with a state ``S`` ``[d_k, d_v]`` that starts at zero, for each
position ``t``::

    S = Diag(exp(g_t)) S                (decay, g_t in R^d_k, g_t <= 0)
    u = beta_t (v_t - S^T k_t)          (what the state gets wrong about k_t)
    S = S + k_t u^T                     (the delta-rule write)
    o_t = S^T q_t

which is ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_(t-1) + beta_t k_t
v_t^T``.  At a ``g`` that is constant across a head's channels this is the
gated delta rule of ``ops/gated_delta.py``.

``recurrent_kda`` is that loop, one position at a time: the form the tests
and the benchmark's probe hold everything else to.

``chunk_kda`` is the same function in chunks of 64 positions, in the WY form
of ``ops/gated_delta.py``: with ``G_t`` the running sum of ``g`` inside a
chunk (a vector a position) and ``S_0`` the state the chunk starts from::

    (I + L) U = beta V - beta (K exp(G)) S_0,
    L[t, s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])   for s < t
    o_t = (q_t exp(G_t)) S_0 + sum_(s<=t) A[t, s] u_s,
    A[t, s] = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])
    S_C = Diag(exp(G_C)) S_0 + sum_s (k_s exp(G_C - G_s)) u_s^T

The decay cannot be pulled out of ``K K^T`` as one scalar a pair of
positions: a row of ``K`` is scaled by ``exp(G_t)`` and a column by
``exp(-G_s)``, channel by channel, and ``exp(-G_s)`` leaves f32 after a few
dozen positions.  So a chunk is cut into sub-chunks of ``SUB`` = 16
positions and every exponent is taken relative to a sum inside or at the
edge of a sub-chunk (``_pair_decay``): inside a sub-chunk both factors are
relative to the sum at its middle (each within ``exp(+-8 * 5)`` where ``g >=
-5``, well inside f32 even times a small ``k``: what the model's
``kda_safe_gate`` and ``kda_lower_bound`` are for; relative to its start the
row's factor fell to ``exp(-80)`` and a small ``k`` times it below f32's
least normal number), and between two sub-chunks all three factors (the
row's from its sub-chunk's start, the sub-chunks between, the column's to
its sub-chunk's end) are at most one.  **``g`` must be bounded below by about
-5 a position**; the recurrence needs no bound.

Precision as ``ops/gated_delta.py``: the state, the decays, the solve and
every product that reads or writes the state are f32 at the highest matmul
precision; the products that stay inside a chunk (the two ``[64, 64]``
matrices and ``P U``) take their (decayed) operands in the compute type and
add in f32.

Shapes: ``q, k [B, T, H, d_k]``, ``v [B, T, H, d_v]``, ``g [B, T, H, d_k]``
f32, ``beta [B, T, H]``; returns ``o [B, T, H, d_v]`` in ``v``'s type and the
final state ``[B, H, d_k, d_v]`` f32.

What runs where.  ``chunk_kda`` is what the benchmark's probe calls and what
the layer's ``hetu_kda_scan`` node (``layers/kda.py``) calls wherever the
kernels do not run.  On a TPU it runs as two Pallas kernels, ``hetu_kda_fwd``
and ``hetu_kda_bwd`` (``ops/pallas/kda.py``, a ``jax.custom_vjp``; the
backward keeps the chunk-start states and the chunks' triangular inverses
and rebuilds the rest), where it can
read that they apply: ``d_k`` and ``d_v`` multiples of 128, ``chunk`` 64, q,
k and v all bf16 or all f32, ``g`` f32.  Each call counts its choice at
trace time in ``hetu_kernel_choice_total{kernel="kda", impl, reason}``:
``pallas``, or ``jnp`` with ``head_dim_not_128_aligned``, ``chunk!=64``,
``dtype:<name>``, ``dtype:mixed`` or ``gate_dtype:<name>``.
``chunk_kda_in_place`` (PR 41) is the same kernel pair under the same rule
handed what the layer has: it reads the convolution's output ``[B, T, 3 H
d]`` and the ``f`` and ``z`` windows of the projection ``[B, T, 5 H d]`` in
place, takes the head's norms, its gate and the gated norm of its output on
the chunk in VMEM, and returns the ``[B, T, H d]`` that the output product
reads; it counts ``pallas`` once a call too, and returns None where
``chunk_kda`` would take the ``jax.numpy`` form (which then counts why).
Which of the two ran is in ``hetu_kda_entry_total{form}`` (``in_place`` /
``plain``).  What a mesh (which the scan node sees, ``ops/base.py
KernelOp``) and a platform without Mosaic mean is ``dispatch.take``'s rule;
``chunk_kda_jnp`` then runs.  The kernels themselves run anywhere when called
directly (interpret mode on the CPU): ``tests/test_kda.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: positions a chunk, as the gated delta rule's
CHUNK = 64
#: positions a sub-chunk: no exponent is taken over more than these
SUB = 16

_HI = jax.lax.Precision.HIGHEST


def recurrent_kda(q, k, v, g, beta, state_dtype=jnp.float32):
    """The recurrence, one position at a time.  ``state_dtype`` is the type
    the state is carried in between positions (f32; a lower type is what the
    tests' negative control and the benchmark's precision readings use)."""
    B, T, H, dk = q.shape
    f32 = jnp.float32

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x              # [B, H, ...]
        S = S.astype(f32) * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                                precision=_HI))
        S = S + k_t[..., :, None] * u[..., None, :]
        o_t = jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HI)
        return S.astype(state_dtype), o_t

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta))
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), state_dtype)
    S, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), S.astype(f32)


def chunk_kda(q, k, v, g, beta, chunk=CHUNK):
    """The chunked form; see the module's docstring: the Pallas kernel pair
    where ``dispatch.take`` and its rule allow, else the ``jax.numpy`` form."""
    from .pallas import dispatch, kda as kernels
    if dispatch.take("kda", None,
                     kernels.unsupported(q, k, v, g, chunk)):
        return kernels.kda(q, k, v, g, beta)
    return chunk_kda_jnp(q, k, v, g, beta, chunk)


def chunk_kda_in_place(mixed, proj, beta, a_log, dt_bias, scale, *, heads,
                       lower_bound, eps):
    """The whole mixer between its convolution and its output product where
    ``chunk_kda``'s kernels apply, else None (the caller then runs its
    ``jax.numpy`` form around ``chunk_kda``, which counts why): on a TPU,
    under the rule ``chunk_kda`` reads, from ``mixed [B, T, 3 H d]`` (``q~ |
    k~ | v``), ``proj [B, T, 5 H d]`` (``.. | f | z``), ``beta [B, T, H]``,
    ``a_log [H]``, ``dt_bias [H d]`` and the norm's ``scale [d]`` to the
    normalised, gated ``y [B, T, H d]`` (``ops/pallas/kda.py kda_in_place``:
    the windows read in place, no ``[B, T, H, d]`` view formed)."""
    from .pallas import dispatch, kda as kernels
    B, T, _ = mixed.shape
    d = mixed.shape[2] // (3 * heads)
    head = jax.ShapeDtypeStruct((B, T, heads, d), mixed.dtype)
    reason = kernels.unsupported(
        head, jax.ShapeDtypeStruct(head.shape, proj.dtype), head,
        jax.ShapeDtypeStruct(head.shape, jnp.float32), CHUNK)
    # a refusal is ``chunk_kda``'s to count, when the caller falls back on it
    if reason is not None or not dispatch.take("kda", None):
        return None
    rate = jnp.repeat(jnp.exp(a_log.astype(jnp.float32)), d)
    return kernels.kda_in_place(mixed, proj, beta, rate, dt_bias, scale,
                                lower_bound=lower_bound, eps=eps)


def _pair_decay(a, k, G, ct, sub=SUB):
    """``A[t, s] = sum_c a_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for ``s <= t``
    (whatever above the diagonal: the caller masks), ``a, k, G [.., C, d]``
    f32 with ``G`` the running sum of a non-positive ``g`` inside the chunk:
    ``[.., C, C]`` f32, no exponent over more than ``sub`` positions'
    worth."""
    f32 = jnp.float32
    C, d = G.shape[-2:]
    n = C // sub
    lead = G.shape[:-2]
    cut = lambda x: x.reshape(lead + (n, sub, d))
    a, k, G = cut(a), cut(k), cut(G)
    end = G[..., -1:, :]                            # a sub-chunk's last sum
    start = jnp.concatenate([jnp.zeros_like(end[..., :1, :, :]),
                             end[..., :-1, :, :]], axis=-3)
    rows = (a * jnp.exp(G - start)).astype(ct)      # from its start: <= 1
    cols = (k * jnp.exp(end - G)).astype(ct)        # on to its end: <= 1
    # inside a sub-chunk both factors relative to its MIDDLE sum: each within
    # exp(+-5 sub / 2), so neither a small k times the one leaves f32 below
    # nor the other above
    mid = G[..., sub // 2:sub // 2 + 1, :]
    diag = jnp.einsum("...nik,...njk->...nij",
                      (a * jnp.exp(G - mid)).astype(ct),
                      (k * jnp.exp(mid - G)).astype(ct),
                      preferred_element_type=f32)
    # whole sub-chunks between column block j's end and row block i's start
    i = jnp.arange(n)
    gap = start[..., :, None, 0, :] - end[..., None, :, 0, :]
    between = jnp.exp(jnp.where((i[:, None] > i[None, :])[..., None], gap,
                                -jnp.inf))          # [.., n, n, d]
    via = (rows.astype(f32)[..., :, :, None, :]
           * between[..., :, None, :, :]).astype(ct)    # [.., n, sub, n, d]
    off = jnp.einsum("...iajk,...jbk->...iajb", via, cols,
                     preferred_element_type=f32)
    same = (i[:, None] == i[None, :])[:, None, :, None]
    out = jnp.where(same, diag[..., :, :, None, :], off)
    return out.reshape(lead + (C, C))


def chunk_kda_jnp(q, k, v, g, beta, chunk=CHUNK):
    """The chunked form in ``jax.numpy``: what the kernels are held to, and
    what runs wherever they do not."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    f32, ct = jnp.float32, v.dtype
    C = chunk
    assert C % SUB == 0, (C, SUB)
    pad = -T % C
    if pad:
        # positions of padding write nothing (beta 0), decay nothing (g 0)
        # and their outputs are cut off
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) *
                                    (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    N = (T + pad) // C

    def chunks(x):                      # [B, T, H, ...] -> [B, H, N, C, ...]
        x = x.reshape((B, N, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v = (chunks(x).astype(f32) for x in (q, k, v))
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    G = jnp.cumsum(g, axis=-2)                           # [B, H, N, C, dk]
    i = jnp.arange(C)
    lower = i[:, None] >= i[None, :]
    L = jnp.where(i[:, None] > i[None, :],
                  _pair_decay(k, k, G, ct) * beta[..., None], 0.0)
    eG = jnp.exp(G)
    rhs = jnp.concatenate([v * beta[..., None],
                           k * eG * beta[..., None]], axis=-1)
    # (I + L) [V' | W] = rhs: the diagonal of a unit triangular solve is
    # not read, so L stands for I + L
    sol = jax.lax.linalg.triangular_solve(
        L, rhs, left_side=True, lower=True, unit_diagonal=True)
    v_prime, w = sol[..., :dv], sol[..., dv:]
    G_end = G[..., -1:, :]                               # [B, H, N, 1, dk]
    k_end = k * jnp.exp(G_end - G)
    a_end = jnp.exp(G_end[..., 0, :])                    # [B, H, N, dk]

    def walk(S, x):
        """From the state a chunk starts at to the next chunk's."""
        w_n, vp_n, ke_n, a_n = x
        u = vp_n - jnp.matmul(w_n, S, precision=_HI)     # [B, H, C, dv]
        S_next = S * a_n[..., None] + jnp.einsum(
            "bhck,bhcv->bhkv", ke_n, u, precision=_HI)
        return S_next, (S, u)

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w, v_prime, k_end, a_end))
    S_last, (S_start, u) = jax.lax.scan(
        walk, jnp.zeros((B, H, dk, dv), f32), xs)
    S_start, u = jnp.moveaxis(S_start, 0, 2), jnp.moveaxis(u, 0, 2)
    p = jnp.where(lower, _pair_decay(q, k, G, ct), 0.0).astype(ct)
    o = (jnp.matmul(q * eG, S_start, precision=_HI)
         + jnp.einsum("bhncs,bhnsv->bhncv", p, u.astype(ct),
                      preferred_element_type=f32))
    o = jnp.moveaxis(o, 1, 3).reshape(B, N * C, H, dv)[:, :T]
    return o.astype(ct), S_last
