"""The gated delta rule (Gated DeltaNet, Yang et al. 2024, arXiv:2412.06464;
the linear-attention layers of Qwen3-Next).

Per value head, with a state ``S`` ``[d_k, d_v]`` that starts at zero, for
each position ``t``::

    S = exp(g_t) S                      (decay, g_t <= 0)
    u = beta_t (v_t - S^T k_t)          (what the state gets wrong about k_t)
    S = S + k_t u^T                     (the delta-rule write)
    o_t = S^T q_t

``recurrent_gated_delta_rule`` is that loop, one position at a time: the
form the tests hold the chunked one to, and what a decode step will run.

``chunk_gated_delta_rule`` is the same function in chunks of ``chunk``
positions (the WY form of the paper's section 3.2, as the reference
implementations of flash-linear-attention and HF ``modeling_qwen3_next``
cut it).  With ``G_t`` the running sum of ``g`` inside a chunk and ``S_0`` the
state the chunk starts from, the chunk's writes solve one unit lower
triangular system::

    (I + L) U = beta V - (beta K exp(G)) S_0,
    L[t, s] = beta_t (k_t . k_s) exp(G_t - G_s)  for s < t

so ``U = V' - W S_0`` with ``V' = T beta V``, ``W = T (beta K exp(G))``,
``T = (I + L)^-1``; then ``o_t = exp(G_t) S_0^T q_t + sum_{s<=t} exp(G_t -
G_s) (k_s . q_t) u_s`` and ``S_C = exp(G_C) S_0 + sum_s exp(G_C - G_s) k_s
u_s^T``.  Everything but the walk from chunk state to chunk state is
products inside a chunk, batched over all chunks; the walk is a
``lax.scan`` of two products a chunk.  ``V'`` and ``W`` come from one
triangular solve with ``[beta V | beta K exp(G)]`` on the right
(``lax.linalg.triangular_solve``: substitution, exact where a Neumann
series of ``L`` would cancel large terms; on a v5e 4.4 ms forward for the
4,096 systems of a layer where ``T`` formed by twelve masked ``[64, 64]``
products took 14.2, PR 31).

Precision.  The state, the decays, the solve and every product that reads
or writes the state are f32 at the highest matmul precision whatever the
compute type; the three products that stay inside a chunk (``K K^T``,
``Q K^T``, ``P U``) take their operands in the compute type and add in f32.
The kernels hold to the same: an f32 operand of theirs enters the matrix
unit as its three bf16 parts (six bf16 passes a product of two, what
``HIGHEST`` is on a TPU), beside bf16 q, k, v as beside f32 ones.
The backward pass of the ``jax.numpy`` form is JAX's own through the solve
and the scan (the chunk states are its residuals: ``d_k x d_v`` f32 a chunk
and head).

Shapes: ``q, k [B, T, H, d_k]``, ``v [B, T, H, d_v]``, ``g, beta [B, T, H]``
(``H`` value heads; q and k already repeated for them, normalised and
scaled by the caller); returns ``o [B, T, H, d_v]`` in ``v``'s type and
the final state ``[B, H, d_k, d_v]`` f32.  ``chunk_gated_delta_rule_in_place``
takes the layer's arrays instead: ``mixed [B, T, 2 key_dim + value_dim]``
(``q~ | k~ | v`` as the convolution wrote them, ``H / rep`` key heads) and
``g, beta``; returns ``o [B, T, H d_v]``, or None.

What runs where.  The layer's ``hetu_gdn_scan`` node
(``layers/gated_delta_net.py``) asks ``chunk_gated_delta_rule_in_place``
first (PR 69): on a TPU, outside a mesh, under the rule below, where a
program's four value heads are whole key heads' (``gcd(H, 4) % rep == 0``,
else ``key_head_split_across_programs``) and v's window starts at a block
of a program's v (else ``value_window_not_block_aligned``), the kernel pair
reads ``mixed`` in place, a key head once for its value heads, and takes the
L2 norms on the chunk in VMEM; what stands between the convolution's output
and the gated norm is then the gates (``[B, T, H]``, XLA's), the two kernels
and one concatenation of ``d mixed``.  Where it returns None the node runs
its ``jax.numpy`` prologue around ``chunk_gated_delta_rule``, which is also
what the benchmark's long-memory probe calls
(``chipbench/builders/qwen3_next.py`` ``delta_rule_gap``).  Either way a
recomputed group keeps what the forward kernel wrote (``ops/pallas/dispatch.py
KEPT["gdn"]``): the output and, f32 a chunk and head, the chunk-start state
and the chunk's inverse, ``B T H (d_v itemsize + (d_k d_v + 64 x 64) / 16)``
bytes a call (466 MB a layer of the Qwen3-Next cell as HBM tiles them).  On
a TPU ``chunk_gated_delta_rule`` runs as two Pallas kernels,
``hetu_gdn_fwd`` and ``hetu_gdn_bwd`` (``ops/pallas/gated_delta.py``, a
``jax.custom_vjp``: one walk over chunk states in VMEM each way; the
backward keeps the chunk-start states and the chunks' triangular inverses
and rebuilds everything else), where
it can read that they apply: ``d_k`` and ``d_v`` multiples of 128, ``chunk``
64, q, k and v all bf16 or all f32; any ``T``, ``B`` and ``H``.  Each call
counts its choice at trace time in ``hetu_kernel_choice_total{kernel=
"gated_delta", impl, reason}``: ``pallas``, or ``jnp`` with
``head_dim_not_128_aligned``, ``chunk!=64``, ``dtype:<name>`` or
``dtype:mixed``; the in-place entry counts ``pallas`` where it engages and
``jnp`` with one of its own two reasons where it alone refuses (the prologue
is then ``jax.numpy`` and the plain entry counts its ``pallas`` beside it).  What a mesh (which the scan node sees, ``ops/base.py
KernelOp``) and a platform without Mosaic mean is ``dispatch.take``'s rule;
``chunk_gated_delta_rule_jnp`` then runs, bit for bit what this function was
before it had kernels.  The kernels themselves run anywhere when called
directly (interpret mode on the CPU): ``tests/test_gated_delta_kernel.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: positions a chunk: the [chunk, chunk] matrices fill half an MXU tile and
#: the scan is T / 64 steps long
CHUNK = 64

_HI = jax.lax.Precision.HIGHEST


def recurrent_gated_delta_rule(q, k, v, g, beta, state_dtype=jnp.float32):
    """The recurrence, one position at a time.  ``state_dtype`` is the type
    the state is carried in between positions (f32; a lower type is what the
    tests' negative control and the benchmark's precision readings use)."""
    B, T, H, dk = q.shape
    f32 = jnp.float32

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x              # [B, H, ...]
        S = S.astype(f32) * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                                precision=_HI))
        S = S + k_t[..., :, None] * u[..., None, :]
        o_t = jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=_HI)
        return S.astype(state_dtype), o_t

    xs = tuple(jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta))
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), state_dtype)
    S, o = jax.lax.scan(step, S0, xs)
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), S.astype(f32)


def chunk_gated_delta_rule(q, k, v, g, beta, chunk=CHUNK):
    """The chunked form; see the module's docstring: the Pallas kernel pair
    where ``dispatch.take`` and its rule allow, else the ``jax.numpy`` form."""
    from .pallas import dispatch, gated_delta as kernels
    if dispatch.take("gated_delta", None,
                     kernels.unsupported(q, k, v, chunk)):
        return kernels.gated_delta_rule(q, k, v, g, beta)
    return chunk_gated_delta_rule_jnp(q, k, v, g, beta, chunk)


def chunk_gated_delta_rule_in_place(mixed, g, beta, *, key_heads, dk, dv,
                                    rep):
    """The rule from the convolution's output where its kernels read that in
    place, else None (the caller then runs its ``jax.numpy`` prologue around
    ``chunk_gated_delta_rule``): on a TPU, under the rule
    ``chunk_gated_delta_rule`` reads, where a program's value heads are whole
    key heads' and v's window starts at a block, from ``mixed [B, T, 2
    key_dim + value_dim]`` (``q~ | k~ | v``) and ``g, beta [B, T, value
    heads]`` to ``o [B, T, value heads x dv]`` (``ops/pallas/gated_delta.py
    gated_delta_rule_in_place``: a key head read once for its ``rep`` value
    heads, its L2 norms taken on the chunk in VMEM)."""
    from .pallas import dispatch, gated_delta as kernels
    B, T, _ = mixed.shape
    head = lambda d: jax.ShapeDtypeStruct((B, T, key_heads * rep, d),
                                          mixed.dtype)
    # that refusal is ``chunk_gated_delta_rule``'s to count, when the caller
    # falls back on it; this entry's own is counted here
    if kernels.unsupported(head(dk), head(dk), head(dv), CHUNK) is not None:
        return None
    if not dispatch.take("gated_delta", None,
                         kernels.in_place_unsupported(key_heads, dk, dv,
                                                      rep)):
        return None
    return kernels.gated_delta_rule_in_place(mixed, g, beta, dk=dk, dv=dv,
                                             rep=rep)


def chunk_gated_delta_rule_jnp(q, k, v, g, beta, chunk=CHUNK):
    """The chunked form in ``jax.numpy``: what the kernels are held to, and
    what runs wherever they do not (a scan node under a mesh calls it
    itself, ``layers/gated_delta_net.py``)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    f32, ct = jnp.float32, v.dtype
    C = chunk
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

    q, k, v = chunks(q), chunks(k), chunks(v)
    g, beta = chunks(g.astype(f32)), chunks(beta.astype(f32))
    G = jnp.cumsum(g, axis=-1)                           # [B, H, N, C]
    i = jnp.arange(C)
    lower = i[:, None] >= i[None, :]
    # exp(G_t - G_s) for s <= t; the difference is masked before the exp,
    # above the diagonal it is positive and may overflow
    decay = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :],
                              -jnp.inf))
    kb = (k.astype(f32) * beta[..., None]).astype(ct)
    kk = jnp.einsum("bhnck,bhnsk->bhncs", kb, k, preferred_element_type=f32)
    L = jnp.where(i[:, None] > i[None, :], kk * decay, 0.0)
    eG = jnp.exp(G)[..., None]
    rhs = jnp.concatenate(
        [v.astype(f32) * beta[..., None],
         k.astype(f32) * (beta[..., None] * eG)], axis=-1)
    # (I + L) [V' | W] = rhs: the diagonal of a unit triangular solve is
    # not read, so L stands for I + L
    sol = jax.lax.linalg.triangular_solve(
        L, rhs, left_side=True, lower=True, unit_diagonal=True)
    v_prime, w = sol[..., :dv], sol[..., dv:]            # V', W
    G_end = G[..., -1:]                                  # [B, H, N, 1]
    k_end = k.astype(f32) * jnp.exp(G_end - G)[..., None]
    a_end = jnp.exp(G_end[..., 0])                       # [B, H, N]

    def walk(S, x):
        """From the state a chunk starts at to the next chunk's."""
        w_n, vp_n, ke_n, a_n = x
        u = vp_n - jnp.matmul(w_n, S, precision=_HI)     # [B, H, C, dv]
        S_next = S * a_n[..., None, None] + jnp.einsum(
            "bhck,bhcv->bhkv", ke_n, u, precision=_HI)
        return S_next, (S, u)

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (w, v_prime, k_end, a_end))
    S_last, (S_start, u) = jax.lax.scan(
        walk, jnp.zeros((B, H, dk, dv), f32), xs)
    S_start, u = jnp.moveaxis(S_start, 0, 2), jnp.moveaxis(u, 0, 2)
    qk = jnp.einsum("bhnck,bhnsk->bhncs", q, k, preferred_element_type=f32)
    p = jnp.where(lower, qk * decay, 0.0).astype(ct)
    o = (jnp.matmul(q.astype(f32) * eG, S_start, precision=_HI)
         + jnp.einsum("bhncs,bhnsv->bhncv", p, u.astype(ct),
                      preferred_element_type=f32))
    o = jnp.moveaxis(o, 1, 3).reshape(B, N * C, H, dv)[:, :T]
    return o.astype(ct), S_last
