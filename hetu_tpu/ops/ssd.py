"""The selective state-space recurrence of Mamba-2 (Dao and Gu 2024,
arXiv:2405.21060; the ``M`` blocks of Nemotron-H and the ``mamba`` layers of
Granite 4.0-H), in its state-space dual form over chunks.

Per head, with a state ``S`` ``[P, N]`` (``P`` the head's channels, ``N`` the
state size) that starts at zero, for each position ``t``::

    S = exp(dt_t A) S + dt_t x_t B_t^T      (A < 0 a scalar a head, dt_t > 0)
    y_t = S C_t

``B`` and ``C`` are shared by the heads of a group (``G`` groups, head ``h``
reads group ``h // (H / G)``; Nemotron-H has 8 groups of 8 heads, Granite
4.0-H ONE group of all 64).  The skip ``D x_t``, the gate and the norm
are the layer's (``layers/mamba2.py``).

``recurrent_ssd`` is that loop, one position at a time: the form the tests
hold the chunked one to, and what a decode step will run.

``chunk_ssd`` is the same function in chunks of ``chunk`` positions
(section 6 of the paper, as ``ssd_minimal`` and HF ``modeling_nemotron_h``
cut it; ``chunk_ssd_jnp`` writes it out).  With ``a_t = dt_t A`` and
``seg[t, s] = a_(s+1) + .. + a_t`` (``s <= t``, inside a chunk):

1. inside a chunk, ``y_t += sum_(s<=t) exp(seg[t, s]) (C_t . B_s) dt_s x_s``:
   ``(C B^T * L)(dt x)`` with ``L = exp(seg)``;
2. a chunk's own end state, ``sum_s exp(seg[last, s]) dt_s x_s B_s^T``;
3. the walk from chunk state to chunk state, ``S_(n+1) = exp(sum of the
   chunk's a) S_n + (2)``: a ``lax.scan`` of one multiply-add a chunk;
4. ``y_t += exp(a_first + .. + a_t) S_n C_t``, what the chunk's start state
   still gives at ``t``.

``seg`` is built from a masked cumulative sum of ``a`` laid out ``[chunk,
chunk]`` (every entry a sum of non-positive terms), not from differences of
one running sum, which cancel where the running sum is large.  (``jnp.cumsum``
over the ``[chunk, chunk]`` layout, which XLA fuses with the mask and the
``exp``: 14.8 ms forward and backward for a mixer of the Nemotron-H cell on a
v5e, where a product with a triangular matrix of ones at the highest
precision took 20.6 and read the same, PR 33.  The sums from a chunk's start,
``a_0 + .. + a_t``, are that matrix's first column plus ``a_0``.)

Precision.  ``dt``, ``A``, every decay and the state from chunk to chunk are
f32 whatever the compute type.  The four products take their operands in
the compute type (``x``'s) and add in f32; with f32 operands they run at the
highest matmul precision.  The backward pass of the ``jax.numpy`` form is
JAX's own through the products and the scan.

Shapes: ``x [b, T, H, P]``, ``dt [b, T, H]`` (after its softplus), ``A
[H]``, ``B, C [b, T, G, N]``; returns ``y [b, T, H, P]`` in ``x``'s type and
the last state ``[b, H, P, N]`` f32.

What runs where.  ``chunk_ssd`` is what the benchmark's long-memory probe
calls (``chipbench/builders/nemotron_h.py`` ``ssd_state_gap``) and what the
layer's ``hetu_ssm_scan`` node (``layers/mamba2.py``) calls on its slices of
``xBC`` where ``chunk_ssd_in_place`` hands it None.  That function is the
node's first choice: the same kernel pair's second entry
(``ops/pallas/ssd.py ssd_in_place``), which reads ``x | B | C`` where the
convolution wrote them and adds the skip ``D x`` on the chunk in VMEM, under
``chunk_ssd``'s rule and two conditions more, read from the shapes (``H P`` a
multiple of ``N``, ``T`` whole blocks of 8 x 128 positions, or fewer chunks
in one program); it counts the one choice of a call that it takes
(``pallas``) and nothing of one it does not, and which entry ran is in
``hetu_ssd_form_total{form}`` (``plain`` / ``in_place``).  On a TPU
``chunk_ssd`` runs as two Pallas kernels, ``hetu_ssd_fwd`` and ``hetu_ssd_bwd``
(``ops/pallas/ssd.py``, a ``jax.custom_vjp``: one walk over chunk states in
VMEM each way, up to eight of a group's heads a program and a wider group
as blocks of heads on the grid, each reading the group's ``B`` and ``C`` in
place, the group's ``dB`` and ``dC`` summed over its blocks; the decays built
in VMEM from ``dt`` and ``A``; the backward keeps the chunk-start states and
rebuilds everything else), where it can read that they apply: ``P`` a
multiple of 64 with some block of up to eight of a group's ``H / G`` heads
filling whole 128-lane tiles, ``N`` a multiple of 128, ``chunk`` 128, ``x``,
``B`` and ``C`` all bf16 or all f32, a program's blocks within half the
kernels' VMEM; any ``b``, ``T``, ``H`` and ``G``.  Each call counts its choice
at trace time in ``hetu_kernel_choice_total{kernel="ssd", impl, reason}``:
``pallas``, or ``jnp`` with ``head_dim_not_64_aligned``,
``state_not_128_aligned``, ``chunk!=128``, ``dtype:<name>``, ``dtype:mixed`` or
``blocks_over_vmem``.  What a mesh (which the scan node sees, ``ops/base.py
KernelOp``) and a platform without Mosaic mean is ``dispatch.take``'s rule;
``chunk_ssd_jnp`` then runs, bit for bit what this function was before it had
kernels.  The kernels themselves run anywhere when called directly
(interpret mode on the CPU): ``tests/test_ssd_kernel.py``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: positions a chunk (Nemotron-H's ``chunk_size``)
CHUNK = 128

_HI = jax.lax.Precision.HIGHEST


def recurrent_ssd(x, dt, A, B, C, state_dtype=jnp.float32):
    """The recurrence, one position at a time.  ``state_dtype`` is the type
    the state is carried in between positions (f32; a lower type is what the
    tests' negative control uses)."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    f32 = jnp.float32
    rep = H // G

    def step(S, t):
        x_t, dt_t, B_t, C_t = t                    # [b, H, ..]
        S = S.astype(f32) * jnp.exp(dt_t * A)[..., None, None]
        S = S + (dt_t[..., None] * x_t)[..., :, None] * B_t[..., None, :]
        y_t = jnp.einsum("bhpn,bhn->bhp", S, C_t, precision=_HI)
        return S.astype(state_dtype), y_t

    def heads(t):                                  # a group's B, C a head
        return jnp.repeat(t.astype(f32), rep, axis=2)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (
        x.astype(f32), dt.astype(f32), heads(B), heads(C)))
    S, y = jax.lax.scan(step, jnp.zeros((b, H, P, N), state_dtype), xs)
    return jnp.moveaxis(y, 0, 1).astype(x.dtype), S.astype(f32)


def segsum(a):
    """``seg[.., t, s] = a[.., s + 1] + .. + a[.., t]`` for ``s <= t`` and
    ``-inf`` above the diagonal, from a cumulative sum over ``t`` of ``a``
    masked to ``s < t``."""
    L = a.shape[-1]
    i = jnp.arange(L)
    below = i[:, None] > i[None, :]
    seg = jnp.cumsum(jnp.where(below, a[..., :, None], 0.0), axis=-2)
    return jnp.where(i[:, None] >= i[None, :], seg, -jnp.inf)


def chunk_ssd(x, dt, A, B, C, chunk=CHUNK):
    """The chunked form; see the module's docstring: the Pallas kernel pair
    where ``dispatch.take`` and its rule allow, else the ``jax.numpy`` form."""
    from .pallas import dispatch, ssd as kernels
    if dispatch.take("ssd", None, kernels.unsupported(x, B, C, chunk)):
        return kernels.ssd(x, dt, A, B, C)
    return chunk_ssd_jnp(x, dt, A, B, C, chunk)


def chunk_ssd_in_place(xbc, dt, A, D, *, heads, head_dim, groups, state,
                       chunk=CHUNK):
    """The scan with its skip from the convolution's output where its kernels
    read that in place, else None (the caller then slices ``x``, ``B``, ``C``
    out and adds the skip around ``chunk_ssd``): on a TPU, under the rule
    ``chunk_ssd`` reads, where the three windows start at whole blocks and
    nothing is padded, from ``xbc [b, T, H P + 2 G N]`` (``x | B | C``), ``dt
    [b, T, H]`` and ``A, D [H]`` to ``y [b, T, H P]`` with ``D x`` in it
    (``ops/pallas/ssd.py ssd_in_place``).  One choice a call: this entry
    counts ``pallas`` where it runs and nothing where it does not (the
    slicing form's ``chunk_ssd`` then counts its own)."""
    from .pallas import dispatch, ssd as kernels
    b, T, _ = xbc.shape
    x = jax.ShapeDtypeStruct((b, T, heads, head_dim), xbc.dtype)
    bc = jax.ShapeDtypeStruct((b, T, groups, state), xbc.dtype)
    if kernels.unsupported(x, bc, bc, chunk) is not None:
        return None
    if kernels.in_place_unsupported(T, heads * head_dim, state) is not None:
        return None
    if not dispatch.take("ssd", None):
        return None
    return kernels.ssd_in_place(xbc, dt, A, D, heads=heads,
                                head_dim=head_dim, groups=groups, state=state)


def chunk_ssd_jnp(x, dt, A, B, C, chunk=CHUNK):
    """The chunked form in ``jax.numpy``: what the kernels are held to, and
    what runs wherever they do not (a scan node under a mesh calls it
    itself, ``layers/mamba2.py``)."""
    b, T, H, P = x.shape
    G, N = B.shape[2:]
    R = H // G
    f32, ct = jnp.float32, x.dtype
    full = _HI if ct == f32 else None
    L = chunk
    pad = -T % L
    if pad:
        # a position of padding writes nothing and decays nothing (dt 0)
        x, dt, B, C = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) *
                               (t.ndim - 2)) for t in (x, dt, B, C))
    n = (T + pad) // L
    dt = dt.astype(f32)
    # [b, n, G, R, L]: a head's positions of a chunk along the lanes
    dt_c = jnp.moveaxis(dt.reshape(b, n, L, G, R), 2, 4)
    a = dt_c * A.astype(f32).reshape(G, R)[:, :, None]
    seg = segsum(a)
    decay = jnp.exp(seg)                           # [b, n, G, R, L, L]
    x_c = x.reshape(b, n, L, G, R, P)
    B_c, C_c = (t.astype(ct).reshape(b, n, L, G, N) for t in (B, C))
    xdt = (x_c.astype(f32) * jnp.moveaxis(dt_c, 4, 2)[..., None])

    # 1. inside a chunk
    cb = jnp.einsum("bnlgs,bnmgs->bnglm", C_c, B_c, precision=full,
                    preferred_element_type=f32)
    scores = (cb[:, :, :, None] * decay).astype(ct)
    y = jnp.einsum("bngrlm,bnmgrp->bnlgrp", scores, xdt.astype(ct),
                   precision=full, preferred_element_type=f32)

    # 2. each chunk's own end state
    to_end = jnp.moveaxis(decay[..., -1, :], 4, 2)         # [b, n, L, G, R]
    own = jnp.einsum("bnlgrp,bnlgs->bngrps",
                     (xdt * to_end[..., None]).astype(ct), B_c,
                     precision=full, preferred_element_type=f32)

    # 3. the walk over chunk states, f32
    a_sum = seg[..., 0] + a[..., :1]        # a_0 + .. + a_t: [b, n, G, R, L]
    through = jnp.exp(a_sum[..., -1])                      # [b, n, G, R]

    def walk(S, t):
        own_n, through_n = t
        return S * through_n[..., None, None] + own_n, S
    S_last, S_start = jax.lax.scan(
        walk, jnp.zeros((b, G, R, P, N), f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(through, 1, 0)))
    S_start = jnp.moveaxis(S_start, 0, 1)                  # [b, n, G, R, P, N]

    # 4. what the chunk's start state gives at each position
    from_start = jnp.moveaxis(jnp.exp(a_sum), 4, 2)        # [b, n, L, G, R]
    y = y + jnp.einsum("bnlgs,bngrps->bnlgrp", C_c, S_start.astype(ct),
                       precision=full, preferred_element_type=f32
                       ) * from_start[..., None]
    y = y.reshape(b, n * L, H, P)[:, :T]
    return y.astype(ct), S_last.reshape(b, H, P, N)
