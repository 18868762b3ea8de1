"""Context/sequence parallelism for long sequences.

The reference has NO ring attention / Ulysses / blockwise CP (SURVEY.md §5:
verified absent; only Megatron-style SP in Galvatron).  These are designed
fresh for TPU:

* **Ring attention** (`ring_attention`): sequence sharded over a 'cp' mesh
  axis; Q stays local while K/V blocks rotate around the ICI ring via
  `ppermute`, combined with online-softmax accumulation (flash-attention
  style m/l/o running stats).  Communication fully overlaps compute on TPU
  since XLA schedules the ppermute DMA concurrently with the matmuls.
* **Ulysses attention** (`ulysses_attention`): all_to_all head↔sequence
  resharding — attention itself stays local per device but over all tokens
  of a subset of heads (DeepSpeed-Ulysses scheme), one a2a before and after.
* **Megatron-SP** is subsumed by GSPMD: annotating activations
  P('dp', 'tp', None) around LN/dropout gives the scatter/gather pairs
  (tools/Hetu-Galvatron .../transformer.py sequence_parallel flag) without
  explicit code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from .collectives import varying


def _block_attend(q, k, v, m, l, o, q_off, k_off, scale, causal):
    """One flash block: update running (m, l, o) with K/V block.

    q: [B,H,Sq,D]; k,v: [B,H,Sk,D]; m,l: [B,H,Sq]; o: [B,H,Sq,D].
    q_off/k_off are global sequence offsets of the local blocks.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        iq = q_off + jnp.arange(q.shape[-2])[:, None]
        ik = k_off + jnp.arange(k.shape[-2])[None, :]
        s = jnp.where(iq >= ik, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows (m_new = -inf): keep them at zero weight
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = alpha * l + jnp.sum(p, axis=-1)
    o_new = alpha[..., None] * o + jnp.einsum(
        "bhqk,bhkd->bhqd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def ring_attention_shard(q, k, v, axis_name, n_shards, causal=True,
                         scale=None):
    """Per-shard ring attention body (inside shard_map).

    q,k,v: local [B, H, S/cp, D] blocks, sequence-sharded on `axis_name`.
    Returns local attention output [B, H, S/cp, D].
    """
    seq_block = q.shape[-2]
    scale = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    my = lax.axis_index(axis_name)
    q_off = my * seq_block

    # scan carries start replicated but become shard-dependent
    m = varying(jnp.full(q.shape[:-1], -jnp.inf, dtype=jnp.float32),
                (axis_name,))
    l = varying(jnp.zeros(q.shape[:-1], dtype=jnp.float32), (axis_name,))
    o = varying(jnp.zeros(q.shape, dtype=jnp.float32), (axis_name,))

    def step(carry, r):
        k_blk, v_blk, m, l, o = carry
        # K/V block currently held came from shard (my - r) mod n
        src = jnp.mod(my - r, n_shards)
        k_off = src * seq_block
        m, l, o = _block_attend(q, k_blk, v_blk, m, l, o, q_off, k_off,
                                scale, causal)
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return (k_blk, v_blk, m, l, o), None

    (k, v, m, l, o), _ = lax.scan(step, (k, v, m, l, o),
                                  jnp.arange(n_shards))
    l = jnp.maximum(l, 1e-20)
    return (o / l[..., None]).astype(q.dtype)


# -- flash ring attention --------------------------------------------------
# Same ring schedule, but each (Q-local, K-block) pair runs through the
# Pallas flash kernels (ops/pallas/flash_attention.py blockwise API):
# per-pair HBM traffic stays O(S·d) instead of the jnp path's O(S_local²)
# score tensors, which is what makes long local sequences feasible.  The
# backward is a second ring pass: dq accumulates locally from the combined
# lse, while (dk, dv) accumulators travel WITH their K/V block around the
# ring and arrive home after n steps holding every shard's contribution.


def _ring_rotate(xs, axis_name, n_shards):
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
    return [lax.ppermute(x, axis_name, perm) for x in xs]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(q, k, v, axis_name, n_shards, causal, scale):
    o, _ = _ring_flash_fwd_impl(q, k, v, axis_name, n_shards, causal,
                                scale)
    return o


def _ring_flash_fwd_impl(q, k, v, axis_name, n_shards, causal, scale):
    from ..ops.pallas.flash_attention import flash_attention_block
    sq = q.shape[-2]
    my = lax.axis_index(axis_name)
    q_off = my * sq
    o0 = varying(jnp.zeros(q.shape, jnp.float32), (axis_name,))
    lse0 = varying(jnp.full(q.shape[:-1], -1e30, jnp.float32),
                   (axis_name,))

    def step(carry, r):
        k_blk, v_blk, o, lse = carry
        src = jnp.mod(my - r, n_shards)
        o_blk, lse_blk = flash_attention_block(
            q, k_blk, v_blk, q_off, src * sq, causal=causal, scale=scale)
        lse_new = jnp.logaddexp(lse, lse_blk)
        o = (o * jnp.exp(lse - lse_new)[..., None]
             + o_blk.astype(jnp.float32)
             * jnp.exp(lse_blk - lse_new)[..., None])
        k_blk, v_blk = _ring_rotate([k_blk, v_blk], axis_name, n_shards)
        return (k_blk, v_blk, o, lse_new), None

    (_, _, o, lse), _ = lax.scan(step, (k, v, o0, lse0),
                                 jnp.arange(n_shards))
    return o.astype(q.dtype), lse


def _ring_flash_fwd(q, k, v, axis_name, n_shards, causal, scale):
    o, lse = _ring_flash_fwd_impl(q, k, v, axis_name, n_shards, causal,
                                  scale)
    return o, (q, k, v, o, lse)


def _ring_flash_bwd(axis_name, n_shards, causal, scale, res, g):
    from ..ops.pallas.flash_attention import flash_attention_block_bwd
    q, k, v, o, lse = res
    sq = q.shape[-2]
    my = lax.axis_index(axis_name)
    q_off = my * sq
    dq0 = varying(jnp.zeros(q.shape, jnp.float32), (axis_name,))
    dk0 = varying(jnp.zeros(k.shape, jnp.float32), (axis_name,))
    dv0 = varying(jnp.zeros(v.shape, jnp.float32), (axis_name,))

    def step(carry, r):
        k_blk, v_blk, dk_blk, dv_blk, dq = carry
        src = jnp.mod(my - r, n_shards)
        dq_c, dk_c, dv_c = flash_attention_block_bwd(
            q, k_blk, v_blk, o, lse, g, q_off, src * sq,
            causal=causal, scale=scale)
        dq = dq + dq_c.astype(jnp.float32)
        dk_blk = dk_blk + dk_c.astype(jnp.float32)
        dv_blk = dv_blk + dv_c.astype(jnp.float32)
        k_blk, v_blk, dk_blk, dv_blk = _ring_rotate(
            [k_blk, v_blk, dk_blk, dv_blk], axis_name, n_shards)
        return (k_blk, v_blk, dk_blk, dv_blk, dq), None

    (_, _, dk, dv, dq), _ = lax.scan(step, (k, v, dk0, dv0, dq0),
                                     jnp.arange(n_shards))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(mesh, q, k, v, *, axis="cp", causal=True, scale=None,
                   batch_axis="dp"):
    """Host-level: q,k,v [B, H, S, D] with S sharded over `axis`.

    Uses the Pallas blockwise flash kernels when the per-shard shapes fit
    the kernel envelope (128-multiple local seq, 8-aligned d ≤ 512);
    otherwise the jnp online-softmax path.  On a combined mesh the batch
    dim stays sharded over ``batch_axis`` (if present) — attention is
    batch-local, so dp shards pass straight through the shard_map."""
    from ..ops.pallas.flash_attention import blockwise_supported
    n = mesh.shape[axis]
    b_ax = batch_axis if (batch_axis and batch_axis in mesh.shape
                          and q.shape[0] % mesh.shape[batch_axis] == 0) \
        else None
    spec = P(b_ax, None, axis, None)
    b_local = q.shape[0] // (mesh.shape[b_ax] if b_ax else 1)
    local_q = (b_local, q.shape[1], q.shape[2] // n, q.shape[3])
    if blockwise_supported(local_q, local_q):
        # custom_vjp functions take positional args only; check_vma off
        # because pallas_call out_shapes don't carry vma annotations
        f = shard_map(
            lambda q, k, v: _ring_flash(q, k, v, axis, n, causal, scale),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)
        return f(q, k, v)
    f = shard_map(
        functools.partial(ring_attention_shard, axis_name=axis, n_shards=n,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return f(q, k, v)


def ulysses_attention_shard(q, k, v, axis_name, n_shards, causal=True,
                            scale=None):
    """Per-shard Ulysses body (inside shard_map over `axis_name`).

    Local q,k,v: [B, H, S/n, D].  a2a → [B, H/n, S, D] (all tokens, head
    subset) → plain attention → a2a back.
    """
    def seq_to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    q, k, v = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    d = q.shape[-1]
    scale_ = scale if scale is not None else 1.0 / (d ** 0.5)
    # after the a2a the attention is plain LOCAL self-attention over the
    # full sequence (head subset) — route it through the flash kernel when
    # the shape fits, the same win as single-device attention
    from ..ops.pallas.flash_attention import flash_attention
    o = flash_attention(q, k, v, causal=causal, scale=scale_)
    if o is None:
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                       preferred_element_type=jnp.float32) * scale_
        if causal:
            S = s.shape[-1]
            iq = jnp.arange(S)[:, None]
            ik = jnp.arange(S)[None, :]
            s = jnp.where(iq >= ik, s, -1e9)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                       preferred_element_type=jnp.float32).astype(v.dtype)
    return heads_to_seq(o.astype(v.dtype))


def ulysses_attention(mesh, q, k, v, *, axis="cp", causal=True, scale=None):
    n = mesh.shape[axis]
    assert q.shape[1] % n == 0, "num heads must divide cp degree"
    spec = P(None, None, axis, None)
    f = shard_map(
        functools.partial(ulysses_attention_shard, axis_name=axis,
                          n_shards=n, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)  # pallas out_shapes carry no vma annotations
    return f(q, k, v)
