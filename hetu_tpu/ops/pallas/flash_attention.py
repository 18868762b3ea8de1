"""Pallas TPU flash attention (FlashAttention-2 style, fwd + bwd kernels).

The reference composes attention from batched matmuls + a full [B,H,S,S]
softmax (layers/attention.py) — O(S^2) HBM traffic, which OOMs BERT-base at
per-chip batch 64.  This kernel keeps the score tile in VMEM with online
softmax, so HBM traffic stays O(S·d):

  forward : grid (B*H, S/block_q); the kv loop runs inside the kernel with
            running (m, l, acc) carries; saves the logsumexp for backward.
  backward: two kernels — dQ over q blocks, dK/dV over kv blocks — that
            recompute P tiles from (Q, K, lse) instead of storing them
            (the standard flash backward: dS = P∘(dO·Vᵀ − D),
            D = rowsum(dO∘O)).
  dropout : applied to the probability tiles in-kernel with the TPU PRNG,
            reseeded per (seed, bh, q-block, kv-block) tile so the backward
            kernels replay the identical mask; l accumulates un-dropped
            sums so O = dropout(softmax(S))·V exactly.

Supported: additive key mask [B, 1, 1, S] (BERT padding masks), causal,
any head dim ≤ 512 and any seq ≥ 128: the wrapper zero-pads d to the
8-aligned [32, 512] kernel envelope and pads seq up to a block multiple
with -inf key-column masking, then slices the output (padding/slicing sit
OUTSIDE the custom_vjp, so jnp.pad's own VJP zeroes the padded rows'
cotangents and the gradients stay exact).  Returns None only for truly
unsupported cases (d > 512, short seqs where the O(S^2) composition is
cheaper, non-[B,1,1,S] masks) so callers fall back to the jnp composition
(ops/attention.py).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import interpret

_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453

_BLOCK_Q = 512
_BLOCK_K = 512
_NEG_INF = -1e30


def unsupported(q, k, v, mask=None, dropout_keep=1.0):
    """Why the kernel cannot take these operands, or None when it can.
    Callers that fall back to the jnp composition record this string
    (ops/pallas/dispatch.py)."""
    if q.ndim != 4 or k.shape != q.shape or v.shape != q.shape:
        return "not_self_attention_4d"
    b, h, s, d = q.shape
    # head dim is always the FULL last block dim, so Mosaic only needs it
    # 8-aligned (the wrapper pads to that); > 512 would blow VMEM tiles
    if d > 512:
        return "head_dim>512"
    # below one lane-tile of rows the O(S^2) composition is cheaper than
    # padding up to a kernel block
    if s < 128:
        return "seq<128"
    if mask is not None and tuple(mask.shape) != (b, 1, 1, s):
        return "mask_not_b11s"
    if dropout_keep < 1.0 and interpret():
        return "dropout_prng_needs_mosaic"
    return None


def _pad_plan(s):
    """(padded_seq, block): pad seq to a block multiple and pick the block.

    512 tiles measured fastest on v5e at both BERT (B64·H12·S512·d64:
    9.9 ms vs 13.9 ms fwd+bwd with 256 tiles — beating XLA's S^2
    composition at 13.7 ms) and GPT-2.7B shapes (causal S2048·d80:
    64 ms vs 87 ms); smaller blocks only when the padded seq doesn't
    divide, keeping padding waste < one 128-row tile."""
    s_pad = s if s % 128 == 0 else -(-s // 128) * 128
    for block in (512, 256, 128):
        if s_pad % block == 0:
            return s_pad, block
    raise AssertionError(s_pad)


def _keep_threshold(keep_prob):
    # uint32 threshold: bits < threshold  <=>  keep (prob ~ keep_prob)
    return np.uint32(min(int(keep_prob * 4294967296.0), 4294967295))


def _tile_index(bh, qi, j, nq, nk):
    """Unique int32 per (batch*head, q-block, kv-block) tile — Mosaic's
    prng_seed accepts at most two scalars, so fold the coordinates."""
    return (bh * nq + qi) * nk + j


def _tile_keep(shape, seed_ref, tile, keep_prob):
    """The deterministic keep mask for one prob tile.  ALL kernels (fwd,
    dq, dkv) must obtain masks through this single helper — the backward
    replays the forward's masks purely by reseeding with the same tile
    index."""
    pltpu.prng_seed(seed_ref[0], tile)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits < _keep_threshold(keep_prob)


def _drop_tile(p, seed_ref, tile, keep_prob):
    keep = _tile_keep(p.shape, seed_ref, tile, keep_prob)
    return jnp.where(keep, p / keep_prob, 0.0)


# -- forward ---------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, seed_ref, offs_ref,
                o_ref, lse_ref, *, scale, causal, block_k, q_len, k_len,
                keep_prob, empty_lse_neg=False):
    """offs_ref (optional SMEM int32[2] = [q_off, k_off]): GLOBAL sequence
    offsets of the local q/k blocks — the ring-attention path attends a
    rotating remote K/V block, so causal masking compares global positions.
    ``empty_lse_neg``: blockwise callers need lse=-inf semantics for rows
    with no live key in THIS block (so the cross-block logaddexp combine
    ignores them); self-attention callers need +inf (see comment below)."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    d = q_ref.shape[2]
    # inputs stay in their storage dtype (bf16 models hit the MXU's
    # bf16 rate — pre-casting to f32 forced f32-rate matmuls, ~4x
    # slower); products/accumulation are f32 via preferred_element_type,
    # identical numerics on the input side (bf16->f32 casts are exact)
    q = q_ref[0]                                      # (bq, d)
    q_off = offs_ref[0] if offs_ref is not None else 0
    k_off = offs_ref[1] if offs_ref is not None else 0
    row = (q_off + qi * bq
           + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0))

    nk = k_len // block_k
    nk_causal = nk
    if causal:
        # kv blocks strictly above the diagonal contribute nothing; with
        # offsets the bound is dynamic (clamped below), without it's static
        hi = (q_off + (qi + 1) * bq - 1 - k_off) // block_k + 1
        nk_causal = jax.lax.clamp(0, hi, nk) if offs_ref is not None \
            else jax.lax.min(nk, hi)

    def make_body(masked):
        def body(j, carry):
            m, l, acc = carry
            kb = k_ref[0, pl.ds(j * block_k, block_k), :]
            vb = v_ref[0, pl.ds(j * block_k, block_k), :]
            # scores tracked in BASE-2 units (s2 = s * log2(e)): exp2 is
            # the VPU's native exponential; lse converts back to natural
            # units at the end so the backward's exp(s - lse) contract is
            # unchanged
            s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) \
                * (scale * _LOG2E)
            if mask_ref is not None:
                s = s + (mask_ref[0, 0,
                                  pl.ds(j * block_k, block_k)][None, :]
                         * _LOG2E)
            if causal and masked:
                col = (k_off + j * block_k
                       + jax.lax.broadcasted_iota(jnp.int32,
                                                  (bq, block_k), 1))
                s = jnp.where(row >= col, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            p = jnp.exp2(s - m_new[:, None])
            alpha = jnp.exp2(m - m_new)
            # l accumulates UN-dropped sums: O = dropout(P_norm) @ V
            l_new = l * alpha + jnp.sum(p, axis=1)
            if keep_prob < 1.0:
                nq, nk_tot = q_len // bq, k_len // block_k
                p = _drop_tile(p, seed_ref,
                               _tile_index(bh, qi, j, nq, nk_tot),
                               keep_prob)
            acc_new = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new
        return body

    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    if causal and k_len // block_k > 8:
        # split loop: kv blocks fully below the diagonal need no mask —
        # the where+iota per tile is pure VPU overhead on ~(nk-1)/nk of
        # the causal work, alternating with the exp2 on the critical
        # path.  Only worth it when there are MANY kv blocks (long
        # context / ring shards); at nk <= ~8 the second loop's
        # bookkeeping outweighs the saved masking (measured +0.1
        # ms/layer on GPT-2.7B S=2048 with 512-blocks, -12% kernel time
        # at S=8192).
        lo = (q_off + qi * bq - k_off) // block_k
        n_full = (jax.lax.clamp(0, lo, nk) if offs_ref is not None
                  else jax.lax.max(0, jax.lax.min(nk, lo)))
        carry = jax.lax.fori_loop(0, n_full, make_body(False),
                                  (m0, l0, acc0))
        m, l, acc = jax.lax.fori_loop(n_full, nk_causal, make_body(True),
                                      carry)
    else:
        m, l, acc = jax.lax.fori_loop(0, nk_causal, make_body(True),
                                      (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    m = m * _LN2    # back to natural-log units for the stored lse
    # fully-masked rows (l == 0, every key at -inf): output is 0; store
    # lse = +large so the backward's p = exp(s - lse) underflows to 0 —
    # storing m (≈ -1e30) instead would give p = exp(0) = 1 everywhere
    # and garbage dq/dk/dv for the row.  Blockwise (ring) callers instead
    # want -large: their backward uses the COMBINED lse (never empty for a
    # causal row), and the fwd combine must treat this block as weightless.
    empty = _NEG_INF if empty_lse_neg else -_NEG_INF
    lse = jnp.where(l == 0.0, empty, m + jnp.log(l_safe))
    lse_ref[0, 0] = lse.astype(jnp.float32)


def _make_kern(base, has_mask, has_seed, n_out, has_offs=False, **consts):
    """Adapts a kernel with optional (mask_ref, seed_ref, offs_ref) slots
    to the positional ref list pallas_call passes."""

    def kern(*refs):
        n_in = len(refs) - n_out
        ins = list(refs[:n_in])
        outs = list(refs[n_in:])
        offs_ref = ins.pop() if has_offs else None
        seed_ref = ins.pop() if has_seed else None
        mask_ref = ins.pop() if has_mask else None
        base(*ins, mask_ref, seed_ref, offs_ref, *outs, **consts)

    return kern


def _fwd(q, k, v, mask, causal, scale, keep_prob=1.0, seed=None,
         block_q=_BLOCK_Q, block_k=_BLOCK_K, offsets=None,
         empty_lse_neg=False):
    """q: [b,h,sq,d]; k,v: [b,h,sk,d] (sq != sk in the blockwise/ring path,
    where ``offsets`` = int32[2] global [q_off, k_off])."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
        pl.BlockSpec((1, sk, d), lambda bh, i: (bh, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda bh, i: (bh, 0, 0)),
    ]
    args = [qf, kf, vf]
    if mask is not None:
        in_specs.append(pl.BlockSpec(
            (1, 1, sk), lambda bh, i, h=h: (bh // h, 0, 0)))
        args.append(mask.reshape(b, 1, sk).astype(jnp.float32))
    if keep_prob < 1.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed.reshape(1).astype(jnp.int32))
    if offsets is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(offsets)
    kern = _make_kern(_fwd_kernel, mask is not None, keep_prob < 1.0, 2,
                      has_offs=offsets is not None,
                      scale=scale, causal=causal, block_k=block_k,
                      q_len=sq, k_len=sk, keep_prob=keep_prob,
                      empty_lse_neg=empty_lse_neg)
    o, lse = pl.pallas_call(
        kern,
        name="hetu_flash_fwd",
        interpret=interpret(),
        grid=(b * h, sq // block_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, i: (bh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, sq), jnp.float32),
        ])(*args)
    return o.reshape(b, h, sq, d), lse


# -- backward --------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, mask_ref,
                   seed_ref, offs_ref, dq_ref, *, scale, causal, block_k,
                   q_len, k_len, keep_prob):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    d = q_ref.shape[2]
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0]
    dsum = dsum_ref[0, 0]
    q_off = offs_ref[0] if offs_ref is not None else 0
    k_off = offs_ref[1] if offs_ref is not None else 0
    row = (q_off + qi * bq
           + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0))

    def body(j, acc):
        kb = k_ref[0, pl.ds(j * block_k, block_k), :]
        vb = v_ref[0, pl.ds(j * block_k, block_k), :]
        # base-2 scores (exp2 = native VPU exponential; p identical)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * (scale * _LOG2E)
        if mask_ref is not None:
            s = s + (mask_ref[0, 0, pl.ds(j * block_k, block_k)][None, :]
                     * _LOG2E)
        if causal:
            col = (k_off + j * block_k
                   + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1))
            s = jnp.where(row >= col, s, _NEG_INF)
        p = jnp.exp2(s - (lse * _LOG2E)[:, None])
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if keep_prob < 1.0:  # replay the fwd tile mask on dP
            nq, nk_tot = q_len // bq, k_len // block_k
            dp = _drop_tile(dp, seed_ref,
                            _tile_index(bh, qi, j, nq, nk_tot), keep_prob)
        ds = p * (dp - dsum[:, None])
        return acc + jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    acc0 = jnp.zeros((bq, d), jnp.float32)
    nk = k_len // block_k
    if causal:
        # above-diagonal kv tiles are fully masked (p == 0): skip them
        hi = (q_off + (qi + 1) * bq - 1 - k_off) // block_k + 1
        nk = jax.lax.clamp(0, hi, nk) if offs_ref is not None \
            else jax.lax.min(nk, hi)
    acc = jax.lax.fori_loop(0, nk, body, acc0)
    dq_ref[0] = (acc * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, mask_ref,
                    seed_ref, offs_ref, dk_ref, dv_ref, *, scale, causal,
                    block_q, q_len, k_len, keep_prob):
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    bk = k_ref.shape[1]
    d = k_ref.shape[2]
    k = k_ref[0]
    v = v_ref[0]
    q_off = offs_ref[0] if offs_ref is not None else 0
    k_off = offs_ref[1] if offs_ref is not None else 0
    col = (k_off + ki * bk
           + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1))
    mblk = (mask_ref[0, 0, pl.ds(ki * bk, bk)][None, :]
            if mask_ref is not None else None)

    def body(i, carry):
        dk, dv = carry
        qb = q_ref[0, pl.ds(i * block_q, block_q), :]
        dob = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.ds(i * block_q, block_q)]
        dsum = dsum_ref[0, 0, pl.ds(i * block_q, block_q)]
        s = jax.lax.dot_general(qb, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
            * (scale * _LOG2E)
        if mblk is not None:
            s = s + mblk * _LOG2E
        if causal:
            rr = (q_off + i * block_q
                  + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 0))
            s = jnp.where(rr >= col, s, _NEG_INF)
        p = jnp.exp2(s - (lse * _LOG2E)[:, None])
        if keep_prob < 1.0:
            # fwd seeded by tile (bh, q-block=i, kv-block=ki)
            nq, nk_tot = q_len // block_q, k_len // bk
            keep = _tile_keep(p.shape, seed_ref,
                              _tile_index(bh, i, ki, nq, nk_tot),
                              keep_prob)
            p_dropped = jnp.where(keep, p / keep_prob, 0.0)
        else:
            keep = None
            p_dropped = p
        dv_new = dv + jax.lax.dot_general(
            p_dropped.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(dob, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if keep is not None:
            dp = jnp.where(keep, dp / keep_prob, 0.0)
        ds = p * (dp - dsum[:, None])
        dk_new = dk + jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk_new, dv_new

    dk0 = jnp.zeros((bk, d), jnp.float32)
    dv0 = jnp.zeros((bk, d), jnp.float32)
    i_start = 0
    if causal:
        # q tiles strictly above the diagonal see none of this kv block;
        # with offsets the bound is dynamic (global positions)
        lo = (k_off + ki * bk - q_off) // block_q
        i_start = jax.lax.clamp(0, lo, q_len // block_q) \
            if offs_ref is not None else lo
    dk, dv = jax.lax.fori_loop(i_start, q_len // block_q, body,
                               (dk0, dv0))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_impl(q, k, v, mask, o, lse, dout, causal, scale, keep_prob, seed,
              block_q=_BLOCK_Q, block_k=_BLOCK_K, offsets=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    qf = q.reshape(b * h, sq, d)
    kf, vf = (t.reshape(b * h, sk, d) for t in (k, v))
    dof = dout.reshape(b * h, sq, d)
    dsum = jnp.sum(dof.astype(jnp.float32)
                   * o.reshape(b * h, sq, d).astype(jnp.float32),
                   axis=-1)[:, None, :]                      # (BH, 1, Sq)
    args = [qf, kf, vf, dof, lse, dsum]
    base_specs = [
        pl.BlockSpec((1, sq, d), lambda bh, i: (bh, 0, 0)),  # q (full)
        pl.BlockSpec((1, sk, d), lambda bh, i: (bh, 0, 0)),  # k
        pl.BlockSpec((1, sk, d), lambda bh, i: (bh, 0, 0)),  # v
        pl.BlockSpec((1, sq, d), lambda bh, i: (bh, 0, 0)),  # do
        pl.BlockSpec((1, 1, sq), lambda bh, i: (bh, 0, 0)),  # lse
        pl.BlockSpec((1, 1, sq), lambda bh, i: (bh, 0, 0)),  # dsum
    ]
    extra_args, extra_specs = [], []
    if mask is not None:
        extra_args.append(mask.reshape(b, 1, sk).astype(jnp.float32))
        extra_specs.append(pl.BlockSpec(
            (1, 1, sk), lambda bh, i, h=h: (bh // h, 0, 0)))
    if keep_prob < 1.0:
        extra_args.append(seed.reshape(1).astype(jnp.int32))
        extra_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if offsets is not None:
        extra_args.append(offsets)
        extra_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    dq_specs = list(base_specs)
    dq_specs[0] = pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0))
    dq_specs[3] = pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0))
    dq_specs[4] = pl.BlockSpec((1, 1, block_q), lambda bh, i: (bh, 0, i))
    dq_specs[5] = pl.BlockSpec((1, 1, block_q), lambda bh, i: (bh, 0, i))

    dq_kern = _make_kern(_bwd_dq_kernel, mask is not None, keep_prob < 1.0,
                         1, has_offs=offsets is not None,
                         scale=scale, causal=causal, block_k=block_k,
                         q_len=sq, k_len=sk, keep_prob=keep_prob)
    dq = pl.pallas_call(
        dq_kern, name="hetu_flash_bwd_dq", interpret=interpret(),
        grid=(b * h, sq // block_q),
        in_specs=dq_specs + extra_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
    )(*args, *extra_args)

    dkv_specs = list(base_specs)
    dkv_specs[1] = pl.BlockSpec((1, block_k, d), lambda bh, i: (bh, i, 0))
    dkv_specs[2] = pl.BlockSpec((1, block_k, d), lambda bh, i: (bh, i, 0))
    dkv_kern = _make_kern(_bwd_dkv_kernel, mask is not None,
                          keep_prob < 1.0, 2, has_offs=offsets is not None,
                          scale=scale, causal=causal,
                          block_q=block_q, q_len=sq, k_len=sk,
                          keep_prob=keep_prob)
    dk, dv = pl.pallas_call(
        dkv_kern, name="hetu_flash_bwd_dkv", interpret=interpret(),
        grid=(b * h, sk // block_k),
        in_specs=dkv_specs + extra_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bh, i: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, i: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, sk, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, sk, d), v.dtype),
        ])(*args, *extra_args)

    return (dq.reshape(b, h, sq, d), dk.reshape(b, h, sk, d),
            dv.reshape(b, h, sk, d))


# -- custom-vjp wrappers ---------------------------------------------------
# two variants (with/without mask) keep the signatures positional; the
# dropout seed is a traced uint32 tensor with zero cotangent.

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_nomask(q, k, v, seed, causal, scale, keep_prob, block):
    return _fwd(q, k, v, None, causal, scale, keep_prob, seed,
                block_q=block, block_k=block)[0]


def _flash_nomask_fwd(q, k, v, seed, causal, scale, keep_prob, block):
    o, lse = _fwd(q, k, v, None, causal, scale, keep_prob, seed,
                  block_q=block, block_k=block)
    return o, (q, k, v, seed, o, lse)


def _flash_nomask_bwd(causal, scale, keep_prob, block, res, g):
    q, k, v, seed, o, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, None, o, lse, g, causal, scale,
                           keep_prob, seed, block_q=block, block_k=block)
    return dq, dk, dv, jnp.zeros_like(seed)


_flash_nomask.defvjp(_flash_nomask_fwd, _flash_nomask_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_mask(q, k, v, mask, seed, causal, scale, keep_prob, block):
    return _fwd(q, k, v, mask, causal, scale, keep_prob, seed,
                block_q=block, block_k=block)[0]


def _flash_mask_fwd(q, k, v, mask, seed, causal, scale, keep_prob, block):
    o, lse = _fwd(q, k, v, mask, causal, scale, keep_prob, seed,
                  block_q=block, block_k=block)
    return o, (q, k, v, mask, seed, o, lse)


def _flash_mask_bwd(causal, scale, keep_prob, block, res, g):
    q, k, v, mask, seed, o, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, mask, o, lse, g, causal, scale,
                           keep_prob, seed, block_q=block, block_k=block)
    # The additive mask is treated as NON-differentiable data (our graphs
    # build it from placeholder attention masks).  A learned attention bias
    # must use the jnp fallback path, which differentiates the bias.
    return dq, dk, dv, jnp.zeros_like(mask), jnp.zeros_like(seed)


_flash_mask.defvjp(_flash_mask_fwd, _flash_mask_bwd)


# -- blockwise API (ring / context parallelism) ----------------------------
# One (Q-local, K/V-block) pair with GLOBAL sequence offsets: the ring
# schedule (parallel/context_parallel.py) rotates K/V blocks around the
# ICI ring and combines per-block results with logaddexp.  No reference
# counterpart (SURVEY §5: the reference has no ring attention); the
# blockwise math follows the flash-attention decomposition.

def _block_sizes(sq, sk):
    bq = next((b for b in (512, 256, 128) if sq % b == 0), None)
    bk = next((b for b in (512, 256, 128) if sk % b == 0), None)
    return bq, bk


def blockwise_supported(q_shape, k_shape):
    b, h, sq, d = q_shape
    sk = k_shape[2]
    bq, bk = _block_sizes(sq, sk)
    return (d <= 512 and d % 8 == 0 and d >= 32
            and bq is not None and bk is not None)


def flash_attention_block(q, k, v, q_off, k_off, *, causal=True,
                          scale=None):
    """Fused attention of local q [B,H,Sq,D] against ONE K/V block
    [B,H,Sk,D] at global offsets (q_off, k_off) — returns
    (o_normalized [B,H,Sq,D], lse [B,H,Sq]) where rows with no live key in
    this block get lse = -1e30 (weightless under the logaddexp combine)."""
    b, h, sq, d = q.shape
    bq, bk = _block_sizes(sq, k.shape[2])
    offsets = jnp.stack([q_off, k_off]).astype(jnp.int32)
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    o, lse = _fwd(q, k, v, None, causal, float(scale), 1.0,
                  jnp.zeros((1,), jnp.int32), block_q=bq, block_k=bk,
                  offsets=offsets, empty_lse_neg=True)
    return o, lse.reshape(b, h, sq)


def flash_attention_block_bwd(q, k, v, o, lse, dout, q_off, k_off, *,
                              causal=True, scale=None):
    """Gradients of one ring step given the COMBINED (o, lse) of the full
    ring forward: p = exp(s - lse_final) is each block's true global
    attention weight, so dq sums over blocks and (dk, dv) are per-block
    exact.  lse: [B,H,Sq]."""
    b, h, sq, d = q.shape
    bq, bk = _block_sizes(sq, k.shape[2])
    offsets = jnp.stack([q_off, k_off]).astype(jnp.int32)
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    return _bwd_impl(q, k, v, None, o, lse.reshape(b * h, 1, sq), dout,
                     causal, float(scale), 1.0,
                     jnp.zeros((1,), jnp.int32), block_q=bq, block_k=bk,
                     offsets=offsets)


def flash_attention(q, k, v, mask=None, causal=False, scale=None,
                    dropout_keep=1.0, seed=None):
    """Fused attention; returns None when shapes are unsupported so the
    caller falls back to the jnp composition (ops/attention.py).

    ``dropout_keep`` < 1 applies attention-prob dropout in-kernel (TPU
    PRNG); ``seed`` must then be an int32/uint32 scalar array.
    """
    if unsupported(q, k, v, mask, dropout_keep) is not None:
        return None
    if dropout_keep < 1.0 and seed is None:
        raise ValueError(
            "flash_attention: dropout_keep < 1 requires seed= (an int32 "
            "scalar array; the per-tile dropout masks derive from it)")
    b, h, s, d = q.shape
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    if dropout_keep >= 1.0:
        seed = jnp.zeros((1,), jnp.int32)

    # pad into the kernel envelope; padding/slicing live OUTSIDE the
    # custom_vjp so jnp.pad's VJP zero-fills the padded rows' cotangents
    # and the gradients of the real region stay exact
    d_pad = max(32, -(-d // 8) * 8)
    s_pad, block = _pad_plan(s)
    if d_pad != d or s_pad != s:
        pad3 = ((0, 0), (0, 0), (0, s_pad - s), (0, d_pad - d))
        q, k, v = (jnp.pad(t, pad3) for t in (q, k, v))
        if s_pad != s and not (causal and mask is None):
            # padded key columns must not attend; real causal rows never
            # see columns ≥ s, so pure-causal needs no mask
            base = (mask if mask is not None
                    else jnp.zeros((b, 1, 1, s), jnp.float32))
            mask = jnp.pad(base, ((0, 0), (0, 0), (0, 0), (0, s_pad - s)),
                           constant_values=_NEG_INF)

    if mask is None:
        out = _flash_nomask(q, k, v, seed, causal, float(scale),
                            float(dropout_keep), block)
    else:
        out = _flash_mask(q, k, v, mask, seed, causal, float(scale),
                          float(dropout_keep), block)
    if d_pad != d or s_pad != s:
        out = out[:, :, :s, :d]
    return out


def sharded_flash_attention(mesh, q, k, v, mask=None, *, batch_axes=(),
                            head_axes=(), seed=None, **kw):
    """:func:`flash_attention` inside a GSPMD mesh program.

    ``pallas_call`` does not partition, and attention is local to one
    (batch row, head), so the kernel runs under ``shard_map`` on each
    device's own slice: q/k/v ``[B, H, S, D]`` split their batch dim over
    ``batch_axes`` and their head dim over ``head_axes`` (mesh axis names;
    both dims must divide), the ``[B, 1, 1, S]`` mask follows the batch,
    and the dropout seed is offset by the shard's index so that shards do
    not repeat one mask.  Same return contract as ``flash_attention``."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    batch_axes, head_axes = tuple(batch_axes), tuple(head_axes)
    spec = P(batch_axes or None, head_axes or None, None, None)
    mspec = P(batch_axes or None, None, None, None)
    operands, specs = [q, k, v], [spec, spec, spec]
    if mask is not None:
        operands.append(mask)
        specs.append(mspec)
    if seed is not None:
        operands.append(seed)
        specs.append(P())

    def local(q, k, v, *rest):
        rest = list(rest)
        m = rest.pop(0) if mask is not None else None
        sd = rest.pop(0) if seed is not None else None
        if sd is not None and batch_axes + head_axes:
            shard = jax.lax.axis_index(batch_axes + head_axes)
            sd = sd + shard.astype(sd.dtype) * jnp.asarray(
                -1640531535, sd.dtype)        # 0x9E3779B1, wraps in int32
        return flash_attention(q, k, v, mask=m, seed=sd, **kw)

    # pallas out_shapes carry no varying-axes annotations
    return shard_map(local, mesh=mesh, in_specs=tuple(specs),
                     out_specs=spec, check_vma=False)(*operands)
