"""Pallas TPU flash attention (FlashAttention-2 style): one forward and one
backward kernel, reading and writing the heads where they lie.

The reference composes attention from batched matmuls + a full [B,H,S,S]
softmax (layers/attention.py) — O(S^2) HBM traffic, which OOMs BERT-base at
per-chip batch 64.  These kernels keep the score tile in VMEM with online
softmax, so HBM traffic stays O(S·d).

Two layouts, one pair of kernel bodies.  The layers hand over the
projections' ``[B, S, H*D]`` and get the context back the same way
(``num_heads`` given): a program then walks ``g = 128 // D`` neighbouring
heads (2 at head size 64, 4 at 32, 1 at a multiple of 128) as one block
``(rows, g*D)``, 128 lanes wide, cut out of the array in place — no
transpose on either side of either pass.  Callers that own ``[B, H, S, D]``
(ring and Ulysses attention, Galvatron) and head sizes whose groups are not
lane-aligned (80, 96) run the same bodies with ``g = 1`` over the free
``[B*H, S, D]`` view; only the ``BlockSpec`` index maps differ (``_Walk``).
Inside a program the heads are a static loop.  Head ``h``'s products are
taken over the block's whole lane width with the other heads' lanes zeroed
in one operand (``_only_head``): on a 128 x 128 matrix unit a contraction
over 64 or an output 64 wide costs the same passes as one over 128, the
zeros add nothing to the f32 sums, and the heads' outputs land in their own
lanes of one tile, which is stored once.

K and V may be NARROWER than q in place (grouped queries, heads of whole lane
tiles): ``[B, S, KV*D]`` under q's ``[B, S, H*D]``, ``rep = H / KV`` read from
the widths.  Program ``hg`` then reads the lane block ``hg // rep`` of K and V
(``_Walk.kv_rows``: forward, the window's band, the backward pass's inputs):
nothing repeats a key head in HBM.  The backward kernel writes dK and dV a
QUERY head, as it does behind a ``repeat_kv``, and ``_group_sum`` adds each
group up outside it (accumulating a group inside would fetch the whole q, o
and dO again for every key block).  ``rep = 1`` is the program it was.

V (and the context) may have ANOTHER head size than q and k in either layout:
``[B, H, S, D]`` of any two sizes, or in place where both are whole lane tiles
and a program takes one head (latent attention: ``[B, S, H x 256]`` over ``[B,
S, H x 128]``, the entry ``bshd_v128``); the two walks (``_walks``) then
differ in their width alone.

  forward : ``hetu_flash_fwd``, grid (B, H/g, Sq/block_q); the kv loop runs
            inside the kernel with running (m, l, acc) carries; saves the
            logsumexp ``[B, H/g, g, Sq]`` for the backward pass.
  backward: ``hetu_flash_bwd``, grid (B, H/g, Sk/block_k) over key blocks,
            a loop over the query blocks that see the key block inside.  A
            tile's S, exp, dropout bits, dP and dS = P∘(dP − D) are formed
            once and feed dV += P^T dO, dK += dS^T Q and dQ[i] += dS K.  dQ
            accumulates in f32 VMEM scratch for the whole (Sq, g*D) of the
            head group, resident across the key-block axis (the grid is
            sequential), and is written at the last key block.
            D = rowsum(dO∘O) comes from the O and dO rows the program
            already holds.
  dropout : applied to the probability tiles in-kernel with the TPU PRNG,
            reseeded per (seed, batch row, head, q-block, kv-block) tile so
            the backward replays the identical mask in either layout; l
            accumulates un-dropped sums so O = dropout(softmax(S))·V
            exactly.  The 1/keep factor is applied to the (rows, g*D)
            results, not to the (block_q, block_k) tiles.

Three static plans beside the causal and the full walk, each the same two
bodies under other loop bounds: ``window`` (a band of keys behind a position,
``hetu_swa_*``), ``bd`` (the block-diffusion mask over a clean and a noised
copy, ``hetu_flash_*_bd``) and ``eva`` (exact keys inside an aligned window
and one summary a chunk of keys for every window before it, a second pooled
operand whose gradients the backward kernel hands out, ``hetu_eva_*``;
``ops/eva.py``).

Supported: additive key mask [B, 1, 1, S] (BERT padding masks), causal,
any head dim ≤ 512 and any seq ≥ 128: the wrapper pads seq up to a block
multiple with -inf key-column masking and, in the 4-D layout, zero-pads d
to the 8-aligned [32, 512] kernel envelope, then slices the output
(padding/slicing sit OUTSIDE the custom_vjp, so jnp.pad's own VJP zeroes
the padded rows' cotangents and the gradients stay exact).  Returns None
only for truly unsupported cases (d > 512, short seqs where the O(S^2)
composition is cheaper, non-[B,1,1,S] masks) so callers fall back to the
jnp composition (ops/attention.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import telemetry
from .dispatch import counted, interpret, kept, named

_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453

_BLOCK_Q = 512
_BLOCK_K = 512
_NEG_INF = -1e30
_LANES = 128


def unsupported(q, k, v, mask=None, dropout_keep=1.0, window=None,
                block_diffusion=None, eva=None):
    """Why the kernel cannot take these ``[B, H, S, D]`` operands, or None
    when it can.  Callers that fall back to the jnp composition record this
    string (ops/pallas/dispatch.py).  ``window``: the keys a position sees,
    its own among them (``_fwd``); with one, dropout inside the kernel is not
    built.  ``block_diffusion``: the block length of the block-diffusion mask
    (``_fwd_kernel``'s ``bd``); with it neither dropout nor a window nor a key
    mask is built, a block is a power of two (its index is a shift) of at
    most half the smallest tile, and a half is at least one tile of rows.
    ``eva = (window, chunk)`` (``_fwd_kernel``'s ``eva``): exact keys inside an
    aligned window, one summary a chunk for the windows before it; with it no
    dropout, key mask or grouped queries are built, the window is whole tiles
    of 128 rows, a chunk divides 128 and a window's summaries are whole
    packed sublane tiles (16 rows)."""
    if window is not None and dropout_keep < 1.0:
        return "window_with_dropout"
    if eva is not None:
        for reason, holds in (
                ("eva_with_dropout", dropout_keep < 1.0),
                ("eva_with_mask", mask is not None),
                ("eva_with_window", window is not None
                 or block_diffusion is not None),
                ("eva_window_not_128_aligned", eva[0] % _LANES),
                ("eva_chunk_not_dividing_128", _LANES % eva[1]),
                ("eva_window_summaries_not_16_aligned",
                 eva[0] // eva[1] % 16),
                ("eva_grouped_queries",
                 q.ndim == 4 and k.ndim == 4 and k.shape[1] != q.shape[1])):
            if holds:
                return reason
    if block_diffusion is not None:
        for reason, holds in (
                ("block_diffusion_with_dropout", dropout_keep < 1.0),
                ("block_diffusion_with_window", window is not None),
                ("block_diffusion_with_mask", mask is not None),
                ("block_not_a_power_of_two",
                 block_diffusion & (block_diffusion - 1)),
                ("block>64", block_diffusion > 64),
                ("half<128", q.ndim == 4 and q.shape[2] < 256)):
            if holds:
                return reason
    # keys and values of fewer heads than the queries (grouped queries): only
    # as the [B, S, KV*D] that ``heads_view`` is given, read in place
    if (q.ndim != 4 or k.ndim != 4 or v.ndim != 4
            or (k.shape[0],) + k.shape[2:] != (q.shape[0],) + q.shape[2:]
            or q.shape[1] % k.shape[1] or v.shape[:3] != k.shape[:3]):
        return "not_self_attention_4d"
    b, h, s, d = q.shape
    if k.shape[1] != h and d % _LANES:
        return "grouped_head_dim_not_128_aligned"
    d = max(d, v.shape[3])
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


def pair_view_unsupported(num_heads, num_kv_heads, head_dim,
                          dropout_keep=1.0, mask=None):
    """Why a differential layer's PAIRS (``layers/attention.py
    DifferentialAttention``: query heads ``(2 i, 2 i + 1)`` side by side on one
    value of ``2 head_dim``) are not read in place as heads of ``2 head_dim``
    on ``num_kv_heads / 2`` key heads, or None when they are: what that view
    really asks is a head of 64 or a multiple (a pair is then whole lane
    tiles, which ``unsupported`` asks of grouped queries:
    ``grouped_head_dim_not_128_aligned``), an even number of query and of key
    heads, no dropout on the probabilities and no key mask (neither is built
    into the pair's combination)."""
    for reason, holds in (
            ("pair_heads_odd", num_heads % 2 or num_kv_heads % 2),
            ("pair_head_dim_not_64_aligned", head_dim % (_LANES // 2)),
            ("pair_with_dropout", dropout_keep < 1.0),
            ("pair_with_key_mask", mask is not None)):
        if holds:
            return reason
    return None


def heads_view(x, num_heads):
    """The shape ``[B, H, S, D]`` of which ``x`` ``[B, S, H*D]`` is a view
    (no array: what ``unsupported`` and the mesh plan read)."""
    b, s, width = x.shape
    return jax.ShapeDtypeStruct((b, num_heads, s, width // num_heads),
                                x.dtype)


def heads_views(q, k, v, num_heads):
    """``heads_view`` of q ``[B, S, H*D]`` and of k, v ``[B, S, KV*D]``: the
    key heads are as many as k's width holds of q's ``H`` (``rep = H / KV``
    is read from the widths)."""
    kv_heads = num_heads // (q.shape[-1] // k.shape[-1])
    return (heads_view(q, num_heads), heads_view(k, kv_heads),
            heads_view(v, kv_heads))


def heads_per_program(num_heads, head_dim):
    """Heads one program takes when ``[B, S, H*D]`` is read in place: as
    many as fill the 128 lanes of a block; 0 when the heads cannot be cut
    out of that array in lane-aligned groups (head sizes 80 or 96, a head
    count the group does not divide), which is the ``[B, H, S, D]`` walk's
    case."""
    if head_dim % _LANES == 0:
        return 1
    if head_dim < 32 or _LANES % head_dim:
        return 0
    group = _LANES // head_dim
    return 0 if num_heads % group else group


class _Walk(NamedTuple):
    """How the kernels address one call's tensors.  ``bshd`` is ``[B, S,
    H*D]`` read in place, ``group`` heads a program; ``bhsd`` is ``[B, H, S,
    D]`` through its free ``[B*H, S, D]`` view, one head a program.  Either
    way a kernel sees blocks ``(1, rows, group*dim)`` on the grid ``(batch,
    heads/group, row blocks)``.  ``rep`` query heads read one key head
    (``bshd`` with heads of whole lane tiles): K and V are ``[B, S,
    heads/rep*dim]`` and program ``hg`` reads their lane block ``hg // rep``
    (``kv_rows``)."""
    layout: str
    batch: int
    heads: int
    dim: int
    group: int
    rep: int = 1

    @property
    def width(self):
        return self.group * self.dim

    def seq(self, x):
        return x.shape[1 if self.layout == "bshd" else 2]

    def flat(self, x):
        if self.layout == "bshd":
            return x
        return x.reshape(self.batch * self.heads, x.shape[2], self.dim)

    def unflat(self, x):
        if self.layout == "bshd":
            return x
        return x.reshape(self.batch, self.heads, x.shape[1], self.dim)

    def rows(self, pick):
        """Index map of a row block for the program ``(b, hg, t)``;
        ``pick(t)`` is the block's index along the rows."""
        if self.layout == "bshd":
            return lambda b, hg, t: (b, pick(t), hg)
        heads = self.heads
        return lambda b, hg, t: (b * heads + hg, pick(t), 0)

    def kv_rows(self, pick):
        """``rows`` for a block of K or V: the key head of the program's
        query head."""
        if self.rep == 1:
            return self.rows(pick)
        rep = self.rep
        return lambda b, hg, t: (b, pick(t), hg // rep)


def _walk(q, num_heads, k=None):
    """The walk of ``q``; with ``k``, of ``q`` over the keys ``k``, which may
    be narrower."""
    if q.ndim == 4:
        b, h, _, d = q.shape
        return _Walk("bhsd", b, h, d, 1)
    b, _, width = q.shape
    d = width // num_heads
    rep = 1 if k is None else width // k.shape[-1]
    assert rep == 1 or d % _LANES == 0, (q.shape, k.shape, num_heads)
    return _Walk("bshd", b, num_heads, d, heads_per_program(num_heads, d),
                 rep)


def _walks(q, k, v, num_heads):
    """``(walk of q over k, walk of v)``: v's heads are k's."""
    w = _walk(q, num_heads, k)
    return w, _walk(v, num_heads and num_heads // w.rep)


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


def tile_keep(shape, seed_ref, tile, keep_prob):
    """The deterministic keep mask for one prob tile.  BOTH kernels must
    obtain masks through this single helper — the backward replays the
    forward's masks purely by reseeding with the same tile index."""
    pltpu.prng_seed(seed_ref[0], tile)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits < _keep_threshold(keep_prob)


def _only_head(x, h, dim, group):
    """``x`` (rows, group*dim) with the lanes of every head but ``h``
    zeroed; ``x`` itself where a program has one head."""
    if group == 1:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lane >= h * dim) & (lane < (h + 1) * dim), x,
                     jnp.zeros_like(x))


def _nt(a, b):
    """a @ b^T with f32 accumulation.  Operands stay in their storage
    dtype (bf16 models hit the MXU's bf16 rate — pre-casting to f32
    forced f32-rate matmuls, ~4x slower); bf16->f32 is exact, so the
    numerics on the input side are identical."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _nn(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _tn(a, b):
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


# -- the block-diffusion mask ----------------------------------------------

_FAR = 1 << 30


def _bd_edges(block, rows, cols):
    """``edge(strict, wide)`` for a tile of ``rows x cols`` that starts at a
    multiple of the block in both directions (the mask's tiles under an edge
    all lie on a half's diagonal): the pairs whose key's block comes before
    the query's (``strict`` 1) or not after it (0), all of them (``wide`` 1)
    or those of the query's own block alone (0).  ``strict`` and ``wide`` may
    be traced scalars; the block is a power of two."""
    shift = block.bit_length() - 1
    rb, cb = (jax.lax.shift_right_logical(
        jax.lax.broadcasted_iota(jnp.int32, (rows, cols), axis), shift)
        for axis in (0, 1))

    def edge(strict, wide):
        return (cb + strict <= rb) & (cb + wide * _FAR >= rb)
    return edge


def _bd_tiles(nh):
    """``{pass: (walked, visible)}`` of the block-diffusion mask over ``2 nh``
    square tiles a side, a head: the tiles the forward kernel's key loops and
    the backward kernel's query loops walk (the loops of ``_fwd_kernel`` and
    ``_bwd_kernel``, counted as they run) against the tiles that hold a
    visible pair (the four rules on whole tiles: a tile holds at least two
    whole blocks, ``unsupported``)."""
    def visible(qi, kj):
        (qn, qt), (kn, kt) = divmod(qi, nh), divmod(kj, nh)
        if kn:                  # noised keys: their own noised queries
            return bool(qn) and qt == kt
        return kt <= qt         # clean keys: a tile holds whole blocks
    seen = sum(visible(qi, kj) for qi in range(2 * nh)
               for kj in range(2 * nh))
    forward = sum(t + 1 + noised for noised in (0, 1) for t in range(nh))
    backward = sum(2 * (nh - t - 1) + 2 for t in range(nh)) + nh
    return {"forward": (forward, seen), "backward": (backward, seen)}


# -- forward ---------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, seed_ref, offs_ref,
                o_ref, lse_ref, *, scale, causal, block_k, q_len, k_len,
                keep_prob, heads, group, empty_lse_neg=False, window=None,
                back=0, bd=None, eva=None, pooled=None):
    """``eva = (W, n, rows)`` (static; causal, square tiles, no mask, offsets,
    dropout or window) with ``pooled = (ks_ref, vs_ref)``: windows of ``W``
    keys aligned at multiples of ``W``, ``n`` summaries a window (one a chunk
    of ``W / n`` keys), read ``rows`` at a time.  Query tile ``qi`` of window
    ``w`` walks the summaries of the windows before ``w`` whole, then its own
    window's key tiles up to the diagonal whole and the diagonal tile under
    its edge, all under ONE running maximum and sum: no tile of the walk is
    masked but the diagonal one (``_eva_tiles``).

    ``bd = (K, nh)`` (static; not causal, no mask, no offsets): the
    block-diffusion mask over ``2 nh`` square tiles of rows, a clean copy of a
    sequence in the first ``nh`` and its noised copy in the rest, blocks of
    ``K`` tokens (``b(i) = i // K`` on a token's index in its half): clean on
    clean ``b(j) <= b(i)``, noised on clean ``b(j) < b(i)``, noised on noised
    ``b(j) == b(i)``, clean on noised never.  Query tile ``t`` of either half
    walks the clean key tiles ``0 .. t - 1`` whole, the clean tile ``t`` under
    its edge and, noised, its own noised tile under the block diagonal: no
    tile without a visible pair (``_bd_tiles``).

    ``window`` (static; causal, no offsets): row ``i`` sees the keys ``j``
    with ``0 <= i - j < window``.  The key loop then starts at the first block
    that holds such a key, only the blocks that the window's edge or the
    diagonal crosses are masked, and ``k_ref`` / ``v_ref`` hold the ``back``
    rows before the query block and the block's own (``_fwd``), not all keys.

    offs_ref (optional SMEM int32[2] = [q_off, k_off]): GLOBAL sequence
    offsets of the local q/k blocks — the ring-attention path attends a
    rotating remote K/V block, so causal masking compares global positions.
    ``empty_lse_neg``: blockwise callers need lse=-inf semantics for rows
    with no live key in THIS block (so the cross-block logaddexp combine
    ignores them); self-attention callers need +inf (see comment below)."""
    b, hg, qi = (pl.program_id(a) for a in range(3))
    bq, width = q_ref.shape[1], q_ref.shape[2]
    dim = width // group
    # v and o are as wide as q and k, or (one head a program) of a head size
    # of their own: scores over q's width, the context over v's
    v_width = v_ref.shape[2]
    q_all = q_ref[0]                                  # (bq, g*d)
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
    if window is not None:
        # the first key block a row of this query block sees, the first that
        # every row sees whole, and the block k_ref's first row lies in
        j_lo = jax.lax.max(0, (qi * bq - window + 1) // block_k)
        j_in = jax.lax.max(j_lo, -((window - (qi + 1) * bq) // block_k))
        held_from = jax.lax.max(0, qi * bq - back) // block_k

    def make_body(q, h, masked, see=None, summaries=0):
        """``summaries``: the rows of a tile of ``pooled``'s refs, which the
        body then reads in place of K's and V's."""
        bh = b * heads + hg * group + h

        def body(j, carry):
            m, l, acc = carry
            def at():   # block j's first row in k_ref / v_ref
                return (j if window is None else j - held_from) * block_k
            if summaries:
                rows = pl.ds(j * summaries, summaries)
                kb = pooled[0][0, rows, :]
                vb = _only_head(pooled[1][0, rows, :], h, dim, group)
            else:
                kb = k_ref[0, pl.ds(at(), block_k), :]
                vb = _only_head(v_ref[0, pl.ds(at(), block_k), :],
                                h, dim, group)
            # scores tracked in BASE-2 units (s2 = s * log2(e)): exp2 is
            # the VPU's native exponential; lse converts back to natural
            # units at the end so the backward's exp(s - lse) contract is
            # unchanged
            s = _nt(q, kb) * (scale * _LOG2E)
            if mask_ref is not None:
                s = s + (mask_ref[0, 0,
                                  pl.ds(j * block_k, block_k)][None, :]
                         * _LOG2E)
            if causal and masked:
                col = (k_off + j * block_k
                       + jax.lax.broadcasted_iota(jnp.int32,
                                                  (bq, block_k), 1))
                seen = row >= col
                if window is not None:
                    seen = seen & (row - col < window)
                s = jnp.where(seen, s, _NEG_INF)
            elif see is not None:
                s = jnp.where(see, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            p = jnp.exp2(s - m_new[:, None])
            alpha = jnp.exp2(m - m_new)
            # l accumulates UN-dropped sums: O = dropout(P_norm) @ V
            l_new = l * alpha + jnp.sum(p, axis=1)
            if keep_prob < 1.0:
                nq = q_len // bq
                keep = tile_keep(p.shape, seed_ref,
                                  _tile_index(bh, qi, j, nq, nk), keep_prob)
                p = jnp.where(keep, p, 0.0)    # 1/keep_prob: at the end
            acc_new = acc * alpha[:, None] + _nn(p.astype(vb.dtype), vb)
            return m_new, l_new, acc_new
        return body

    out = None
    for h in range(group):
        q = _only_head(q_all, h, dim, group)
        m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((bq,), jnp.float32)
        acc0 = jnp.zeros((bq, v_width), jnp.float32)
        if bd is not None:
            # the clean tiles before this one whole; then the clean tile t
            # under its edge and, for a noised query tile, its own noised
            # tile under the block diagonal, LAST: a row of the first block
            # sees nothing of the clean tile, and what that tile left in its
            # sums is scaled to nothing by the first key it does see
            nh = bd[1]
            noised = (qi >= nh).astype(jnp.int32)
            t = qi - noised * nh
            edge = _bd_edges(bd[0], bq, block_k)

            def under_edge(r, carry):
                own = r * noised
                return make_body(q, h, False, edge(noised - own, 1 - own))(
                    t + r * nh, carry)
            carry = jax.lax.fori_loop(0, t, make_body(q, h, False),
                                      (m0, l0, acc0))
            m, l, acc = jax.lax.fori_loop(0, 1 + noised, under_edge, carry)
        elif eva is not None:
            # the summaries of the windows before this one, then this
            # window's key tiles before the diagonal, then the diagonal's
            span, n, rows = eva
            w = (qi * bq) // span
            carry = jax.lax.fori_loop(
                0, w * (n // rows),
                make_body(q, h, False, summaries=rows), (m0, l0, acc0))
            carry = jax.lax.fori_loop(w * (span // block_k), qi,
                                      make_body(q, h, False), carry)
            m, l, acc = make_body(q, h, True)(qi, carry)
        elif window is not None:
            # [j_lo, inside): the window's edge crosses; [inside, diagonal):
            # every pair is seen; [diagonal, nk_causal): the diagonal crosses
            diagonal = jax.lax.clamp(j_lo, (qi * bq) // block_k, nk_causal)
            inside = jax.lax.clamp(j_lo, j_in, diagonal)
            carry = jax.lax.fori_loop(j_lo, inside, make_body(q, h, True),
                                      (m0, l0, acc0))
            carry = jax.lax.fori_loop(inside, diagonal,
                                      make_body(q, h, False), carry)
            m, l, acc = jax.lax.fori_loop(diagonal, nk_causal,
                                          make_body(q, h, True), carry)
        elif causal and nk > 8:
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
            carry = jax.lax.fori_loop(0, n_full, make_body(q, h, False),
                                      (m0, l0, acc0))
            m, l, acc = jax.lax.fori_loop(n_full, nk_causal,
                                          make_body(q, h, True), carry)
        else:
            m, l, acc = jax.lax.fori_loop(0, nk_causal,
                                          make_body(q, h, True),
                                          (m0, l0, acc0))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        # acc is zero outside head h's lanes (V's other lanes were zeroed),
        # so the heads' parts add up to the one tile that is stored
        part = acc * ((1.0 / keep_prob) / l_safe)[:, None]
        out = part if out is None else out + part
        m = m * _LN2    # back to natural-log units for the stored lse
        # fully-masked rows (l == 0, every key at -inf): output is 0; store
        # lse = +large so the backward's p = exp(s - lse) underflows to 0 —
        # storing m (≈ -1e30) instead would give p = exp(0) = 1 everywhere
        # and garbage dq/dk/dv for the row.  Blockwise (ring) callers
        # instead want -large: their backward uses the COMBINED lse (never
        # empty for a causal row), and the fwd combine must treat this
        # block as weightless.
        empty = _NEG_INF if empty_lse_neg else -_NEG_INF
        lse = jnp.where(l == 0.0, empty, m + jnp.log(l_safe))
        lse_ref[0, 0, h] = lse.astype(jnp.float32)
    o_ref[0] = out.astype(o_ref.dtype)


def _make_kern(base, n_in, has_mask, has_seed, has_offs, **consts):
    """Adapts a kernel with optional (mask_ref, seed_ref, offs_ref) slots
    after its ``n_in`` tensor operands to the positional ref list
    pallas_call passes (operands, outputs, scratch)."""

    def kern(*refs):
        ins, rest = list(refs[:n_in]), list(refs[n_in:])
        mask_ref = rest.pop(0) if has_mask else None
        seed_ref = rest.pop(0) if has_seed else None
        offs_ref = rest.pop(0) if has_offs else None
        base(*ins, mask_ref, seed_ref, offs_ref, *rest, **consts)

    return kern


def _extras(walk, mask, keep_prob, seed, offsets, sk):
    """The optional operands, in ``_make_kern``'s order, and their specs:
    the ``[B, 1, 1, Sk]`` mask follows the batch row, seed and offsets are
    scalars in SMEM."""
    args, specs = [], []
    if mask is not None:
        args.append(mask.reshape(walk.batch, 1, sk).astype(jnp.float32))
        specs.append(pl.BlockSpec((1, 1, sk), lambda b, hg, t: (b, 0, 0)))
    if keep_prob < 1.0:
        args.append(seed.reshape(1).astype(jnp.int32))
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if offsets is not None:
        args.append(offsets)
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    return args, specs


def _compiler_params(resident_bytes):
    """Scoped VMEM from the shapes: the blocks and scratch a program holds
    (blocks are double-buffered) beside its f32 (block_q, block_k) tiles.
    Mosaic's default 16 MiB holds BERT's 512 rows, not the whole-sequence
    blocks of a 4,096-row call."""
    return pltpu.CompilerParams(vmem_limit_bytes=int(
        min(100 << 20, (24 << 20) + 2 * resident_bytes)))


def _kernel_name(which, window, bd, eva=None):
    """The pass's kernel: a name of its own with a window or with summaries
    (their events are not the flash passes'), the pass's name and ``_bd``
    under the block-diffusion mask (its events are)."""
    if eva is not None:
        return f"hetu_eva_{which}"
    if window is not None:
        return f"hetu_swa_{which}"
    return f"hetu_flash_{which}" + ("" if bd is None else "_bd")


def _eva_fwd_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, *rest, **consts):
    """``_fwd_kernel`` with the summaries' two refs behind K's and V's."""
    _fwd_kernel(q_ref, k_ref, v_ref, *rest, pooled=(ks_ref, vs_ref),
                **consts)


def _fwd(q, k, v, mask, causal, scale, keep_prob=1.0, seed=None,
         block_q=_BLOCK_Q, block_k=_BLOCK_K, offsets=None,
         empty_lse_neg=False, num_heads=None, window=None, bd=None,
         eva=None, pooled=()):
    """q: [b,h,sq,d]; k,v: [b,h,sk,d] (sq != sk in the blockwise/ring path,
    where ``offsets`` = int32[2] global [q_off, k_off]), or all three
    [b,s,h*d] with ``num_heads``.  Returns (o, lse [b, h/g, g, sq]).

    ``window`` (causal self-attention, no offsets, no dropout): the same body
    under the name ``hetu_swa_fwd``; a program holds of K and V the rows its
    query block can see, ``back`` rows before the block and the block's own,
    cut out where they lie, unless that is all of them.

    ``bd`` (``_fwd_kernel``; self-attention over both halves, square tiles,
    no mask, dropout, offsets or window): the same body under the name
    ``hetu_flash_fwd_bd``.

    ``eva`` (``_fwd_kernel``) with ``pooled = (ks, vs)``, the chunks'
    summaries in the layout of k and v, whole in a program: the same body
    under the name ``hetu_eva_fwd``."""
    w, wv = _walks(q, k, v, num_heads)
    sq, sk = w.seq(q), w.seq(k)
    q_spec = pl.BlockSpec((1, block_q, w.width), w.rows(lambda t: t))
    o_spec = pl.BlockSpec((1, block_q, wv.width), w.rows(lambda t: t))
    held, consts = sk, {}
    if window is not None:
        assert causal and offsets is None and keep_prob >= 1.0 and sq == sk
        assert block_q % block_k == 0, (block_q, block_k)
        back = -(-(window - 1) // block_k) * block_k
        held = min(sk, back + block_q)
        consts = dict(window=window, back=back if held < sk else sk)
    if bd is not None:
        assert (not causal and window is None and offsets is None
                and mask is None and keep_prob >= 1.0 and sq == sk
                and block_q == block_k and sq == 2 * bd[1] * block_q), (
            bd, sq, sk, block_q, block_k)
        consts = dict(bd=bd)
    pooled_specs = []
    if eva is not None:
        assert (causal and window is None and bd is None and offsets is None
                and mask is None and keep_prob >= 1.0 and sq == sk
                and block_q == block_k and w.rep == 1
                and eva[0] % block_k == 0 and eva[1] % eva[2] == 0), (
            eva, sq, sk, block_q, block_k)
        consts = dict(eva=eva)
        pooled_specs = [pl.BlockSpec((1, w.seq(x), x_walk.width),
                                     w.rows(lambda t: 0))
                        for x, x_walk in zip(pooled, (w, wv))]
    if held < sk:
        # addressed by element, not by block (Mosaic: all dimensions or
        # none): the band starts where it starts
        def band(width):
            at = w.kv_rows(lambda t: pl.multiple_of(
                jnp.maximum(t * block_q - back, 0), block_k))

            def index(b, hg, t):
                lead, row, lane = at(b, hg, t)
                return lead, row, lane * width
            return pl.BlockSpec(tuple(map(pl.Element, (1, held, width))),
                                index)
        k_spec, v_spec = band(w.width), band(wv.width)
    else:
        k_spec = pl.BlockSpec((1, sk, w.width), w.kv_rows(lambda t: 0))
        v_spec = pl.BlockSpec((1, sk, wv.width), w.kv_rows(lambda t: 0))
    extra_args, extra_specs = _extras(w, mask, keep_prob, seed, offsets, sk)
    kern = _make_kern(_fwd_kernel if eva is None else _eva_fwd_kernel,
                      3 + len(pooled), mask is not None, keep_prob < 1.0,
                      offsets is not None,
                      scale=scale, causal=causal, block_k=block_k,
                      q_len=sq, k_len=sk, keep_prob=keep_prob,
                      heads=w.heads, group=w.group,
                      empty_lse_neg=empty_lse_neg, **consts)
    groups = w.heads // w.group
    item = q.dtype.itemsize
    o, lse = pl.pallas_call(
        kern,
        name=_kernel_name("fwd", window, bd, eva),
        interpret=interpret(),
        grid=(w.batch, groups, sq // block_q),
        in_specs=[q_spec, k_spec, v_spec] + pooled_specs + extra_specs,
        out_specs=[
            o_spec,
            pl.BlockSpec((1, 1, w.group, block_q),
                         lambda b, hg, t: (b, hg, 0, t)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(
                w.flat(q).shape[:-1] + (w.rep * wv.flat(v).shape[-1],),
                q.dtype),
            jax.ShapeDtypeStruct((w.batch, groups, w.group, sq),
                                 jnp.float32),
        ],
        compiler_params=_compiler_params(
            (block_q + held + sum(w.seq(x) for x in pooled[:1]))
            * (w.width + wv.width) * item),
    )(w.flat(q), w.flat(k), wv.flat(v),
      *(x_walk.flat(x) for x, x_walk in zip(pooled, (w, wv))), *extra_args)
    return wv.unflat(o), lse


# -- backward --------------------------------------------------------------

def _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, mask_ref,
                seed_ref, offs_ref, dq_ref, dk_ref, dv_ref, dq_acc, *,
                scale, causal, block_q, q_len, k_len, keep_prob, heads,
                group, window=None, bd=None, eva=None, pooled=None):
    """One key block of one head group: every tile (query block i, this
    key block) is formed once and feeds dV, dK and dQ[i].  ``eva``
    (``_fwd_kernel``; the caller passes ``causal`` False and the diagonal
    tile gets its edge here) with ``pooled = (ks_ref, vs_ref, dks_ref,
    dvs_ref)``: the grid's last axis holds the key tiles and BEHIND them the
    summaries' tiles; a key tile walks its own query tile under the edge and
    the rest of its window whole, a summaries' tile the query tiles of every
    later window whole, and writes ``d k^`` and ``d v^``.  ``window``
    (``_fwd_kernel``): the query loop ends with the last block that holds a
    row which sees one of these keys.  ``bd`` (``_fwd_kernel``): a clean key
    tile ``t`` walks the query tiles ``t`` of both halves under their edges
    and the tiles behind them whole, a noised one its own query tile."""
    b, hg, kj = (pl.program_id(a) for a in range(3))
    bk, width = k_ref.shape[1], k_ref.shape[2]
    dim = width // group
    nq, nk = q_len // block_q, k_len // bk
    if eva is not None:
        return _eva_bwd_tiles(
            q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, dk_ref,
            dv_ref, dq_acc, pooled, scale=scale, block_q=block_q, nq=nq,
            nk=nk, group=group, eva=eva)
    k = k_ref[0]
    v = v_ref[0]
    k_heads = [_only_head(k, h, dim, group) for h in range(group)]
    q_off = offs_ref[0] if offs_ref is not None else 0
    k_off = offs_ref[1] if offs_ref is not None else 0
    col = (k_off + kj * bk
           + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)
           if causal else None)
    mblk = (mask_ref[0, 0, pl.ds(kj * bk, bk)][None, :] * _LOG2E
            if mask_ref is not None else None)

    @pl.when(kj == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def body(i, carry, see=None):
        dk, dv = carry
        rows = pl.ds(i * block_q, block_q)
        qb = q_ref[0, rows, :]
        dob = do_ref[0, rows, :]
        # D = rowsum(dO∘O) a head, from the rows already here
        od = dob.astype(jnp.float32) * o_ref[0, rows, :].astype(jnp.float32)
        dq = jnp.zeros((block_q, width), jnp.float32)
        for h in range(group):
            qh = _only_head(qb, h, dim, group)
            doh = _only_head(dob, h, dim, group)
            dsum = jnp.sum(_only_head(od, h, dim, group), axis=1)
            lse = lse_ref[0, 0, h, rows]
            # base-2 scores (exp2 = native VPU exponential; p identical)
            s = _nt(qh, k) * (scale * _LOG2E)
            if mblk is not None:
                s = s + mblk
            if causal:
                rr = (q_off + i * block_q
                      + jax.lax.broadcasted_iota(jnp.int32,
                                                 (block_q, bk), 0))
                seen = rr >= col
                if window is not None:
                    seen = seen & (rr - col < window)
                s = jnp.where(seen, s, _NEG_INF)
            elif see is not None:
                s = jnp.where(see, s, _NEG_INF)
            p = jnp.exp2(s - (lse * _LOG2E)[:, None])
            dp = _nt(doh, v)
            if keep_prob < 1.0:
                # replay the fwd tile (batch row x head, q-block i, kv-block
                # kj); the 1/keep_prob of both dropped tiles is applied to
                # the (rows, g*d) results: ds here is keep_prob x the true
                keep = tile_keep(
                    p.shape, seed_ref,
                    _tile_index(b * heads + hg * group + h, i, kj, nq, nk),
                    keep_prob)
                p_drop = jnp.where(keep, p, 0.0)
                dp = jnp.where(keep, dp, 0.0)
                dsum = dsum * keep_prob
            else:
                p_drop = p
            ds = (p * (dp - dsum[:, None])).astype(qb.dtype)
            dv = dv + _tn(p_drop.astype(dob.dtype), doh)
            dk = dk + _tn(ds, qh)
            dq = dq + _nn(ds, k_heads[h])
        dq_acc[rows, :] += dq
        return dk, dv

    zeros = jnp.zeros((bk, width), jnp.float32)
    v_zeros = (zeros if v_ref.shape[2] == width
               else jnp.zeros((bk, v_ref.shape[2]), jnp.float32))
    i_start = 0
    if causal:
        # q tiles strictly above the diagonal see none of this kv block;
        # with offsets the bound is dynamic (global positions)
        lo = (k_off + kj * bk - q_off) // block_q
        i_start = jax.lax.clamp(0, lo, nq) if offs_ref is not None else lo
    i_end = nq
    if window is not None:
        i_end = jax.lax.min(nq, ((kj + 1) * bk + window - 2) // block_q + 1)
    if bd is not None:
        nh = bd[1]
        clean = (kj < nh).astype(jnp.int32)
        t = kj - (1 - clean) * nh
        behind = nh - t - 1
        edge = _bd_edges(bd[0], block_q, bk)

        def whole(u, carry):
            # the query tiles behind t, of the clean half and then of the
            # noised one
            return body(u + t + 1 + (u >= behind) * (t + 1), carry)

        def under_edge(r, carry):
            # a clean key tile: the clean query tile t (not strict), then the
            # noised one (strict); a noised key tile: its own query tile
            return body(t + nh * (r + 1 - clean), carry,
                        edge(r * clean, clean))
        carry = jax.lax.fori_loop(0, 2 * behind * clean, whole,
                                  (zeros, v_zeros))
        dk, dv = jax.lax.fori_loop(0, 1 + clean, under_edge, carry)
    else:
        dk, dv = jax.lax.fori_loop(i_start, i_end, body, (zeros, v_zeros))
    dk_ref[0] = (dk * (scale / keep_prob)).astype(dk_ref.dtype)
    dv_ref[0] = (dv * (1.0 / keep_prob)).astype(dv_ref.dtype)

    @pl.when(kj == nk - 1)
    def _():
        dq_ref[0] = (dq_acc[...] * (scale / keep_prob)).astype(dq_ref.dtype)


def _eva_bwd_tiles(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
                   dk_ref, dv_ref, dq_acc, pooled, *, scale, block_q, nq, nk,
                   group, eva):
    """``_bwd_kernel`` under ``eva``: program ``kj < nk`` is key tile ``kj``,
    program ``nk + t`` the summaries' tile ``t``.  The transpose of the
    forward walk: what a tile of keys or of summaries feeds is dQ of the query
    tiles that walked it."""
    kj = pl.program_id(2)
    width = k_ref.shape[2]
    dim = width // group
    span, n, rows = eva
    ks_ref, vs_ref, dks_ref, dvs_ref = pooled
    last = pl.num_programs(2) - 1

    @pl.when(kj == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def walk(k, v, first, stop, edge):
        """(dk, dv) of the keys ``k``, ``v`` over the query tiles ``[first,
        stop)``, the first of them under the diagonal's edge where ``edge``."""
        k_heads = [_only_head(k, h, dim, group) for h in range(group)]
        cols = k.shape[0]

        def body(i, carry, see=None):
            dk, dv = carry
            at = pl.ds(i * block_q, block_q)
            qb, dob = q_ref[0, at, :], do_ref[0, at, :]
            od = dob.astype(jnp.float32) * o_ref[0, at, :].astype(jnp.float32)
            dq = jnp.zeros((block_q, width), jnp.float32)
            for h in range(group):
                qh = _only_head(qb, h, dim, group)
                doh = _only_head(dob, h, dim, group)
                dsum = jnp.sum(_only_head(od, h, dim, group), axis=1)
                s = _nt(qh, k) * (scale * _LOG2E)
                if see is not None:
                    s = jnp.where(see, s, _NEG_INF)
                p = jnp.exp2(s - (lse_ref[0, 0, h, at] * _LOG2E)[:, None])
                dp = _nt(doh, v)
                ds = (p * (dp - dsum[:, None])).astype(qb.dtype)
                dv = dv + _tn(p.astype(dob.dtype), doh)
                dk = dk + _tn(ds, qh)
                dq = dq + _nn(ds, k_heads[h])
            dq_acc[at, :] += dq
            return dk, dv

        zeros = (jnp.zeros((cols, width), jnp.float32),
                 jnp.zeros((cols, v.shape[1]), jnp.float32))
        if edge:
            seen = (jax.lax.broadcasted_iota(jnp.int32, (block_q, cols), 0)
                    >= jax.lax.broadcasted_iota(jnp.int32, (block_q, cols), 1))
            zeros = body(first, zeros, seen)
            first = first + 1
        return jax.lax.fori_loop(first, stop, body, zeros)

    @pl.when(kj < nk)
    def _():
        # the key tile's own query tile, then the rest of its window
        stop = jax.lax.min(nq, ((kj * block_q) // span + 1)
                           * (span // block_q))
        dk, dv = walk(k_ref[0], v_ref[0], kj, stop, True)
        dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(kj >= nk)
    def _():
        # the window these summaries are of; every query tile behind it
        first = (((kj - nk) * rows) // n + 1) * (span // block_q)
        dk, dv = walk(ks_ref[0], vs_ref[0], first, nq, False)
        dks_ref[0] = (dk * scale).astype(dks_ref.dtype)
        dvs_ref[0] = dv.astype(dvs_ref.dtype)

    @pl.when(kj == last)
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _eva_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, ks_ref,
                    vs_ref, mask_ref, seed_ref, offs_ref, dq_ref, dk_ref,
                    dv_ref, dks_ref, dvs_ref, dq_acc, **consts):
    """``_bwd_kernel`` with the summaries' refs behind the inputs and their
    gradients' behind the outputs."""
    _bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, mask_ref,
                seed_ref, offs_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                pooled=(ks_ref, vs_ref, dks_ref, dvs_ref), **consts)


def _bwd_impl(q, k, v, mask, o, lse, dout, causal, scale, keep_prob, seed,
              block_q=_BLOCK_Q, block_k=_BLOCK_K, offsets=None,
              num_heads=None, window=None, bd=None, eva=None, pooled=()):
    """(dq, dk, dv) in the operands' layout; ``lse`` as ``_fwd`` returns
    it.  With ``window`` the kernel is ``hetu_swa_bwd``, with ``bd``
    ``hetu_flash_bwd_bd``, with ``eva`` and ``pooled = (ks, vs)``
    ``hetu_eva_bwd``, which hands ``(dq, dk, dv, dks, dvs)`` out."""
    w, wv = _walks(q, k, v, num_heads)
    sq, sk = w.seq(q), w.seq(k)
    whole_q = pl.BlockSpec((1, sq, w.width), w.rows(lambda t: 0))
    whole_o = pl.BlockSpec((1, sq, wv.width), w.rows(lambda t: 0))
    # what is read of K and V lies at the key head, what is written of dK and
    # dV at the query head: with ``rep`` > 1 the two results are a QUERY head
    # wide and ``_flash_bwd`` sums each group
    def tile(t):
        return t
    tiles, pooled_specs, pooled_shapes = sk // block_k, [], []
    if eva is not None:
        assert (block_q == block_k and w.rep == 1 and offsets is None
                and mask is None and keep_prob >= 1.0), (eva, block_q, block_k)
        # the summaries' tiles stand behind the keys' on the grid's last axis:
        # a program of either kind leaves the other kind's blocks where they
        # were (an output block is written back when its index changes)
        nk, rows = tiles, eva[2]

        def tile(t):
            return jnp.minimum(t, nk - 1)
        pooled_specs = [pl.BlockSpec((1, rows, x.width), w.rows(
            lambda t: jnp.maximum(t - nk, 0))) for x in (w, wv)]
        pooled_shapes = [jax.ShapeDtypeStruct(x_walk.flat(x).shape, x.dtype)
                         for x, x_walk in zip(pooled, (w, wv))]
        tiles += w.seq(pooled[0]) // rows
        causal = False
    k_spec, v_spec = (pl.BlockSpec((1, block_k, x.width), w.rows(tile))
                      for x in (w, wv))
    k_read, v_read = (pl.BlockSpec((1, block_k, x.width),
                                   w.kv_rows(tile)) for x in (w, wv))
    lse_spec = pl.BlockSpec((1, 1, w.group, sq),
                            lambda b, hg, t: (b, hg, 0, 0))
    extra_args, extra_specs = _extras(w, mask, keep_prob, seed, offsets, sk)
    kern = _make_kern(_bwd_kernel if eva is None else _eva_bwd_kernel,
                      6 + len(pooled), mask is not None, keep_prob < 1.0,
                      offsets is not None,
                      scale=scale, causal=causal, block_q=block_q,
                      q_len=sq, k_len=sk, keep_prob=keep_prob,
                      heads=w.heads, group=w.group, window=window, bd=bd,
                      **({} if eva is None else {"eva": eva}))
    item = q.dtype.itemsize
    dq, dk, dv, *d_pooled = pl.pallas_call(
        kern, name=_kernel_name("bwd", window, bd, eva),
        interpret=interpret(),
        grid=(w.batch, w.heads // w.group, tiles),
        in_specs=[whole_q, k_read, v_read, whole_o, whole_o, lse_spec]
        + pooled_specs + extra_specs,
        out_specs=[whole_q, k_spec, v_spec] + pooled_specs,
        out_shape=[jax.ShapeDtypeStruct(w.flat(q).shape, q.dtype)]
        + [jax.ShapeDtypeStruct(
            x.flat(t).shape[:-1] + (w.rep * x.flat(t).shape[-1],), t.dtype)
           for x, t in ((w, k), (wv, v))] + pooled_shapes,
        scratch_shapes=[pltpu.VMEM((sq, w.width), jnp.float32)],
        compiler_params=_compiler_params(
            2 * (sq + block_k) * (w.width + wv.width) * item
            + sq * w.width * 4),
    )(w.flat(q), w.flat(k), wv.flat(v), wv.flat(o), wv.flat(dout), lse,
      *(x_walk.flat(x) for x, x_walk in zip(pooled, (w, wv))), *extra_args)
    grads = (w.unflat(dq), w.unflat(_group_sum(dk, w.rep, w.dim)),
             wv.unflat(_group_sum(dv, w.rep, wv.dim)))
    if eva is None:
        return grads
    return grads + tuple(x.unflat(t) for x, t in zip((w, wv), d_pooled))


def _group_sum(x, rep, dim):
    """dK or dV a QUERY head ``[B, S, H*dim]`` -> a key head ``[B, S,
    H/rep*dim]``: each key head's ``rep`` query heads added up in f32 with one
    rounding (what the transpose of a ``repeat_kv`` in front of the kernels
    would do), on slices of whole lane tiles: a view by heads would be a pass
    over HBM of its own."""
    if rep == 1:
        return x
    heads = [x[..., at:at + dim].astype(jnp.float32)
             for at in range(0, x.shape[-1], dim)]
    return jnp.concatenate(
        [sum(heads[at + 1:at + rep], heads[at])
         for at in range(0, len(heads), rep)], axis=-1).astype(x.dtype)


# -- custom-vjp wrapper ----------------------------------------------------
# the mask (None or [B,1,1,S]) and the dropout seed (a traced int32 tensor)
# are operands with zero cotangent.

def _blocks(block, window, bd=None):
    """The keywords of ``_fwd`` / ``_bwd_impl`` that a call's static plan
    sets: one block size for both sides, or with a window ``(block_q,
    block_k)``; under the block-diffusion mask ``bd`` too."""
    if bd is not None:
        return dict(block_q=block, block_k=block, bd=bd)
    if window is None:
        return dict(block_q=block, block_k=block)
    return dict(block_q=block[0], block_k=block[1], window=window)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, mask, seed, causal, scale, keep_prob, block, num_heads,
           window=None, bd=None):
    return _fwd(q, k, v, mask, causal, scale, keep_prob, seed,
                num_heads=num_heads, **_blocks(block, window, bd))[0]


def _flash_fwd(q, k, v, mask, seed, causal, scale, keep_prob, block,
               num_heads, window=None, bd=None):
    # named HERE, on the values the backward rule reads: a recomputed group
    # keeps them (``dispatch.KEPT``) and its backward pass runs no second
    # forward kernel
    o, lse = named("flash", *_fwd(q, k, v, mask, causal, scale, keep_prob,
                                  seed, num_heads=num_heads,
                                  **_blocks(block, window, bd)))
    return o, (q, k, v, mask, seed, o, lse)


def _flash_bwd(causal, scale, keep_prob, block, num_heads, window, bd, res,
               g):
    q, k, v, mask, seed, o, lse = res
    dq, dk, dv = _bwd_impl(q, k, v, mask, o, lse, g, causal, scale,
                           keep_prob, seed, num_heads=num_heads,
                           **_blocks(block, window, bd))
    # The additive mask is treated as NON-differentiable data (our graphs
    # build it from placeholder attention masks).  A learned attention bias
    # must use the jnp fallback path, which differentiates the bias.
    return (dq, dk, dv, None if mask is None else jnp.zeros_like(mask),
            jnp.zeros_like(seed))


_flash.defvjp(_flash_fwd, _flash_bwd)

# jitted, so that the layers of a model with one attention shape share one
# trace of the two kernel bodies (pallas_call itself traces its kernel anew
# at every call)
_flash_call = jax.jit(_flash, static_argnums=(5, 6, 7, 8, 9, 10, 11))


# -- summaries behind a window (EVA) ---------------------------------------
# q, k, v and the chunks' summaries (ks, vs: one row a chunk of every window
# but the last) are all differentiated; the plan is static.

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _eva(q, k, v, ks, vs, scale, block, num_heads, eva):
    return _fwd(q, k, v, None, True, scale, num_heads=num_heads,
                block_q=block, block_k=block, eva=eva, pooled=(ks, vs))[0]


def _eva_fwd(q, k, v, ks, vs, scale, block, num_heads, eva):
    # named as the flash kernels' are: a recomputed group keeps them
    o, lse = named("flash", *_fwd(q, k, v, None, True, scale,
                                  num_heads=num_heads, block_q=block,
                                  block_k=block, eva=eva, pooled=(ks, vs)))
    return o, (q, k, v, ks, vs, o, lse)


def _eva_bwd(scale, block, num_heads, eva, res, g):
    q, k, v, ks, vs, o, lse = res
    return _bwd_impl(q, k, v, None, o, lse, g, True, scale, 1.0, None,
                     block_q=block, block_k=block, num_heads=num_heads,
                     eva=eva, pooled=(ks, vs))


_eva.defvjp(_eva_fwd, _eva_bwd)
_eva_call = jax.jit(_eva, static_argnums=(5, 6, 7, 8))


def _eva_tiles(nq, span, n, rows):
    """``{pass: (whole, edge)}`` of the plan ``eva = (span, n, rows)`` in
    tiles (``span``: a window's key tiles; ``n`` summaries a window, ``rows``
    a tile of them) over ``nq`` square tiles of rows, as the kernels' loops
    run: the (query tile, key tile) pairs walked whole, keys ``("k", j)`` and
    summaries ``("s", j)``, and those walked under the diagonal's edge."""
    fwd_whole, fwd_edge, bwd_whole, bwd_edge = [], [], [], []
    for qi in range(nq):                          # _fwd_kernel
        w = qi // span
        fwd_whole += [(qi, ("s", j)) for j in range(w * (n // rows))]
        fwd_whole += [(qi, ("k", j)) for j in range(w * span, qi)]
        fwd_edge.append((qi, ("k", qi)))
    for kj in range(nq):                          # _eva_bwd_tiles, keys
        bwd_edge.append((kj, ("k", kj)))
        bwd_whole += [(i, ("k", kj)) for i in range(
            kj + 1, min(nq, (kj // span + 1) * span))]
    for t in range((nq - 1) // span * (n // rows)):     # and summaries
        bwd_whole += [(i, ("s", t)) for i in range(
            (t * rows // n + 1) * span, nq)]
    return {"forward": (fwd_whole, fwd_edge),
            "backward": (bwd_whole, bwd_edge)}


def eva_pairs(seq, window, chunk):
    """``(local, remote)``: the (query, key) and the (query, summary) pairs a
    head attends to over ``seq`` positions: position ``t`` of window ``w = t
    // window`` sees the keys ``window w .. t`` and the ``window / chunk``
    summaries of each window before ``w``."""
    full, rest = divmod(seq, window)
    local = full * window * (window + 1) // 2 + rest * (rest + 1) // 2
    remote = (window // chunk) * (window * full * (full - 1) // 2
                                  + rest * full)
    return local, remote


def _eva_plan(s, s_pad, window, chunk):
    """``(tile, eva, summaries read)`` of a call with ``eva = (window,
    chunk)`` over ``s`` positions padded to ``s_pad``: the largest tile that
    divides both the padded sequence and the window, the kernels' static
    ``(window, summaries a window, rows of a tile of them)``, and how many
    summaries the last window's queries see (the windows before it, whole).
    Counts the plan's pairs in ``hetu_eva_pairs_total{part}``."""
    tile = next(b for b in (512, 256, 128)
                if s_pad % b == 0 and window % b == 0)
    n = window // chunk
    rows = next(r for r in (512, 256, 128, 64, 32, 16) if n % r == 0)
    pairs = telemetry.get_registry().counter(
        "hetu_eva_pairs_total",
        "Trace-time (query, key) pairs a head attends to in the EVA calls "
        "planned, by part: local (exact keys of the query's own window) and "
        "remote (one summary a chunk of every window before it)",
        labels=("part",))
    for part, count in zip(("local", "remote"), eva_pairs(s, window, chunk)):
        pairs.labels(part=part).inc(count)
    return tile, (window, n, rows), (s_pad - 1) // window * n


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
    o, lse = _fwd(q, k, v, None, causal, float(scale), block_q=bq,
                  block_k=bk, offsets=offsets, empty_lse_neg=True)
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
    return _bwd_impl(q, k, v, None, o, lse.reshape(b, h, 1, sq), dout,
                     causal, float(scale), 1.0, None, block_q=bq,
                     block_k=bk, offsets=offsets)


#: (block_q, block_k) of a call with a window, by the window: the largest pair
#: that divides the sequence is taken (``_window_plan``)
WINDOW_BLOCKS = ((512, 512), (256, 256), (128, 128))


def _window_plan(s_pad, window):
    """``(block_q, block_k)`` of a call with a window over ``s_pad`` rows, and
    the share of the causal plan's tile area (blocks of ``_pad_plan``) that
    its key loop visits: the gauge ``hetu_attn_window_block_share``."""
    bq, bk = next(b for b in WINDOW_BLOCKS if s_pad % b[0] == 0)
    block = _pad_plan(s_pad)[1]
    visited = sum((q_lo + bq - 1) // bk + 1 - max(0, (q_lo - window + 1) // bk)
                  for q_lo in range(0, s_pad, bq)) * bq * bk
    causal = sum((q_lo + block - 1) // block + 1
                 for q_lo in range(0, s_pad, block)) * block * block
    share = visited / causal
    telemetry.get_registry().gauge(
        "hetu_attn_window_block_share",
        "Tile area the window attention kernel's key loop visits over the "
        "causal flash plan's, at the last call planned").set(share)
    return (bq, bk), share


def _halves(x, axis, rows):
    """``x`` with each half of its ``axis`` (a clean and a noised copy, one
    behind the other) padded with zeros, or cut back, to ``rows`` rows."""
    half = x.shape[axis] // 2
    if half == rows:
        return x
    two = x.reshape(x.shape[:axis] + (2, half) + x.shape[axis + 1:])
    if rows < half:
        two = jax.lax.slice_in_dim(two, 0, rows, axis=axis + 1)
    else:
        pad = [(0, 0)] * two.ndim
        pad[axis + 1] = (0, rows - half)
        two = jnp.pad(two, pad)
    return two.reshape(x.shape[:axis] + (2 * rows,) + x.shape[axis + 1:])


def _bd_plan(half, block_diffusion):
    """``(rows a half is padded to, tile, bd)`` of a call under the
    block-diffusion mask, and the gauge ``hetu_flash_tiles{mask, pass,
    tiles}``: the tiles a head's loops walk and the tiles that hold a visible
    pair (``_bd_tiles``), at the last call planned."""
    assert half % block_diffusion == 0, (
        "a half is whole blocks: a padded key then lies in a block behind "
        "every real query's", half, block_diffusion)
    rows, tile = _pad_plan(half)
    gauge = telemetry.get_registry().gauge(
        "hetu_flash_tiles",
        "Tiles a head's loops walk in a flash kernel under a mask that is "
        "neither causal nor a window (walked) and tiles that hold a visible "
        "pair (visible), by pass, at the last call planned",
        labels=("mask", "pass", "tiles"))
    for which, counts in _bd_tiles(rows // tile).items():
        for tiles, n in zip(("walked", "visible"), counts):
            gauge.labels(**{"mask": "block_diffusion", "pass": which,
                            "tiles": tiles}).set(n)
    return rows, tile, (int(block_diffusion), rows // tile)


def _count_entry(walk, v_dim, window=None, block_diffusion=None, eva=None):
    """Trace-time count of the walk taken, beside ``dispatch.record``'s
    count of the kernel-versus-jnp choice.  Values narrower (or wider) than
    the keys are the layout ``bhsd_v<head size of v>``; a call with a window
    adds ``_w<window>``, the block-diffusion mask ``_bd<block>``, summaries
    behind a window ``_eva<window>c<chunk>``, keys of fewer heads than the
    queries ``_kv<key heads>``."""
    telemetry.get_registry().counter(
        "hetu_flash_attention_entry_total",
        "Trace-time flash attention calls by operand layout and the heads "
        "one program takes",
        labels=("layout", "heads_per_program"),
    ).labels(layout=walk.layout + ("" if v_dim == walk.dim
                                   else f"_v{v_dim}")
             + ("" if window is None else f"_w{window}")
             + ("" if block_diffusion is None else f"_bd{block_diffusion}")
             + ("" if eva is None else "_eva{}c{}".format(*eva))
             + ("" if walk.rep == 1 else f"_kv{walk.heads // walk.rep}"),
             heads_per_program=str(walk.group)).inc()


def entries():
    """``{(layout, heads_per_program): count}`` of the calls traced so far
    (empty while telemetry is disabled)."""
    return {(lab["layout"], int(lab["heads_per_program"])): n
            for lab, n in counted("hetu_flash_attention_entry_total")}


def flash_attention(q, k, v, mask=None, causal=False, scale=None,
                    dropout_keep=1.0, seed=None, num_heads=None, window=None,
                    block_diffusion=None, eva=None, summaries=None):
    """Fused attention; returns None when shapes are unsupported so the
    caller falls back to the jnp composition (ops/attention.py).

    q, k, v are ``[B, H, S, D]``, or the projections' ``[B, S, H*D]`` with
    ``num_heads`` (read, and the context written, in place); the latter
    needs ``heads_per_program(num_heads, D)`` to be non-zero.
    ``dropout_keep`` < 1 applies attention-prob dropout in-kernel (TPU
    PRNG); ``seed`` must then be an int32/uint32 scalar array.  ``window``
    (with ``causal``): position ``i`` sees the ``window`` keys ``i - window +
    1 .. i``; the kernels then run as ``hetu_swa_fwd`` / ``hetu_swa_bwd`` and
    skip the blocks outside the band; a window that holds every key is no
    window.  ``block_diffusion`` (not ``causal``): the sequence is a clean copy
    of ``L`` tokens and then their noised copy, blocks of ``block_diffusion``
    tokens, under ``_fwd_kernel``'s ``bd`` mask; the kernels then run as
    ``hetu_flash_fwd_bd`` / ``hetu_flash_bwd_bd`` and walk the tiles that hold
    a visible pair alone.  ``eva = (window, chunk)`` (with ``causal``) and
    ``summaries = (ks, vs)``, one row a chunk in the layout of k and v:
    position ``i`` of window ``w = i // window`` sees the keys ``window w ..
    i`` and the summaries of the chunks of every window before ``w``, under
    one softmax; the kernels then run as ``hetu_eva_fwd`` / ``hetu_eva_bwd``;
    one window that holds every key is causal attention and reads no summary.
    """
    if window is not None:
        assert causal and window >= 1, (causal, window)
        if window >= (q.shape[1] if q.ndim == 3 else q.shape[2]):
            window = None
    if eva is not None:
        assert causal and summaries is not None, (causal, eva)
        if eva[0] >= (q.shape[1] if q.ndim == 3 else q.shape[2]):
            eva = None
    if q.ndim == 3:
        if not heads_per_program(num_heads, q.shape[-1] // num_heads):
            return None
        views = heads_views(q, k, v, num_heads)
    elif k.shape[1] != q.shape[1]:
        return None         # grouped queries come as [B, S, H*D] alone
    else:
        views = (q, k, v)
    if unsupported(*views, mask, dropout_keep, window,
                   block_diffusion, eva) is not None:
        return None
    if dropout_keep < 1.0 and seed is None:
        raise ValueError(
            "flash_attention: dropout_keep < 1 requires seed= (an int32 "
            "scalar array; the per-tile dropout masks derive from it)")
    w, wv = _walks(q, k, v, num_heads)
    dv = wv.dim
    # two head sizes (latent attention: keys wider than values) come as
    # [B, H, S, D], or in place as whole lane tiles each, one head a program
    assert dv == w.dim or q.ndim == 4 or w.group == wv.group == 1, (
        q.shape, v.shape)
    _count_entry(w, dv, window, block_diffusion, eva)
    s, d = w.seq(q), w.dim
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    if dropout_keep >= 1.0:
        seed = jnp.zeros((1,), jnp.int32)

    # pad into the kernel envelope; padding/slicing live OUTSIDE the
    # custom_vjp so jnp.pad's VJP zero-fills the padded rows' cotangents
    # and the gradients of the real region stay exact.  In-place heads are
    # 32, 64 or a multiple of 128 wide: never padded
    d_pad, dv_pad = (max(32, -(-x // 8) * 8) for x in (d, dv))
    s_pad, block = _pad_plan(s)
    bd = tokens = None
    if block_diffusion is not None:
        # each half padded by itself, so a padded key's block lies behind
        # every real query's and needs no mask; from here on ``s`` is the
        # padded length and the generic padding below sees nothing to do
        assert not causal and s % 2 == 0, (causal, s)
        tokens, axis = s // 2, 1 if q.ndim == 3 else 2
        rows, block, bd = _bd_plan(tokens, block_diffusion)
        q, k, v = (_halves(t, axis, rows) for t in (q, k, v))
        s = s_pad = 2 * rows
    if d_pad != d or dv_pad != dv or s_pad != s:
        pad = [(0, 0)] * q.ndim
        pad[1 if q.ndim == 3 else 2] = (0, s_pad - s)
        pad[-1] = (0, d_pad - d)
        q, k = (jnp.pad(t, pad) for t in (q, k))
        pad[-1] = (0, dv_pad - dv)
        v = jnp.pad(v, pad)
        if s_pad != s and not (causal and mask is None):
            # padded key columns must not attend; real causal rows never
            # see columns ≥ s, so pure-causal needs no mask
            base = (mask if mask is not None
                    else jnp.zeros((w.batch, 1, 1, s), jnp.float32))
            mask = jnp.pad(base, ((0, 0), (0, 0), (0, 0), (0, s_pad - s)),
                           constant_values=_NEG_INF)

    if window is not None:
        block = _window_plan(s_pad, window)[0]
    if eva is not None:
        # the summaries the last window's queries see, cut and padded as k
        # and v are: outside the custom_vjp, so the rest get zeros back
        block, plan, seen = _eva_plan(s, s_pad, *eva)
        ks, vs = (jax.lax.slice_in_dim(x, 0, seen, axis=q.ndim - 2)
                  for x in summaries)
        if q.ndim == 4:         # heads in place are never padded
            ks, vs = (jnp.pad(x, [(0, 0)] * 3 + [(0, width - x.shape[-1])])
                      for x, width in ((ks, d_pad), (vs, dv_pad)))
        out = _eva_call(q, k, v, ks, vs, float(scale), block, num_heads,
                        plan)
    else:
        out = _flash_call(q, k, v, mask, seed, causal, float(scale),
                          float(dropout_keep), block, num_heads, window, bd)
    # the context and the f32 log-sum-exp, one value a row and head
    kept("bd" if bd else "eva" if eva else "flash" if window is None
         else "swa",
         out.size * out.dtype.itemsize + out.size // dv_pad * 4)
    if d_pad != d or dv_pad != dv or s_pad != s:
        out = out[:, :s] if q.ndim == 3 else out[:, :, :s, :dv]
    if bd:
        out = _halves(out, 1 if q.ndim == 3 else 2, tokens)
    return out


def shard_seed(seed, axes):
    """Inside ``shard_map``: ``seed`` offset by this shard's index along
    the mesh ``axes``, so that shards do not repeat one dropout mask."""
    if not axes:
        return seed
    shard = jax.lax.axis_index(tuple(axes))
    return seed + shard.astype(seed.dtype) * jnp.asarray(
        -1640531535, seed.dtype)              # 0x9E3779B1, wraps in int32


def sharded_flash_attention(mesh, q, k, v, mask=None, *, batch_axes=(),
                            head_axes=(), seed=None, num_heads=None, **kw):
    """:func:`flash_attention` inside a GSPMD mesh program.

    ``pallas_call`` does not partition, and attention is local to one
    (batch row, head), so the kernel runs under ``shard_map`` on each
    device's own slice: q/k/v split their batch dim over ``batch_axes`` and
    their heads over ``head_axes`` (mesh axis names; both must divide) —
    dim 1 of ``[B, H, S, D]``, the last dim of ``[B, S, H*D]``, whose
    ``num_heads`` is the global count — the ``[B, 1, 1, S]`` mask follows
    the batch, and the dropout seed is offset by the shard's index so that
    shards do not repeat one mask.  Same return contract as
    ``flash_attention``."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    batch_axes, head_axes = tuple(batch_axes), tuple(head_axes)
    if q.ndim == 3:
        spec = P(batch_axes or None, None, head_axes or None)
        num_heads //= int(np.prod([mesh.shape[a] for a in head_axes] or [1]))
    else:
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
        if sd is not None:
            sd = shard_seed(sd, batch_axes + head_axes)
        return flash_attention(q, k, v, mask=m, seed=sd,
                               num_heads=num_heads, **kw)

    # pallas out_shapes carry no varying-axes annotations
    return shard_map(local, mesh=mesh, in_specs=tuple(specs),
                     out_specs=spec, check_vma=False)(*operands)
