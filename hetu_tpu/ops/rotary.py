"""Rotary position embeddings (RoPE), ALiBi biases, and GQA head repeat.

The reference's Llama family applies rotary embeddings inside its
flash-attn mixer (tools/Hetu-Galvatron/galvatron/models/llama/
LlamaModel_sequential.py:14 imports rotary_pos_embedding) and its
Baichuan-13B family uses ALiBi biases (models/baichuan/).  Here RoPE is a
pure pre-transform on q/k — the cos/sin tables are built from static
shapes, so XLA constant-folds them once per compile and fuses the rotation
into the surrounding projection matmuls; flash attention then runs
unchanged on the rotated tensors.

Conventions match huggingface's ``rotate_half`` (non-interleaved halves),
so HF Llama checkpoints import bit-tight (tests/test_torch_parity.py).
"""

from __future__ import annotations

import jax.numpy as jnp

from .base import simple_op


def _rope_tables(seq_len, dim, theta, pos_offset=0):
    # always f32 tables: bf16 positions past ~256 lose the low rotation
    # frequencies entirely
    pos = jnp.arange(pos_offset, pos_offset + seq_len, dtype=jnp.float32)
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    freqs = jnp.outer(pos, inv)                       # [S, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)    # [S, D]
    return jnp.cos(emb), jnp.sin(emb)


def _rotary(x, *, theta=10000.0, pos_offset=0, seq_axis=-2,
            rotary_dim=None):
    """Apply RoPE to [B, H, S, D] (HF rotate_half convention), or, with
    ``seq_axis=1``, to the [B, S, H, D] view of a projection's output.
    ``rotary_dim`` rotates the first ``rotary_dim`` of the ``D`` dimensions
    (frequencies ``theta^(-2i / rotary_dim)``) and passes the rest through
    (partial rotary: GPT-NeoX, Qwen3-Next)."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        assert 0 < rotary_dim < x.shape[-1] and rotary_dim % 2 == 0
        turned = _rotary(x[..., :rotary_dim], theta=theta,
                         pos_offset=pos_offset, seq_axis=seq_axis)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    d, s = x.shape[-1], x.shape[seq_axis]
    along = [1] * x.ndim
    along[seq_axis], along[-1] = s, d
    cos, sin = (t.reshape(along)
                for t in _rope_tables(s, d, theta, pos_offset))
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (xf * cos + rotated * sin).astype(x.dtype)


rotary_embedding_op = simple_op(_rotary, "rotary_embedding")


def _repeat_kv(x, *, n_rep):
    """[B, KV, S, D] -> [B, KV*n_rep, S, D] for grouped-query attention.

    Broadcast + reshape (not jnp.repeat): XLA lowers it to a view-like
    broadcast that fuses into the attention einsum instead of
    materializing the repeated K/V in HBM.
    """
    if n_rep == 1:
        return x
    b, kv, s, d = x.shape
    x = jnp.broadcast_to(x[:, :, None, :, :], (b, kv, n_rep, s, d))
    return x.reshape(b, kv * n_rep, s, d)


repeat_kv_op = simple_op(_repeat_kv, "repeat_kv")


def alibi_slopes(num_heads):
    """Per-head ALiBi slopes (Press et al., the published closed form)."""
    import math

    def pow2_slopes(n):
        start = 2.0 ** (-(2.0 ** -(math.log2(n) - 3)))
        return [start * (start ** i) for i in range(n)]

    if math.log2(num_heads).is_integer():
        return pow2_slopes(num_heads)
    closest = 2 ** math.floor(math.log2(num_heads))
    extra = pow2_slopes(2 * closest)[0::2][: num_heads - closest]
    return pow2_slopes(closest) + extra


def _alibi_bias(q, *, num_heads):
    """Additive [1, H, S, S] ALiBi bias from a [B, H, S, D] query.

    Only the linear -slope*(i-j) term; the causal cut is the attention
    op's ``causal`` flag (reference Baichuan builds both into one mask).
    """
    s = q.shape[-2]
    slopes = jnp.asarray(alibi_slopes(num_heads), dtype=jnp.float32)
    rel = jnp.arange(s, dtype=jnp.float32)[None, :] \
        - jnp.arange(s, dtype=jnp.float32)[:, None]   # j - i  (<= 0 past)
    bias = slopes[:, None, None] * rel[None, :, :]    # [H, S, S]
    return bias[None].astype(q.dtype)


alibi_bias_op = simple_op(_alibi_bias, "alibi_bias")
