"""Compressed convolutional attention (CCA, Zyphra, arXiv:2510.04476; the
attention sublayer of ZAYA1, arXiv:2511.17127): queries, keys and values are
projected DOWN into a latent of ``H`` query and ``J`` key heads of ``d``, the
queries and keys are mixed there over time and over a head's channels, and
attention runs on the latent itself; only ``W_o`` leads back to the hidden
size.  On ``u [B, S, C]`` (the sublayer's normed input), ``g = H / J``, ``u^-_t
= u_(t-1)`` and zeros before position 0, no bias on the products::

    z   = u [W_q | W_k]                                  [B, S, (H + J) d]
    v   = [ u W_v1 | u^- W_v2 ]     the first J / 2 key heads the current
                                    token's, the last J / 2 the previous one's
    m^q_h = (q~_h + k~_(h div g)) / 2;   m^k_j = mean over group j of m^q_h
    z'_t  = a_0 z_(t-1) + a_1 z_t + b                    depthwise, two taps
    z''_t[h] = z'_(t-1)[h] A_0^h + z'_t[h] A_1^h + b'[h]   a head's d channels mixed
    q = z''[:, :H d] + m^q;     k = z''[:, H d:] + m^k
    q^_h = sqrt(d) q_h / |q_h|;   k^_j = exp(t_j) sqrt(d) k_j / |k_j|
    rotary on the first ``rotary_dim`` lanes of a head of q^ and k^
    o_h = softmax_causal(q^_h k^_(h div g)^T / sqrt(d)) v_(h div g);  y = o W_o

The products, rotary and the attention op go where ``MultiHeadAttention``'s
go: under ``hetu_attn``, on the projections' ``[B, S, heads d]`` in place (the
flash and rotary kernels read a query head's key head where it lies; off a
TPU, and where a head is not whole lane tiles, their ``jax.numpy`` forms walk
the same arrays through free views, so this layer has ONE graph).  What CCA
adds between the down-projections and the rotary stands under ONE scope,
``hetu_cca``, forward and backward: the depthwise taps (``ops/causal_conv.py``
with ``act=None``), the head-mixing taps, the two means, the sums, the two L2
norms with the temperature (``_mix``, ``jax.numpy``, f32 inside, the compute
type out) and the values' shift (``_shift``: ``u^- W_v2`` is ``(u W_v2)^-``, so
the shift is of the product's ``[S, J d / 2]`` and not of ``u``).
``hetu_cca_entry_total{path}`` counts the layers built by what runs the
mixing (``xla``: no kernel is written yet).

Weights: ``qk`` ``[C, (H + J) d]`` is ``[W_q | W_k]`` and ``v`` ``[C, J d]`` is
``[W_v1 | W_v2]``; ``taps [2, (H + J) d]`` (oldest first), ``tap_bias``; ``mix
[2, H + J, d, d]`` (``A_0`` on the previous position, ``A_1`` on the current),
``mix_bias``; ``temp [J]``.  Initial values (not published; this file's): taps
``(0, 1)``, ``A_0 = 0``, ``A_1 = I``, biases and ``t`` zero: a fresh layer's
mixing is the identity.  The norms divide by ``sqrt(sum + 1e-12)``.
"""

from __future__ import annotations

import numpy as np

from .. import initializers as init, telemetry
from ..graph.node import VariableOp, scope
from ..ops.attention import scaled_dot_product_attention_op
from ..ops.base import ScopedOp
from ..ops.causal_conv import ConvOp
from ..ops.rotary import RopeTables, pair_item_op, rotary_pair_op
from .attention import count_layout
from .base import BaseLayer, fresh_name
from .common import Linear

_SCOPE = "hetu_cca"


def _mix(z, zc, a, b, temp, *, heads, kv_heads, qk_mean):
    """``(q^ [B, S, H d], k^ [B, S, J d])`` from ``z = [q~ | k~]`` and its
    depthwise convolution ``zc``: the head-mixing taps, the q-k mean, the L2
    norms and the keys' temperature."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    B, S, width = z.shape
    n, g = heads + kv_heads, heads // kv_heads
    d = width // n
    prev = jnp.pad(zc[:, :-1], ((0, 0), (1, 0), (0, 0)))
    full = jax.lax.Precision.HIGHEST if z.dtype == f32 else None

    def head(h):
        # a head is a slice of lanes: two [B S, d] x [d, d] products, f32 sums
        at = slice(h * d, (h + 1) * d)
        return sum(jnp.matmul(x[..., at], a[i, h].astype(x.dtype),
                              precision=full, preferred_element_type=f32)
                   for i, x in enumerate((prev, zc)))
    mixed = jnp.stack([head(h) for h in range(n)], axis=2)
    mixed = mixed + b.astype(f32).reshape(n, d)
    q, k = mixed[:, :, :heads], mixed[:, :, heads:]
    if qk_mean:
        zf = z.astype(f32).reshape(B, S, n, d)
        mq = 0.5 * (zf[:, :, :heads].reshape(B, S, kv_heads, g, d)
                    + zf[:, :, heads:, None])
        q = q + mq.reshape(B, S, heads, d)
        k = k + jnp.mean(mq, axis=3)

    def unit(x):
        return x * (d ** 0.5 * jax.lax.rsqrt(
            jnp.sum(x * x, -1, keepdims=True) + 1e-12))
    k = unit(k) * jnp.exp(temp.astype(f32))[:, None]
    return (unit(q).reshape(B, S, heads * d).astype(z.dtype),
            k.reshape(B, S, kv_heads * d).astype(z.dtype))


def _shift(v):
    """The second half of ``v``'s lanes from the position before (zeros at
    position 0)."""
    import jax.numpy as jnp
    half = v.shape[-1] // 2
    return jnp.concatenate(
        [v[..., :half], jnp.pad(v[:, :-1, half:], ((0, 0), (1, 0), (0, 0)))],
        -1)


class CompressedConvAttention(BaseLayer):
    """``(u [B, S, hidden]) -> [B, S, hidden]``; ``conv_taps`` are the two
    tap counts (``cca_time0``, ``cca_time1``), ``rotary_dim`` the lanes of a
    head that turn (None: all).  ``qk`` holds the nodes ``(q^, k^)`` of the
    last call, before the rotary, and ``out`` the layer's output (a
    comparison's; never a train step's).
    ``_qk_mean=False`` is a test's: it leaves the q-k mean out, so that the
    identity taps give plain grouped-query attention on normed q and k."""

    #: what runs the mixing: the ``jax.numpy`` forms (no kernel is written)
    PATH = "xla"

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 conv_taps=(2, 2), rotary_dim=None, rope_theta=10000.0,
                 rope_tables=None, sequence_length=None, name=None,
                 _qk_mean=True):
        name = fresh_name(name or "cca")
        assert num_heads % num_kv_heads == 0 and num_kv_heads % 2 == 0, (
            "half of the key heads carry the previous token's values",
            num_heads, num_kv_heads)
        assert tuple(conv_taps)[1] == 2, (
            "the head-mixing taps are the previous and the current position",
            conv_taps)
        self.hidden_size, self.head_dim = hidden_size, head_dim
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.inner, kv_dim = num_heads * head_dim, num_kv_heads * head_dim
        self.rotary_dim = None if rotary_dim == head_dim else rotary_dim
        self.rope_theta = rope_theta
        self.rope_tables = rope_tables or RopeTables()
        self.sequence_length = sequence_length
        self._qk_mean = _qk_mean
        n, width = num_heads + num_kv_heads, self.inner + kv_dim
        self.qk_proj = Linear(hidden_size, width, bias=False,
                              name=f"{name}_qk")
        self.v_proj = Linear(hidden_size, kv_dim, bias=False,
                             name=f"{name}_v")
        self.out_proj = Linear(self.inner, hidden_size, bias=False,
                               name=f"{name}_out")
        taps = np.zeros((conv_taps[0], width), np.float32)
        taps[-1] = 1.0
        mix = np.zeros((2, n, head_dim, head_dim), np.float32)
        mix[1] = np.eye(head_dim)
        self.taps = VariableOp(f"{name}_taps", taps.shape,
                               init.NumpyInit(taps))
        self.tap_bias = VariableOp(f"{name}_tap_bias", (width,), init.zeros())
        self.mix = VariableOp(f"{name}_mix", mix.shape, init.NumpyInit(mix))
        self.mix_bias = VariableOp(f"{name}_mix_bias", (width,), init.zeros())
        self.temp = VariableOp(f"{name}_temp", (num_kv_heads,), init.zeros())
        self.qk = self.out = None

    def layout(self):
        """``(layout, reason)`` as ``MultiHeadAttention.layout()`` says them.
        The layer has ONE graph, on the projections' ``[B, S, heads d]``;
        the rotary and flash kernels take it in place where a head is whole
        lane tiles (grouped queries and a partial rotation need them), else
        the reason says so and their ``jax.numpy`` forms walk the same
        arrays through free views."""
        return "bshd", ("head_dim_not_128_aligned" if self.head_dim % 128
                        else "in_place")

    def __call__(self, u, seq_len=None):
        seq_len = seq_len or self.sequence_length
        assert seq_len is not None, "sequence length required"
        telemetry.get_registry().counter(
            "hetu_cca_entry_total",
            "Compressed-convolutional-attention layers built, by what runs "
            "the mixing between the down-projections and the rotary",
            labels=("path",)).labels(path=self.PATH).inc()
        count_layout(*self.layout())
        with scope("hetu_attn"):
            z, v = self.qk_proj(u), self.v_proj(u)
            with scope(_SCOPE):
                zc = ConvOp(_SCOPE, z, self.taps, self.tap_bias, act=None)
                both = ScopedOp(_mix, _SCOPE, z, zc, self.mix, self.mix_bias,
                                self.temp, heads=self.num_heads,
                                kv_heads=self.num_kv_heads,
                                qk_mean=self._qk_mean)
                self.qk = tuple(pair_item_op(both, index=i) for i in (0, 1))
                v = ScopedOp(_shift, _SCOPE, v)
            q, k = rotary_pair_op(*self.qk, self.rope_tables(
                seq_len, self.head_dim, self.rope_theta,
                rotary_dim=self.rotary_dim))
            ctx_ = scaled_dot_product_attention_op(
                q, k, v, causal=True, num_heads=self.num_heads)
            self.out = self.out_proj(ctx_)
            return self.out
