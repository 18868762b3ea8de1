"""Multi-head attention layer.

Reference: /root/reference/python/hetu/layers/attention.py MultiHeadAttention
(the reference flattens to [B*S, H] between every projection).  Here the
layer keeps the [B, S, H] layout end to end — projections are 3D matmuls XLA
maps straight onto the MXU — and the core product is a single fused-attention
op (ops/attention.py) lowered to Pallas flash attention on TPU, which reads
the projections' [B, S, heads*d] and writes the context in place: the layer
moves no heads.  ONE rule, on the layer's own arguments, says which graph a
layer builds (``MultiHeadAttention.layout``, counted in
``hetu_attn_layout_total{layout, reason}``): grouped queries (K and V stay
``[B, S, kv_heads*d]``: the kernels read a query head's key head where it
lies), a gate a head, a partial rotation and a norm a head go in place where a
head is whole lane tiles (``head_dim % 128 == 0``); the norm a head is part of
the pass that rotates (``ops/rotary.py qk_norm_rotary_pair_op``: SDAR, Qwen3).
What keeps the [B, heads, S, d] graph: ALiBi (its bias is built by heads), the
elementwise gate (a head's query and its gate lie side by side in ONE
projection, which the flash kernels would have to read at a stride of ``2 d``:
Qwen3-Next), the inference graphs' fused head projection, heads that are not
whole lane tiles under any of the above (Granite's 64), and a norm a head that
no rotation follows (no cell's layer).

Position-encoding variants for the Llama/Baichuan model tier (reference
tools/Hetu-Galvatron/galvatron/models/llama, models/baichuan): ``rope_theta``
applies rotary embeddings to q/k before the attention product; ``alibi``
adds the per-head linear bias instead; ``num_kv_heads`` < num_heads gives
grouped-query attention (K/V projected to the smaller head count; a query
head reads its key head in place, or on ``[B, heads, S, d]`` K/V are repeated
in front of the op); ``qk_norm`` applies an RMSNorm to
the projected queries and keys (OLMoE), ``qk_norm="head"`` one to each head's
query and key (Qwen3, Qwen3-Next).  ``head_dim`` sets the head size apart
from ``hidden_size // num_heads`` (Qwen3-Next: 16 heads of 256 on a hidden
size of 2,048), ``rotary_dim`` rotates only the first dimensions of a head,
``output_gate`` doubles the query projection and multiplies the context by
the sigmoid of its second half, head by head; ``output_gate="head"`` is one
gate NUMBER a head and a token instead, ``sigmoid(x W_g)`` with ``W_g [hidden,
heads]``, on that head's context before ``W_o`` (Laguna).  ``scale`` is what
the scores are multiplied by before the softmax where it is not ``head_dim **
-0.5`` (Granite 4.0: ``attention_multiplier`` 1/64 on heads of 64).
``window`` keeps of a causal layer's keys the last ``window`` (the position's
own among them); such a layer's block is ``hetu_window_attn`` (no scope name
may lie inside another), not ``hetu_attn``.  ``rope_scaling`` is
``ops/rotary.py yarn_scaling``'s tuple.  ``block_diffusion=K`` (not causal) is
block diffusion's training pass: the sequence is a clean copy of ``L`` tokens
and then their noised copy, token ``i`` of each rotates at position ``i``, and
a position sees what ``ops/attention.py block_diffusion_mask`` shows.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .. import initializers as init
from .. import telemetry
from .base import BaseLayer, fresh_name
from ..graph.node import VariableOp, scope
from .common import Linear, RMSNorm
from ..ops import (array_reshape_op, transpose_op, head_split_linear_op,
                   split_op, sigmoid_op)
from ..ops.base import ScopedOp, simple_op
from ..ops.attention import scaled_dot_product_attention_op
from ..ops.pallas.common import spread as _spread, widen as _widen
from ..ops.pallas.flash_attention import pair_view_unsupported
from ..ops.rotary import (RopeTables, rotary_embedding_op, rotary_pair_op,
                          qk_norm_rotary_pair_op, repeat_kv_op, alibi_bias_op)


def _gate_heads(ctx_, gate):
    """The context ``[B, H, S, d]`` back as ``[B, S, H d]``, each head times
    the sigmoid of its one gate number ``[B, S, H]``, in f32."""
    o = (ctx_.transpose(0, 2, 1, 3).astype(jnp.float32)
         * jax.nn.sigmoid(gate.astype(jnp.float32))[..., None])
    return o.astype(ctx_.dtype).reshape(o.shape[:2] + (-1,))


def _sigmoid_wide(ctx_, gate):
    """``(sigmoid(gate) [B, S, H], the same spread to ctx_'s [B, S, H d])``,
    f32."""
    sig = jax.nn.sigmoid(gate.astype(jnp.float32))
    return sig, _widen(sig, ctx_.shape[-1] // gate.shape[-1])


@jax.custom_vjp
def _gate_heads_in_place(ctx_, gate):
    """``_gate_heads`` on the context as the kernels leave it, ``[B, S, H
    d]``: the same f32 product with one rounding and no view by heads.  Kept
    for the backward pass: the two operands, nothing ``H d`` wide in f32."""
    return (ctx_.astype(jnp.float32) * _sigmoid_wide(ctx_, gate)[1]
            ).astype(ctx_.dtype)


def _gate_in_place_fwd(ctx_, gate):
    return _gate_heads_in_place(ctx_, gate), (ctx_, gate)


def _gate_in_place_bwd(kept, g):
    # behind a barrier, or XLA finds the forward pass's spread sigmoid to be
    # this one and keeps its f32 [B, S, H d] from there to here
    ctx_, gate = jax.lax.optimization_barrier(kept)
    sig, wide = _sigmoid_wide(ctx_, gate)
    g = g.astype(jnp.float32)
    # a head's lanes of g * ctx added up: the spread's transpose, on an f32
    # operand and 128 terms a sum, so at the highest precision
    heads = gate.shape[-1]
    d_sig = jnp.matmul(
        g * ctx_.astype(jnp.float32),
        _spread(heads, ctx_.shape[-1] // heads, dtype=jnp.float32).T,
        precision=jax.lax.Precision.HIGHEST)
    return ((g * wide).astype(ctx_.dtype),
            (d_sig * sig * (1.0 - sig)).astype(gate.dtype))


_gate_heads_in_place.defvjp(_gate_in_place_fwd, _gate_in_place_bwd)
#: the function itself, for a layer whose node calls it on the context the
#: kernels left (``layers/latent_attention.py _out``)
gate_heads_in_place = _gate_heads_in_place

gate_heads_op = simple_op(_gate_heads, "gate_heads")
gate_heads_in_place_op = simple_op(_gate_heads_in_place,
                                   "gate_heads_in_place")


def count_layout(layout, reason):
    """One more attention layer built, in ``hetu_attn_layout_total``."""
    telemetry.get_registry().counter(
        "hetu_attn_layout_total",
        "Attention layers built, by the graph they build (bshd: on the "
        "projections' [B, S, heads*d] in place; bhsd: heads split off "
        "and transposed) and why", labels=("layout", "reason"),
    ).labels(layout=layout, reason=reason).inc()


class MultiHeadAttention(BaseLayer):
    def __init__(self, hidden_size, num_heads, sequence_length=None,
                 dropout_rate=0.0, causal_mask=False, num_kv_heads=None,
                 rope_theta=None, alibi=False, bias=True,
                 fused_head_projection=False, qk_norm=False,
                 qk_norm_eps=1e-5, head_dim=None, rotary_dim=None,
                 output_gate=False, qk_norm_zero_centered=False, scale=None,
                 rope_tables=None, window=None, rope_scaling=None,
                 block_diffusion=None, name=None):
        assert head_dim is not None or hidden_size % num_heads == 0
        assert output_gate in (False, True, "head"), output_gate
        assert window is None or (causal_mask and not dropout_rate), (
            "a window is causal and has no dropout on the probabilities")
        assert block_diffusion is None or not (
            causal_mask or dropout_rate or window or alibi), (
            "the block-diffusion mask stands alone")
        self.block_diffusion = block_diffusion
        self.fused_head_projection = fused_head_projection
        name = fresh_name(name or "attn")
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        assert num_heads % self.num_kv_heads == 0
        self.head_dim = head_dim or hidden_size // num_heads
        #: width of the heads side by side: the context's, and q's
        self.inner = self.num_heads * self.head_dim
        self.rotary_dim = rotary_dim
        self.output_gate = output_gate
        self.window = window
        self.rope_scaling = rope_scaling
        self.scale = scale
        self.sequence_length = sequence_length
        self.dropout_keep = 1.0 - dropout_rate
        self.causal = causal_mask
        self.rope_theta = rope_theta
        #: where the rotary kernels' tables come from: a model's layers share
        #: one (``ops/rotary.py RopeTables``)
        self.rope_tables = rope_tables or RopeTables()
        self.alibi = alibi
        assert not (alibi and rope_theta), "pick one position encoding"
        kv_dim = self.num_kv_heads * self.head_dim
        # with the output gate each head's query is followed by its gate:
        # [.., heads, 2 d] (HF Qwen3NextAttention), not two halves of the row
        self.q_proj = Linear(hidden_size,
                             self.inner * (2 if output_gate is True else 1),
                             bias=bias, name=f"{name}_q")
        self.gate_proj = (Linear(hidden_size, num_heads, bias=False,
                                 name=f"{name}_gate")
                          if output_gate == "head" else None)
        self.k_proj = Linear(hidden_size, kv_dim, bias=bias,
                             name=f"{name}_k")
        self.v_proj = Linear(hidden_size, kv_dim, bias=bias,
                             name=f"{name}_v")
        self.out_proj = Linear(self.inner, hidden_size, bias=bias,
                               name=f"{name}_out")
        # QK-norm (OLMoE, OLMo 2): an RMSNorm over the whole projected
        # width of q and of k, before the head split and the rotation
        self.q_norm = self.k_norm = None
        self.qk_norm_per_head = qk_norm == "head"
        if self.qk_norm_per_head:
            self.q_norm, self.k_norm = (
                RMSNorm(self.head_dim, eps=qk_norm_eps,
                        zero_centered=qk_norm_zero_centered,
                        name=f"{name}_{n}_norm") for n in "qk")
        elif qk_norm:
            assert not fused_head_projection, (
                "qk_norm normalises the projection before the head split")
            self.q_norm = RMSNorm(self.inner, eps=qk_norm_eps,
                                  name=f"{name}_q_norm")
            self.k_norm = RMSNorm(kv_dim, eps=qk_norm_eps,
                                  name=f"{name}_k_norm")

    def _split_heads(self, x, seq_len, n_heads, head_norm=None):
        # [B, S, H] (or [B*S, H]) -> [B, heads, S, d]
        x = array_reshape_op(
            x, output_shape=(-1, seq_len, n_heads, self.head_dim))
        if head_norm is not None:
            x = head_norm(x)
        return transpose_op(x, perm=(0, 2, 1, 3))

    def _project_heads(self, x, proj, seq_len, n_heads, norm=None):
        """Projection + head split.  Inference-only graphs use the fused
        einsum (head_split_linear_op: the head transpose rides the
        matmul epilogue — ~0.25 ms/layer saved at GPT-2.7B fwd shapes);
        training keeps the matmul + reshape + transpose form, whose
        BACKWARD measures ~1% faster end-to-end (the einsum's dW
        contraction lays out worse under XLA)."""
        if self.fused_head_projection:
            return head_split_linear_op(
                x, proj.weight,
                *([] if proj.bias is None else [proj.bias]),
                seq_len=seq_len, n_heads=n_heads, head_dim=self.head_dim)
        x = proj(x)
        if self.qk_norm_per_head:
            return self._split_heads(x, seq_len, n_heads, head_norm=norm)
        return self._split_heads(x if norm is None else norm(x), seq_len,
                                 n_heads)

    def __call__(self, query, key, value, attention_mask=None, seq_len=None,
                 kv_seq_len=None):
        """Returns [B, S, H].  ``kv_seq_len`` (default: ``seq_len``)
        supports cross-attention over a memory of different length
        (reference examples/nlp/hetu_transformer.py multihead_attention,
        decoder side)."""
        with scope("hetu_attn" if self.window is None
                   else "hetu_window_attn"):
            return self._attend(query, key, value, attention_mask, seq_len,
                                kv_seq_len)

    def _attend(self, query, key, value, attention_mask, seq_len,
                kv_seq_len):
        seq_len = seq_len or self.sequence_length
        assert seq_len is not None, "sequence length required"
        if kv_seq_len is not None and kv_seq_len != seq_len:
            # rotary positions implicitly start at 0 on BOTH q and k, the
            # causal mask assumes square [S, S], and the ALiBi bias is
            # built [.., Sq, Sq] from q alone — a differing memory length
            # would silently mis-position/mis-mask (ADVICE r3); only
            # vanilla cross-attention supports it
            assert (self.rope_theta is None and not self.causal
                    and not self.alibi), (
                "kv_seq_len != seq_len is only supported for non-causal, "
                "non-rotary, non-alibi cross-attention")
        kv_seq_len = kv_seq_len or seq_len
        layout, reason = self.layout()
        count_layout(layout, reason)
        if layout == "bhsd":
            return self._attend_bhsd(query, key, value, attention_mask,
                                     seq_len, kv_seq_len)
        q, k, v = self.q_proj(query), self.k_proj(key), self.v_proj(value)
        if self.q_norm is not None and not self.qk_norm_per_head:
            q, k = self.q_norm(q), self.k_norm(k)
        if self.rope_theta is not None:
            # q and k together, on the projections' [B, S, heads * d] and
            # [B, S, kv_heads * d]
            tables = self.rope_tables(
                seq_len, self.head_dim, self.rope_theta, self.rope_scaling,
                **({} if self.rotary_dim is None
                   else {"rotary_dim": self.rotary_dim}),
                **self._copies())
            if self.qk_norm_per_head:
                # the norm a head in the same pass (layout(): never without
                # a rotation here)
                q, k = qk_norm_rotary_pair_op(
                    q, k, self.q_norm.scale, self.k_norm.scale, tables,
                    eps=self.q_norm.eps,
                    zero_centered=self.q_norm.zero_centered)
            else:
                q, k = rotary_pair_op(q, k, tables)
        # [B, S, H] as it comes (a no-op), or a caller's [B*S, H]
        kv_dim = self.num_kv_heads * self.head_dim
        q, k, v = (array_reshape_op(x, output_shape=(-1, n, width))
                   for x, n, width in ((q, seq_len, self.inner),
                                       (k, kv_seq_len, kv_dim),
                                       (v, kv_seq_len, kv_dim)))
        ctx_ = scaled_dot_product_attention_op(
            q, k, v, mask=attention_mask, causal=self.causal,
            scale=self.scale, dropout_keep=self.dropout_keep,
            num_heads=self.num_heads, window=self.window,
            block_diffusion=self.block_diffusion)
        if self.gate_proj is not None:
            ctx_ = gate_heads_in_place_op(ctx_, self.gate_proj(query))
        return self.out_proj(ctx_)

    def _copies(self):
        """The rotary tables' ``copies``: two under the block-diffusion mask
        (a clean and a noised copy of the same positions)."""
        return {} if self.block_diffusion is None else {"copies": 2}

    def layout(self):
        """``(layout, reason)``: ``("bshd", "in_place")`` where the layer
        attends on the projections' ``[B, S, heads*d]``, else ``"bhsd"`` and
        the first thing about the layer that the in-place kernels do not take.
        Grouped queries, a gate a head, a partial rotation and a norm a head
        need heads of whole lane tiles; without them any head size goes.  A
        norm a head goes in place in the pass that rotates (``ops/rotary.py
        qk_norm_rotary_pair_op``); one that NO rotation follows is no cell's
        layer and keeps ``[B, heads, S, d]`` under ``qk_norm_per_head``."""
        tiles = (self.num_kv_heads != self.num_heads
                 or self.output_gate == "head" or self.rotary_dim is not None
                 or self.qk_norm_per_head)
        for reason, holds in (
                ("head_dim_not_128_aligned", tiles and self.head_dim % 128),
                ("qk_norm_per_head",
                 self.qk_norm_per_head and self.rope_theta is None),
                ("gate_elementwise", self.output_gate is True),
                ("alibi", self.alibi),
                ("fused_head_projection", self.fused_head_projection)):
            if holds:
                return "bhsd", reason
        return "bshd", "in_place"

    def _attend_bhsd(self, query, key, value, attention_mask, seq_len,
                     kv_seq_len):
        """The [B, heads, S, d] graph: heads split off and transposed
        before the op, the context transposed back."""
        gate = None
        if self.output_gate is True:
            # [B, S, heads, 2 d]: a head's query, then its gate
            qg = array_reshape_op(self.q_proj(query), output_shape=(
                -1, seq_len, self.num_heads, 2 * self.head_dim))
            gate = array_reshape_op(
                split_op(qg, axes=3, indices=1, splits=2),
                output_shape=(-1, seq_len, self.inner))
            q = self._split_heads(
                split_op(qg, axes=3, indices=0, splits=2), seq_len,
                self.num_heads,
                head_norm=self.q_norm if self.qk_norm_per_head else None)
        else:
            q = self._project_heads(query, self.q_proj, seq_len,
                                    self.num_heads, self.q_norm)
        k = self._project_heads(key, self.k_proj, kv_seq_len,
                                self.num_kv_heads, self.k_norm)
        v = self._project_heads(value, self.v_proj, kv_seq_len,
                                self.num_kv_heads)
        if self.rope_theta is not None:
            kw = ({} if self.rotary_dim is None
                  else {"rotary_dim": self.rotary_dim})
            if self.rope_scaling is not None:
                kw["scaling"] = self.rope_scaling
            kw.update(self._copies())
            q = rotary_embedding_op(q, theta=self.rope_theta, **kw)
            k = rotary_embedding_op(k, theta=self.rope_theta, **kw)
        if self.num_kv_heads != self.num_heads:
            rep = self.num_heads // self.num_kv_heads
            k = repeat_kv_op(k, n_rep=rep)
            v = repeat_kv_op(v, n_rep=rep)
        if self.alibi:
            bias = alibi_bias_op(q, num_heads=self.num_heads)
            attention_mask = (bias if attention_mask is None
                              else attention_mask + bias)
        ctx_ = scaled_dot_product_attention_op(
            q, k, v, mask=attention_mask, causal=self.causal,
            scale=self.scale, dropout_keep=self.dropout_keep,
            window=self.window, block_diffusion=self.block_diffusion)
        if self.gate_proj is not None:
            return self.out_proj(gate_heads_op(ctx_, self.gate_proj(query)))
        ctx_ = transpose_op(ctx_, perm=(0, 2, 1, 3))
        ctx_ = array_reshape_op(ctx_,
                                output_shape=(-1, seq_len, self.inner))
        if gate is not None:
            ctx_ = ctx_ * sigmoid_op(gate)
        return self.out_proj(ctx_)


# -- differential attention (the SambaY decoders) ------------------------------

def lambda_init(layer_index):
    """The constant a differential layer starts its ``lambda`` at, from the
    layer's PUBLISHED index (Differential Transformer, arXiv:2410.05258)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer_index)


def _pair_heads(q, *, half):
    """``q [B, S, P x 2 half]`` (pair ``i`` is ``q1_i | q2_i``) -> ``[B, S, 2 P x
    2 half]``: head ``2 i`` is ``q1_i | 0`` and head ``2 i + 1`` is ``0 | q2_i``,
    so a head's scores on the key pair ``k1 | k2`` are ``q1 . k1`` and ``q2 .
    k2``: two softmaxes over ONE value of ``2 half`` in kernels that know one
    head size.  A zero half costs the matrix unit nothing it would not spend
    on a contraction of 64 (its tiles are 128 deep)."""
    width = 2 * half
    low = jnp.arange(width) < half
    zero = jnp.zeros((), q.dtype)
    out = []
    for i in range(q.shape[-1] // width):
        pair = q[..., i * width:(i + 1) * width]
        out += [jnp.where(low, pair, zero), jnp.where(low, zero, pair)]
    return jnp.concatenate(out, axis=-1)


def _lambda(lq1, lk1, lq2, lk2, lam_init):
    f32 = jnp.float32
    return (jnp.exp(jnp.sum(lq1.astype(f32) * lk1.astype(f32)))
            - jnp.exp(jnp.sum(lq2.astype(f32) * lk2.astype(f32))) + lam_init)


def _differ(ctx_, lq1, lk1, lq2, lk2, gamma, *, width, lam_init, eps):
    """The heads' contexts ``[B, S, 2 P x width]`` (``A1_i``, then ``A2_i``)
    -> ``[B, S, P x width]``: ``(1 - lambda_init) RMSNorm(A1_i - lambda A2_i;
    gamma)`` a pair, f32 inside, one rounding."""
    f32 = jnp.float32
    lam = _lambda(lq1, lk1, lq2, lk2, lam_init)
    g = gamma.astype(f32)
    out = []
    for i in range(ctx_.shape[-1] // (2 * width)):
        at = 2 * i * width
        d = (ctx_[..., at:at + width].astype(f32)
             - lam * ctx_[..., at + width:at + 2 * width].astype(f32))
        d = d * jax.lax.rsqrt(jnp.mean(d * d, -1, keepdims=True) + eps) * g
        out.append(((1.0 - lam_init) * d).astype(ctx_.dtype))
    return jnp.concatenate(out, axis=-1)


def _lanes(x, *, lo, hi):
    return x[..., lo:hi]


class DifferentialAttention(BaseLayer):
    """Differential attention as the SambaY decoders have it: ``num_heads``
    query heads and ``num_kv_heads`` key heads of ``head_dim``; query heads ``(2
    i, 2 i + 1)`` are the pair ``(q1_i, q2_i)``, key heads ``(2 j, 2 j + 1)``
    the pair ``(k1_j, k2_j)``, ``V_j = [v_2j | v_(2j+1)]`` (``2 head_dim``
    wide), pair ``i`` reads key pair ``j = i // (num_heads / num_kv_heads)``::

        A^c_i = softmax(q^c_i (k^c_j)^T / sqrt(head_dim) + mask) V_j   c = 1, 2
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(layer_index)
        O_i = (1 - lambda_init) RMSNorm(A^1_i - lambda A^2_i; gamma)
        out = [O_0 | ..] W_o + b_o

    ``[q | k | v] = u W_qkv + b`` is ONE projection; ``cross=True`` builds the
    query projection alone and the call takes another layer's ``keys`` and
    ``values`` nodes (that layer's ``self.keys`` / ``self.values``, after its
    projection).  The mask is causal, with ``window`` the last ``window`` keys
    (the block is then ``hetu_window_attn``).  No position encoding, no
    dropout, no key mask.

    The pairs stay on the projections' ``[B, S, heads * head_dim]``: a pair is
    one lane tile where ``head_dim`` is 64, and the attention node sees ``2
    P`` heads of ``2 head_dim`` whose other half is zero (``_pair_heads``) on
    ``num_kv_heads / 2`` key heads of the same size, which the flash and the
    window kernels read in place as grouped queries (``pair_view_unsupported``
    in ``ops/pallas/flash_attention.py`` says when they do).  Counted in
    ``hetu_attn_layout_total`` as ``bshd`` / ``differential_pairs`` then, and
    in ``hetu_attn_layers_total`` under ``differential_full`` / ``_window`` /
    ``_cross``; ``hetu_diff_attn_lambda{layer}`` is set by ``lambdas()``'s
    reader (``models/phi4flash.py``)."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, layer_index,
                 sequence_length, window=None, cross=False, eps=1e-5,
                 name=None):
        name = fresh_name(name or "diff_attn")
        d = hidden_size // num_heads
        assert num_heads % 2 == 0 and num_kv_heads % 2 == 0, (
            "differential attention pairs its heads", num_heads, num_kv_heads)
        assert num_heads % num_kv_heads == 0, (num_heads, num_kv_heads)
        self.head_dim, self.num_heads, self.num_kv_heads = (
            d, num_heads, num_kv_heads)
        self.inner, self.kv_dim = num_heads * d, num_kv_heads * d
        self.layer_index, self.lambda_init = layer_index, lambda_init(
            layer_index)
        self.window, self.cross, self.eps = window, cross, eps
        self.sequence_length = sequence_length
        self.pair_view = pair_view_unsupported(num_heads, num_kv_heads, d)
        normal = init.normal(0.0, 0.02)
        self.qkv_proj = Linear(
            hidden_size, self.inner + (0 if cross else 2 * self.kv_dim),
            bias=True, initializer=normal, name=f"{name}_q" if cross
            else f"{name}_qkv")
        self.out_proj = Linear(self.inner, hidden_size, bias=True,
                               initializer=normal, name=f"{name}_out")
        self.lambdas = tuple(
            VariableOp(f"{name}_lambda_{n}", (d,), init.normal(0.0, 0.1))
            for n in ("q1", "k1", "q2", "k2"))
        self.sub_norm = VariableOp(f"{name}_subln_scale", (2 * d,),
                                   init.ones())
        #: the projected keys and values of the last call (not a cross layer)
        self.keys = self.values = None

    def __call__(self, u, keys=None, values=None):
        block = "hetu_attn" if self.window is None else "hetu_window_attn"
        assert (keys is not None) == self.cross, (
            "a cross layer is called with another layer's keys and values")
        count_layout("bshd" if self.pair_view is None else "bhsd",
                     self.pair_view or "differential_pairs")
        with scope(block):
            qkv = self.qkv_proj(u)
            q = qkv
            if not self.cross:
                q, keys, values = (
                    ScopedOp(_lanes, block, qkv, lo=lo, hi=hi)
                    for lo, hi in ((0, self.inner),
                                   (self.inner, self.inner + self.kv_dim),
                                   (self.inner + self.kv_dim,
                                    self.inner + 2 * self.kv_dim)))
                self.keys, self.values = keys, values
            heads = ScopedOp(_pair_heads, block, q, half=self.head_dim)
            ctx_ = scaled_dot_product_attention_op(
                heads, keys, values, causal=True,
                scale=self.head_dim ** -0.5, num_heads=self.num_heads,
                window=self.window,
                form="cross" if self.cross else "differential")
            out = ScopedOp(_differ, block, ctx_, *self.lambdas,
                           self.sub_norm, width=2 * self.head_dim,
                           lam_init=self.lambda_init, eps=self.eps)
            return self.out_proj(out)

    def lambda_value(self, params):
        """``lambda`` of this layer under ``params`` (``{name: array}``)."""
        return float(_lambda(*(params[v.name] for v in self.lambdas),
                             self.lambda_init))
