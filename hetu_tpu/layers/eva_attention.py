"""EVA attention layer (EvaByte's ``attention_class "eva"``; Zheng et al.,
arXiv:2302.04542): causal attention whose query at ``t`` sees the keys of its
own aligned window ``W floor(t / W) .. t`` exactly and everything before the
window through ONE learned summary a chunk of ``c`` keys, both under one
softmax (``ops/eva.py`` has the equations).

On the projections' ``[B, S, H d]`` end to end, as ``MultiHeadAttention``'s
in-place graph: three products, q and k rotated at their own positions
(``ops/rotary.py rotary_pair_op``, the model's one table), the summaries of the
ROTATED keys and the values from two vectors a head (``phi``, ``mu [H, d]``:
the layer's own variables; summaries get no second rotation), the attention
node (a ``ScaledDotProductAttentionOp`` of kind ``eva``: the flash kernels'
third plan on a TPU), the output product.  No bias, no dropout.

Blocks: the projections, the rotation and the output product are
``hetu_attn``'s; the attention node is ``hetu_eva`` and the summaries
``hetu_chunk_summary`` (no scope's name may lie inside another's).
"""

from __future__ import annotations

from .. import initializers as init
from ..graph.node import VariableOp, scope
from ..ops import array_reshape_op
from ..ops.attention import scaled_dot_product_attention_op
from ..ops.eva import chunk_summaries_op, summarised
from ..ops.rotary import RopeTables, rotary_pair_op
from .base import BaseLayer, fresh_name
from .common import Linear


class EvaAttention(BaseLayer):
    def __init__(self, hidden_size, num_heads, window, chunk,
                 sequence_length=None, head_dim=None, rope_theta=10000.0,
                 rope_tables=None, init_std=0.02, name=None):
        name = fresh_name(name or "eva_attn")
        self.hidden_size, self.num_heads = hidden_size, num_heads
        self.head_dim = head_dim or hidden_size // num_heads
        self.inner = num_heads * self.head_dim
        assert window % chunk == 0, (window, chunk)
        self.window, self.chunk = int(window), int(chunk)
        self.sequence_length = sequence_length
        self.rope_theta = rope_theta
        self.rope_tables = rope_tables or RopeTables()
        self.q_proj, self.k_proj, self.v_proj = (
            Linear(hidden_size, self.inner, bias=False, name=f"{name}_{n}")
            for n in "qkv")
        self.out_proj = Linear(self.inner, hidden_size, bias=False,
                               name=f"{name}_out")
        #: a chunk's two learned vectors a head: what its values are weighed
        #: by, and the offset of its pooled key; N(0, init_std) clipped at
        #: one deviation (EvaByte's ``init_fn "v2"`` reading)
        self.phi, self.mu = (
            VariableOp(f"{name}_{n}", (num_heads, self.head_dim),
                       init.truncated_normal(0.0, init_std, cutoff=1.0))
            for n in ("phi", "mu"))

    def __call__(self, x, seq_len=None):
        """``x [B, S, hidden]`` -> ``[B, S, hidden]``."""
        seq_len = seq_len or self.sequence_length
        assert seq_len is not None, "sequence length required"
        with scope("hetu_attn"):
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
            q, k = rotary_pair_op(q, k, self.rope_tables(
                seq_len, self.head_dim, self.rope_theta, None))
            q, k, v = (array_reshape_op(
                t, output_shape=(-1, seq_len, self.inner)) for t in (q, k, v))
            with scope("hetu_chunk_summary"):
                #: the summaries' nodes of the last call, of every window but
                #: the last (a benchmark fetches them beside the logits)
                self.summaries = chunk_summaries_op(
                    k, v, self.phi, self.mu, chunk=self.chunk,
                    upto=summarised(seq_len, self.window))
            with scope("hetu_eva"):
                #: the attention node's output of the last call
                self.context = scaled_dot_product_attention_op(
                    q, k, v, causal=True, num_heads=self.num_heads,
                    eva=(self.window, self.chunk), summaries=self.summaries)
            return self.out_proj(self.context)
