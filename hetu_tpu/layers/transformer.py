"""Transformer blocks shared by BERT/GPT/MoE models.

Reference builds these ad hoc in examples (examples/nlp/bert/hetu_bert.py,
examples/auto_parallel/transformer); here they are first-class layers.  The
block works on [B, S, H] tensors throughout; TP/SP shardings are attached by
parallel/ strategies via dist_state annotations on the weight Variables.
"""

from __future__ import annotations

from .base import BaseLayer, fresh_name
from ..graph.node import scope
from .common import Linear, LayerNorm
from .attention import MultiHeadAttention
from ..ops import gelu_op, dropout_op


class TransformerFFN(BaseLayer):
    def __init__(self, hidden_size, intermediate_size, activation=gelu_op,
                 dropout_rate=0.0, name=None):
        name = fresh_name(name or "ffn")
        self.dense1 = Linear(hidden_size, intermediate_size,
                             name=f"{name}_in")
        self.dense2 = Linear(intermediate_size, hidden_size,
                             name=f"{name}_out")
        self.activation = activation
        self.dropout_rate = dropout_rate

    def __call__(self, x):
        with scope("hetu_mlp"):
            h = self.activation(self.dense1(x))
            h = self.dense2(h)
            if self.dropout_rate > 0:
                h = dropout_op(h, keep_prob=1.0 - self.dropout_rate)
            return h


class TransformerLayer(BaseLayer):
    """Post-LN (BERT-style) or pre-LN (GPT-style) transformer block on
    [B, S, H] nodes."""

    def __init__(self, hidden_size, num_heads, intermediate_size,
                 seq_len=None, dropout_rate=0.0, attn_dropout_rate=0.0,
                 causal=False, pre_norm=False, activation=gelu_op,
                 ffn_layer=None, name=None):
        name = fresh_name(name or "layer")
        self.attn = MultiHeadAttention(hidden_size, num_heads,
                                       sequence_length=seq_len,
                                       dropout_rate=attn_dropout_rate,
                                       causal_mask=causal,
                                       name=f"{name}_attn")
        self.ffn = ffn_layer or TransformerFFN(
            hidden_size, intermediate_size, activation=activation,
            dropout_rate=dropout_rate, name=f"{name}_ffn")
        self.ln1 = LayerNorm(hidden_size, name=f"{name}_ln1")
        self.ln2 = LayerNorm(hidden_size, name=f"{name}_ln2")
        self.pre_norm = pre_norm

    def __call__(self, x, attention_mask=None, seq_len=None):
        # norms and residual sums are the block `hetu_norm`; the sublayers
        # name their own
        if self.pre_norm:
            with scope("hetu_norm"):
                a_in = self.ln1(x)
            a = self.attn(a_in, a_in, a_in, attention_mask=attention_mask,
                          seq_len=seq_len)
            with scope("hetu_norm"):
                x = x + a
                f_in = self.ln2(x)
            f = self.ffn(f_in)
            with scope("hetu_norm"):
                return x + f
        else:
            a = self.attn(x, x, x, attention_mask=attention_mask,
                          seq_len=seq_len)
            with scope("hetu_norm"):
                x = self.ln1(x + a)
            f = self.ffn(x)
            with scope("hetu_norm"):
                return self.ln2(x + f)
