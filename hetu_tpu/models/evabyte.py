"""EvaByte byte-level decoder LMs (``EvaByte/EvaByte``, ``model_type``
"evabyte"): a dense pre-norm stack whose attention is EVA
(``layers/eva_attention.py``) and whose head predicts the next
``num_pred_heads`` bytes at once, over a vocabulary of 320 rows.

Layer, RMSNorm with a unit offset (``norm_add_unit_offset``: ``N(x) = x /
rms(x) (1 + g)`` in f32)::

    a = x + EVA(N_1(x));  y = a + W_down(silu(W_gate N_2(a)) * W_up N_2(a))

no bias anywhere; q and k rotated over the whole head at ``rope_theta``,
rotate-half.  ``fp32_skip_add``: each sum is taken in f32 and rounded once to
the stream's type, which is what the sum of two values of the stream's type
is.  After the last layer ``z = N_f(x)`` and ``logits_i = z W_i`` for ``i = 0
.. num_pred_heads - 1``, f32 results (``fp32_logits``); head ``i`` at position
``t`` is labelled with byte ``t + 1 + i`` and the loss is the mean of the
heads' mean cross-entropies.  The eight ``[hidden, vocab]`` matrices are the
columns ``[vocab i, vocab (i + 1))`` of ONE variable; embedding and heads are
untied.

What the published configuration does not fix is stated where it is used and
listed in ``chipbench/configs/evabyte-6.5b-pretrain.json`` (``assumed``).
``remat="layer"`` recomputes whole decoder layers in the backward pass (the
attention kernel's output and log-sum-exp are kept).  **Not modelled**:
serving (a cache of summaries beside a window of exact keys; the heads as a
self-draft), ``lazy_init`` / ``init_fn``, the published weights.
"""

from __future__ import annotations

from contextlib import nullcontext

import jax.numpy as jnp

from .. import initializers as init
from ..graph.node import remat as remat_scope, scope, scoped_init, stage
from ..layers import Linear, RMSNorm
from ..layers.base import BaseLayer
from ..layers.eva_attention import EvaAttention
from ..ops import array_reshape_op, softmax_cross_entropy_sparse_op
from ..ops.base import ScopedOp
from .llama import LlamaForCausalLM, LlamaMLP, LlamaModel, residual_sublayer


class EvaByteConfig:
    """Arguments are the published keys of ``config.json`` under their own
    names; ``seq_len`` and what the job recomputes (``remat``) are not in
    it."""

    def __init__(self, vocab_size=320, hidden_size=4096,
                 intermediate_size=11008, num_hidden_layers=32,
                 num_attention_heads=32, num_key_value_heads=32,
                 attention_class="eva", chunk_size=16, window_size=2048,
                 num_pred_heads=8, rope_theta=100000, rope_scaling=None,
                 rms_norm_eps=1e-5, norm_add_unit_offset=True,
                 fp32_logits=True, fp32_skip_add=True, fp32_ln=False,
                 mixedp_attn=True, hidden_act="silu", attention_bias=False,
                 tie_word_embeddings=False, max_position_embeddings=32768,
                 max_seq_length=32768, init_std=0.01275, init_fn="v2",
                 init_cutoff_factor=None, lazy_init=True, num_chunks=None,
                 model_type="evabyte", seq_len=2048, remat=None):
        assert attention_class == "eva", attention_class
        assert num_key_value_heads == num_attention_heads, (
            "EVA's summaries are a head's: no grouped queries")
        assert not attention_bias and not tie_word_embeddings
        assert hidden_act == "silu" and rope_scaling is None
        assert fp32_logits and fp32_skip_add and not fp32_ln
        assert num_chunks is None and window_size % chunk_size == 0
        assert seq_len <= max_seq_length, seq_len
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_layers = num_hidden_layers
        self.num_heads = self.num_kv_heads = num_attention_heads
        self.chunk_size, self.window_size = chunk_size, window_size
        self.num_pred_heads = num_pred_heads
        self.rope_theta = float(rope_theta)
        self.rms_eps = rms_norm_eps
        self.unit_offset = bool(norm_add_unit_offset)
        self.init_std = init_std
        self.seq_len = seq_len
        self.tie_embeddings = False
        self.num_experts = None
        assert remat in (None, "layer"), remat
        self.remat = remat


#: published shapes
EVABYTE_CONFIGS = {"evabyte": {}}


class EvaByteDecoderLayer(BaseLayer):
    def __init__(self, config, name, rope_tables=None):
        c = config
        self.attn = EvaAttention(
            c.hidden_size, c.num_heads, c.window_size, c.chunk_size,
            sequence_length=c.seq_len, rope_theta=c.rope_theta,
            rope_tables=rope_tables, init_std=c.init_std,
            name=f"{name}_attn")
        self.mlp = LlamaMLP(c.hidden_size, c.intermediate_size,
                            name=f"{name}_mlp")
        self.input_norm, self.post_norm = (
            RMSNorm(c.hidden_size, eps=c.rms_eps,
                    zero_centered=c.unit_offset, name=f"{name}_{n}")
            for n in ("input_norm", "post_norm"))
        self._layer_scope = remat_scope if c.remat == "layer" else nullcontext

    def __call__(self, x, seq_len=None):
        with self._layer_scope():       # the whole layer one recomputed group
            x = residual_sublayer(x, self.input_norm, self.attn)
            return residual_sublayer(x, self.post_norm, self.mlp)


class EvaByteModel(LlamaModel):
    def _layer(self, i, name):
        return EvaByteDecoderLayer(self.config, name,
                                   rope_tables=self.rope_tables)

    def _norm(self, name):
        return RMSNorm(self.config.hidden_size, eps=self.config.rms_eps,
                       zero_centered=self.config.unit_offset, name=name)


def _heads(z, w, *, vocab):
    """``z [T, hidden] @ w [hidden, P vocab]`` with f32 results, as ``[T P,
    vocab]``: row ``t P + i`` is head ``i`` at position ``t``."""
    return jnp.matmul(z, w, preferred_element_type=jnp.float32).reshape(
        -1, vocab)


def _head_means(ce, labels, *, heads):
    """``[heads]``: each head's mean of ``ce [T heads]`` over its labelled
    positions (``labels >= 0``)."""
    valid = (labels.reshape(-1, heads) >= 0).astype(ce.dtype)
    return jnp.sum(ce.reshape(-1, heads) * valid, axis=0) / jnp.maximum(
        jnp.sum(valid, axis=0), 1.0)


class EvaByteForCausalLM(LlamaForCausalLM):
    model_cls = EvaByteModel

    @scoped_init
    def __init__(self, config, name="evabyte", pipeline_stages=None):
        c = self.config = config
        self.model = self.model_cls(c, name=name,
                                    pipeline_stages=pipeline_stages)
        with (stage(pipeline_stages - 1) if pipeline_stages
              else nullcontext()):
            #: the heads side by side: head i the columns [vocab i,
            #: vocab (i + 1))
            self.lm_head = Linear(c.hidden_size,
                                  c.num_pred_heads * c.vocab_size, bias=False,
                                  initializer=init.normal(0.0, 0.02),
                                  name=f"{name}_lm_head")

    def __call__(self, input_ids):
        """``[B S num_pred_heads, vocab]`` f32: row ``(t, i)`` is head ``i``'s
        logits at position ``t``."""
        c = self.config
        h = self.model(input_ids)
        with scope("hetu_head"):
            h = array_reshape_op(h, output_shape=(-1, c.hidden_size))
            return ScopedOp(_heads, "hetu_head", h, self.lm_head.weight,
                            vocab=c.vocab_size)

    def loss_terms(self, input_ids, labels, logits=None):
        """``(loss, {"ce": ..., "ce_heads": ...})``: ``labels [B, S,
        num_pred_heads]``, head ``i``'s the ids shifted by ``1 + i`` with -1
        at ignored positions (the caller shifts); ``ce_heads [num_pred_heads]``
        each head's mean cross-entropy over its labelled positions, the loss
        their mean."""
        if logits is None:
            logits = self(input_ids)
        with scope("hetu_loss"):
            flat = array_reshape_op(labels, output_shape=(-1,))
            ce = softmax_cross_entropy_sparse_op(logits, flat,
                                                 ignored_index=-1)
            heads = ScopedOp(_head_means, "hetu_loss", ce, flat,
                             heads=self.config.num_pred_heads)
            loss = ScopedOp(jnp.mean, "hetu_loss", heads)
            return loss, {"ce": loss, "ce_heads": heads}
