"""BERT (reference: examples/nlp/bert/hetu_bert.py — embeddings + encoder
stack + MLM/NSP heads; the DP-8 throughput north-star model).

Graph-level model: __call__ builds nodes from id placeholders.  The input
contract matches the reference: input_ids/token_type_ids/attention_mask of
shape [B, S]; attention_mask is converted to an additive [B,1,1,S] bias.
"""

from __future__ import annotations

import numpy as np

from ..graph.node import Op, VariableOp, scope, scoped_init
from .. import initializers as init
from ..layers import (Linear, LayerNorm, Embedding, TransformerLayer,
                      fresh_name)
from ..ops import (array_reshape_op, dropout_op, gelu_op, tanh_op,
                   embedding_lookup_op, matmul_op, broadcastto_op,
                   softmax_cross_entropy_sparse_op, reduce_mean_op, slice_op)


class BertConfig:
    def __init__(self, vocab_size=30522, hidden_size=768,
                 num_hidden_layers=12, num_attention_heads=12,
                 intermediate_size=3072, max_position_embeddings=512,
                 type_vocab_size=2, hidden_dropout_prob=0.1,
                 attention_probs_dropout_prob=0.1, seq_len=128,
                 mlm_bucket_frac=0.25):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.seq_len = seq_len
        # Fraction of tokens the MLM head's masked-position bucket holds.
        # Must exceed the masking rate (0.25 covers the standard 15%
        # recipe); batches that mask more positions than the bucket trip a
        # runtime overflow warning in MaskedSelectLabelsOp and the excess
        # tokens are excluded from the loss.  None = dense full-position
        # head (use for span/40% masking recipes).
        self.mlm_bucket_frac = mlm_bucket_frac


class AttentionMaskOp(Op):
    """[B, S] 0/1 mask -> additive [B, 1, 1, S] bias (reference
    examples/nlp/bert/hetu_bert.py extended_attention_mask)."""

    def _compute(self, input_vals, ctx):
        import jax.numpy as jnp
        (m,) = input_vals
        return ((1.0 - m.astype(jnp.float32))
                * -10000.0)[:, None, None, :]


class PositionIdsOp(Op):
    """Broadcast [S] position embedding rows over the batch of x."""

    def __init__(self, table, x, seq_len):
        super().__init__(table, x, name="position_embed")
        self.seq_len = seq_len

    def _compute(self, input_vals, ctx):
        table, x = input_vals
        return table[None, :self.seq_len, :]


class BertEmbeddings:
    def __init__(self, config, name="bert_embeddings"):
        c = config
        self.word = Embedding(c.vocab_size, c.hidden_size,
                              initializer=init.normal(0.0, 0.02),
                              name=f"{name}_word")
        self.position = VariableOp(f"{name}_position",
                                   (c.max_position_embeddings, c.hidden_size),
                                   init.normal(0.0, 0.02))
        self.token_type = Embedding(c.type_vocab_size, c.hidden_size,
                                    initializer=init.normal(0.0, 0.02),
                                    name=f"{name}_tok_type")
        self.ln = LayerNorm(c.hidden_size, name=f"{name}_ln")
        self.dropout_keep = 1.0 - c.hidden_dropout_prob
        self.config = config

    def __call__(self, input_ids, token_type_ids):
        with scope("hetu_embed"):
            x = self.word(input_ids) + self.token_type(token_type_ids)
            x = x + PositionIdsOp(self.position, x, self.config.seq_len)
            x = self.ln(x)
            if self.dropout_keep < 1.0:
                x = dropout_op(x, keep_prob=self.dropout_keep)
            return x


class BertModel:
    @scoped_init
    def __init__(self, config, name="bert"):
        c = config
        self.config = c
        self.embeddings = BertEmbeddings(c, name=f"{name}_embeddings")
        self.encoder = [
            TransformerLayer(c.hidden_size, c.num_attention_heads,
                             c.intermediate_size, seq_len=c.seq_len,
                             dropout_rate=c.hidden_dropout_prob,
                             attn_dropout_rate=c.attention_probs_dropout_prob,
                             causal=False, pre_norm=False,
                             name=f"{name}_layer{i}")
            for i in range(c.num_hidden_layers)]
        self.pooler = Linear(c.hidden_size, c.hidden_size,
                             name=f"{name}_pooler")

    def __call__(self, input_ids, token_type_ids, attention_mask=None):
        with scope("hetu_attn"):
            mask = AttentionMaskOp(attention_mask) \
                if attention_mask is not None else None
        x = self.embeddings(input_ids, token_type_ids)
        for layer in self.encoder:
            x = layer(x, attention_mask=mask, seq_len=self.config.seq_len)
        # pooled = tanh(W @ x[:, 0])
        with scope("hetu_head"):
            pooled = tanh_op(self.pooler(FirstTokenOp(x)))
        return x, pooled


class FirstTokenOp(Op):
    """[B, S, H] -> [B, H] (CLS token for the pooler)."""

    def _compute(self, input_vals, ctx):
        (x,) = input_vals
        return x[:, 0, :]


class BertForPreTraining:
    """MLM + NSP heads (reference examples/nlp/bert/hetu_bert.py)."""

    @scoped_init
    def __init__(self, config, name="bert"):
        c = config
        self.config = c
        self.bert = BertModel(config, name=name)
        self.mlm_transform = Linear(c.hidden_size, c.hidden_size,
                                    name=f"{name}_mlm_transform")
        self.mlm_ln = LayerNorm(c.hidden_size, name=f"{name}_mlm_ln")
        # decoder shares the word-embedding table (tied weights)
        self.mlm_bias = VariableOp(f"{name}_mlm_bias", (c.vocab_size,),
                                   init.zeros())
        self.nsp = Linear(c.hidden_size, 2, name=f"{name}_nsp")

    def __call__(self, input_ids, token_type_ids, attention_mask):
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        with scope("hetu_head"):
            flat = array_reshape_op(
                seq, output_shape=(-1, self.config.hidden_size))
            return self._mlm_logits(flat), self.nsp(pooled)

    def _mlm_logits(self, h_in):
        h = self.mlm_ln(gelu_op(self.mlm_transform(h_in)))
        logits = matmul_op(h, self.bert.embeddings.word.weight, trans_B=True)
        return logits + broadcastto_op(self.mlm_bias, logits)

    def loss(self, input_ids, token_type_ids, attention_mask, mlm_labels,
             nsp_labels):
        """mlm_labels: [B*S] with -1 for unmasked; nsp_labels: [B].

        The MLM head (transform + LN + tied vocab decoder) runs only on a
        static BUCKET of masked positions (`config.mlm_bucket_frac`,
        default 0.25 of the tokens — standard masking is 0.15): unmasked
        positions contribute zero loss AND zero gradient through the head,
        so gathering first is mathematically identical while cutting the
        dominant [tokens, vocab] matmuls ~4x.  Set mlm_bucket_frac=None
        for the dense full-position head.
        """
        c = self.config
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        frac = c.mlm_bucket_frac
        n_tokens = None
        shape = getattr(mlm_labels, "shape", None)
        if frac is not None and shape is not None and shape[0] is not None:
            n_tokens = int(shape[0])
        with scope("hetu_head"):
            flat = array_reshape_op(seq, output_shape=(-1, c.hidden_size))
            if n_tokens is not None:
                bucket = min(n_tokens, -(-int(n_tokens * frac) // 128) * 128)
                h_in = MaskedSelectOp(flat, mlm_labels, bucket=bucket)
                with scope("hetu_loss"):
                    labels_in = MaskedSelectLabelsOp(mlm_labels,
                                                     bucket=bucket)
            else:
                h_in, labels_in = flat, mlm_labels
            logits = self._mlm_logits(h_in)
        # (nodes are made in the order they always were: a node's id names
        # it and seeds its dropout)
        with scope("hetu_loss"):
            ce = softmax_cross_entropy_sparse_op(logits, labels_in,
                                                 ignored_index=-1)
            mlm_loss = MaskedMeanOp(ce, labels_in)
        with scope("hetu_head"):
            nsp_logits = self.nsp(pooled)
        with scope("hetu_loss"):
            nsp_loss = reduce_mean_op(softmax_cross_entropy_sparse_op(
                nsp_logits, nsp_labels))
            return mlm_loss + nsp_loss


class MaskedSelectOp(Op):
    """Rows of ``x`` at the first ``bucket`` positions where label >= 0
    (fill rows repeat index 0; their loss weight is zeroed downstream, so
    their gradients vanish too).  If more than ``bucket`` positions are
    masked, the excess is dropped — size the bucket above the masking
    rate."""

    def __init__(self, x, labels, bucket, name=None):
        super().__init__(x, labels, name=name)
        self.bucket = int(bucket)

    def _compute(self, input_vals, ctx):
        import jax.numpy as jnp
        x, labels = input_vals
        (pos,) = jnp.nonzero(labels.reshape(-1) >= 0, size=self.bucket,
                             fill_value=0)
        return x[pos]


class MaskedSelectLabelsOp(Op):
    """Labels gathered like MaskedSelectOp's rows, with fill slots forced
    to -1 (ignored) so downstream CE/normalization see only true masks.

    Overflowed masked positions are dropped from the loss; that is a
    silent objective change, so it is surfaced as an IN-GRAPH cumulative
    counter (a non-trainable variable the executor polls host-side every
    ``monitor_interval`` steps and warns on).  Host callbacks are NOT
    used: a callback inside the step stalls the device on the host every
    step to report something that is almost always zero, where the
    counter costs one scalar add and is read off the hot path."""

    def __init__(self, labels, bucket, name=None):
        name = name or fresh_name("masked_labels")
        # int32 counter: exact accumulation (an f32 total would silently
        # freeze past 2^24), and ints bypass the compute_dtype cast so
        # mixed precision never quantizes it
        self.overflow_total = VariableOp(f"{name}_overflow_total", (),
                                         init.zeros(), trainable=False,
                                         dtype=np.int32)
        self.overflow_total.monitor = (
            lambda v: None if v <= 0 else
            f"hetu_tpu: MLM bucket overflow — {int(v)} masked positions "
            "(cumulative) exceeded the bucket and were excluded from the "
            "loss.  Raise BertConfig.mlm_bucket_frac or set it to None.")
        super().__init__(labels, self.overflow_total, name=name)
        self.bucket = int(bucket)
        # opt OUT of any enclosing `with ht.remat():` scope instead of
        # tripping its stateful-op guard: the op is a cheap label gather
        # (nothing worth rematerializing) and keeping it outside the
        # checkpoint group means the counter update runs exactly once
        self.remat_scope = None

    @property
    def is_stateful(self):
        # keeps the trace-level stateful guard honest for any future
        # remat path that might capture this op
        return True

    def _compute(self, input_vals, ctx):
        import jax.numpy as jnp
        labels, total = input_vals
        labels = labels.reshape(-1)
        valid = labels >= 0
        n_valid = jnp.sum(valid)
        over = jnp.maximum(n_valid - self.bucket, 0).astype(jnp.int32)
        ctx.record_update(self.overflow_total, total + over)
        (pos,) = jnp.nonzero(valid, size=self.bucket, fill_value=0)
        live = jnp.arange(self.bucket) < n_valid
        return jnp.where(live, labels[pos], -1)


class BertForSequenceClassification:
    """Pooled-CLS classifier head for GLUE fine-tuning (reference
    examples/nlp/bert/test_glue_hetu_bert.py builds the same
    dropout(pooled) -> Linear(num_labels) head)."""

    @scoped_init
    def __init__(self, config, num_labels, name="bert"):
        self.config = config
        self.num_labels = num_labels
        self.bert = BertModel(config, name=name)
        self.dropout_keep = 1.0 - config.hidden_dropout_prob
        self.classifier = Linear(config.hidden_size, num_labels,
                                 initializer=init.normal(0.0, 0.02),
                                 name=f"{name}_classifier")

    def __call__(self, input_ids, token_type_ids, attention_mask=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        with scope("hetu_head"):
            if self.dropout_keep < 1.0:
                pooled = dropout_op(pooled, self.dropout_keep)
            return self.classifier(pooled)

    def loss(self, input_ids, token_type_ids, attention_mask, labels):
        logits = self(input_ids, token_type_ids, attention_mask)
        with scope("hetu_loss"):
            return reduce_mean_op(
                softmax_cross_entropy_sparse_op(logits, labels)), logits


class MaskedMeanOp(Op):
    """Mean of per-token losses over positions with label >= 0 (the
    reference normalizes MLM loss by the masked-token count)."""

    def _compute(self, input_vals, ctx):
        import jax.numpy as jnp
        ce, labels = input_vals
        valid = (labels.reshape(-1) >= 0).astype(ce.dtype)
        return jnp.sum(ce * valid) / jnp.maximum(jnp.sum(valid), 1.0)
