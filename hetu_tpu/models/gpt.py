"""GPT-style decoder LM (reference: examples/auto_parallel/transformer
test_gpt2.py + Galvatron models/gpt — the 3D-parallel flagship).

Pre-norm causal transformer with tied LM head.  Parallelism comes from
strategy annotations (parallel/strategies.py MegatronLM / Galvatron configs)
or the shard_map fast path in parallel/tensor_parallel.py used by bench.
"""

from __future__ import annotations

from contextlib import nullcontext

import numpy as np

from ..graph.node import Op, VariableOp, scope, stage, scoped_init
from .. import initializers as init
from ..layers import Embedding, LayerNorm, TransformerLayer
from ..ops import (array_reshape_op, matmul_op, reduce_mean_op,
                   softmax_cross_entropy_sparse_op, dropout_op)
from .bert import PositionIdsOp, MaskedMeanOp


class GPTConfig:
    def __init__(self, vocab_size=50257, hidden_size=768, num_layers=12,
                 num_heads=12, seq_len=1024, intermediate_size=None,
                 dropout_prob=0.1):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.seq_len = seq_len
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.dropout_prob = dropout_prob


# published size presets (match Galvatron gpt configs: 1.5b/2.7b/6.7b)
GPT_CONFIGS = {
    "gpt-small": dict(hidden_size=768, num_layers=12, num_heads=12),
    "gpt-medium": dict(hidden_size=1024, num_layers=24, num_heads=16),
    "gpt-1.5b": dict(hidden_size=1600, num_layers=48, num_heads=32),
    "gpt-2.7b": dict(hidden_size=2560, num_layers=32, num_heads=32),
    "gpt-6.7b": dict(hidden_size=4096, num_layers=32, num_heads=32),
}


class GPTModel:
    """``pipeline_stages=k`` wraps construction in `ht.stage` scopes —
    embedding on stage 0, the layer stack split evenly, final LN (and the
    LM head built on top) on the last stage — so the model trains under
    the graph pipeline executor (parallel/graph_pipeline.py; reference
    raw_ctx staging, context.py:1430)."""

    @scoped_init
    def __init__(self, config, name="gpt", pipeline_stages=None):
        c = config
        self.config = c
        self.pipeline_stages = pipeline_stages
        self.wte = Embedding(c.vocab_size, c.hidden_size,
                             initializer=init.normal(0.0, 0.02),
                             name=f"{name}_wte")
        self.wpe = VariableOp(f"{name}_wpe", (c.seq_len, c.hidden_size),
                              init.normal(0.0, 0.01))
        self.layers = [
            TransformerLayer(c.hidden_size, c.num_heads,
                             c.intermediate_size, seq_len=c.seq_len,
                             dropout_rate=c.dropout_prob,
                             attn_dropout_rate=c.dropout_prob,
                             causal=True, pre_norm=True,
                             name=f"{name}_h{i}")
            for i in range(c.num_layers)]
        self.ln_f = LayerNorm(c.hidden_size, name=f"{name}_ln_f")

    def _scope(self, layer_idx=None):
        S = self.pipeline_stages
        if not S:
            return nullcontext()
        if layer_idx is None:
            return stage(0)
        # balanced split of the layer stack over stages
        bounds = np.array_split(np.arange(len(self.layers)), S)
        for s, chunk in enumerate(bounds):
            if layer_idx in chunk:
                return stage(s)
        return stage(S - 1)

    def __call__(self, input_ids):
        c = self.config
        with self._scope(), scope("hetu_embed"):
            x = self.wte(input_ids)
            x = x + PositionIdsOp(self.wpe, x, c.seq_len)
            if c.dropout_prob > 0:
                x = dropout_op(x, keep_prob=1.0 - c.dropout_prob)
        for i, layer in enumerate(self.layers):
            with self._scope(i):
                x = layer(x, seq_len=c.seq_len)
        with (stage(self.pipeline_stages - 1) if self.pipeline_stages
              else nullcontext()), scope("hetu_head"):
            return self.ln_f(x)


class GPTLMHeadModel:
    @scoped_init
    def __init__(self, config, name="gpt", pipeline_stages=None):
        self.transformer = GPTModel(config, name=name,
                                    pipeline_stages=pipeline_stages)
        self.config = config

    def __call__(self, input_ids):
        h = self.transformer(input_ids)
        with scope("hetu_head"):
            h = array_reshape_op(h,
                                 output_shape=(-1, self.config.hidden_size))
            return matmul_op(h, self.transformer.wte.weight, trans_B=True)

    def loss(self, input_ids, labels):
        """labels: [B, S] next-token ids with -1 at padded positions."""
        logits = self(input_ids)
        with scope("hetu_loss"):
            ce = softmax_cross_entropy_sparse_op(
                logits, array_reshape_op(labels, output_shape=(-1,)),
                ignored_index=-1)
            return MaskedMeanOp(ce, array_reshape_op(labels,
                                                     output_shape=(-1,)))
