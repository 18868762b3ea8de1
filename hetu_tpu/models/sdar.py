"""SDAR block-diffusion LMs with experts (``JetLM/SDAR-30B-A3B-Chat``,
``model_type`` "sdar_moe"; SDAR, arXiv:2510.06303): a Qwen3-MoE stack of
weights that generates a BLOCK of tokens at a time by denoising it, and is
trained on a clean and a noised copy of every sequence in one pass (the
two-copy pass of BD3-LMs, arXiv:2503.09573).

``L`` tokens a sequence, blocks of ``K = block_length`` (``b(i) = i // K``).
The model's input is ``[ids | noised] [B, 2L]``, the clean copy first; token
``i`` of either copy rotates at position ``i``.  Layer ``l``, pre-norm,
RMSNorm::

    h = x + Attn(N_1 x);   x' = h + MoE(N_2 h);   final N, untied head

``Attn``: ``num_attention_heads`` query heads on ``num_key_value_heads`` key
heads of ``head_dim``, no bias, an RMSNorm with a learned ``[head_dim]`` weight
over each query head and one over each key head (Qwen3's), rotate-half rotary
over the whole head, under the block-diffusion mask (``ops/attention.py
block_diffusion_mask``): clean on clean ``b(j) <= b(i)``, noised on clean
``b(j) < b(i)``, noised on noised ``b(j) == b(i)``, clean on noised never, so
that the noised block ``b`` sees the clean blocks before it and itself: one
pass computes for every block at once what generation computes a block at a
time.  ``MoE``: softmax over ``num_experts``, the ``num_experts_per_tok``
largest, their weights renormalised (``norm_topk_prob``), SwiGLU experts of
``moe_intermediate_size``, no shared expert, no token dropped.

The head walks the noised half alone, and the loss is the masked-diffusion
bound (MDLM, LLaDA) over the positions the data path masked::

    loss = 1 / (B L)  sum_i [labels_i >= 0] weights_i CE(z_i, labels_i)

with no shift: the logits at a masked position predict that position's token
(``hetu_tpu/dataloader.py block_diffusion_noise`` makes ``input_ids``,
``labels`` and ``weights``).  ``loss_terms`` also returns ``ce_masked``, the
unweighted mean over the masked positions.

``experts_held=(first, count)`` builds one chip's share of an expert-parallel
job (``MoELayer(held=)``), everything else whole.  ``remat`` names what the
backward pass recomputes: ``"layer"`` (whole decoder layers; the flash
kernel's context and log-sum-exp are kept) or None.  **Not modelled**:
generation (a step that denoises a block, a cache that holds the clean blocks,
confidence-ordered unmasking), packed documents under the block mask.
"""

from __future__ import annotations

from contextlib import nullcontext

from ..graph.node import Op, remat as remat_scope, scope, stage
from ..layers import RMSNorm
from ..layers.attention import MultiHeadAttention
from ..layers.base import BaseLayer
from ..layers.moe import MoELayer
from ..ops import (array_reshape_op, softmax_cross_entropy_sparse_op,
                   split_op)
from .bert import MaskedMeanOp
from .llama import LlamaForCausalLM, LlamaModel, residual_sublayer


class SdarMoeConfig:
    """Arguments are the published keys of ``config.json`` under their own
    names; ``seq_len`` (the TOKENS of a sequence: a pass walks twice as many
    positions), ``block_length``, ``mask_token_id``, ``experts_held`` and what
    the job recomputes (``remat``) are not in it."""

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=32,
                 num_key_value_heads=4, head_dim=128, rope_theta=1000000.0,
                 rms_norm_eps=1e-6, num_experts=128, num_experts_per_tok=8,
                 moe_intermediate_size=768, norm_topk_prob=True,
                 tie_word_embeddings=False, max_position_embeddings=32768,
                 attention_bias=False, seq_len=2048, block_length=4,
                 mask_token_id=151669, experts_held=None, remat=None):
        assert not attention_bias, "the attention layer is built without bias"
        assert seq_len <= max_position_embeddings, seq_len
        assert seq_len % block_length == 0, (seq_len, block_length)
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_hidden_layers
        self.num_heads = num_attention_heads
        self.num_kv_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rope_theta = rope_theta
        self.rms_eps = rms_norm_eps
        self.num_experts = num_experts
        self.moe_k = num_experts_per_tok
        self.intermediate_size = moe_intermediate_size
        self.moe_renorm_topk = norm_topk_prob
        self.tie_embeddings = tie_word_embeddings
        #: tokens a sequence; the layers walk ``positions`` = twice as many
        self.tokens, self.positions = seq_len, 2 * seq_len
        self.block_length = block_length
        self.mask_token_id = mask_token_id
        self.experts_held = experts_held
        assert remat in (None, "layer"), remat
        self.remat = remat


#: published shapes; the ``-b32`` siblings differ in ``block_length`` alone
SDAR_CONFIGS = {
    "sdar-30b-a3b-chat": dict(),        # the defaults above are its keys
}


class SdarMoeDecoderLayer(BaseLayer):
    def __init__(self, config, name):
        c = config
        self.attn = MultiHeadAttention(
            c.hidden_size, c.num_heads, sequence_length=c.positions,
            num_kv_heads=c.num_kv_heads, head_dim=c.head_dim, bias=False,
            rope_theta=c.rope_theta, qk_norm="head", qk_norm_eps=c.rms_eps,
            block_diffusion=c.block_length, name=f"{name}_attn")
        self.mlp = MoELayer(
            c.hidden_size, c.intermediate_size, num_experts=c.num_experts,
            k=c.moe_k, capacity_factor=None, expert_act="swiglu",
            renorm_topk=c.moe_renorm_topk, track_load=True,
            held=c.experts_held, name=f"{name}_moe")
        self.input_norm, self.post_norm = (
            RMSNorm(c.hidden_size, eps=c.rms_eps, name=f"{name}_{n}")
            for n in ("input_norm", "post_norm"))
        self._layer_scope = remat_scope if c.remat == "layer" else nullcontext

    def _attend(self, h):
        #: the attention sublayer's output node of the last call (a benchmark
        #: fetches one layer's beside the logits)
        self.attn_out = self.attn(h, h, h, seq_len=self.attn.sequence_length)
        return self.attn_out

    def __call__(self, x, seq_len=None):
        with self._layer_scope():       # the whole layer one recomputed group
            x = residual_sublayer(x, self.input_norm, self._attend)
            return residual_sublayer(x, self.post_norm, self.mlp)


class SdarMoeModel(LlamaModel):
    def _layer(self, i, name):
        return SdarMoeDecoderLayer(self.config, name)

    def __call__(self, input_ids):
        """``[B, 2L]`` ids, the clean copy first -> the final norm of the
        NOISED half, ``[B, L, hidden]``: the clean half is there to be seen."""
        with self._scope():
            x = self._embed(input_ids)
        for i, layer in enumerate(self.layers):
            with self._scope(i):
                x = layer(x)
        with (stage(self.pipeline_stages - 1) if self.pipeline_stages
              else nullcontext()), scope("hetu_head"):
            return self.norm(split_op(x, axes=1, indices=1, splits=2))


class WeightedMeanOp(Op):
    """``sum_i [labels_i >= 0] weights_i ce_i / T`` over ALL ``T`` positions of
    the batch: the diffusion bound's ``1 / t`` a position (``MaskedMeanOp`` is
    the plain mean over the labelled ones)."""

    def _compute(self, input_vals, ctx):
        import jax.numpy as jnp
        ce, labels, weights = (x.reshape(-1) for x in input_vals)
        return jnp.sum(ce.astype(jnp.float32) * (labels >= 0)
                       * weights.astype(jnp.float32)) / ce.shape[0]


class SdarMoeForCausalLM(LlamaForCausalLM):
    """``__call__`` takes ``[B, 2L]`` ids and returns the logits ``[B L,
    vocab]`` of the noised half; ``moe_loads`` is the base class's (``[4,
    count]`` where a share of the experts is held)."""
    model_cls = SdarMoeModel

    def __init__(self, config, name="sdar", pipeline_stages=None):
        super().__init__(config, name=name, pipeline_stages=pipeline_stages)

    def loss(self, input_ids, labels, weights):
        return self.loss_terms(input_ids, labels, weights)[0]

    def loss_terms(self, input_ids, labels, weights, logits=None):
        """``(loss, {"ce": loss, "ce_masked": ...})``: ``labels [B, L]`` hold
        a masked position's token and -1 elsewhere, ``weights [B, L]`` f32 the
        ``1 / t`` of a position's block; no shift, no balance term."""
        if logits is None:
            logits = self(input_ids)
        with scope("hetu_loss"):
            flat = array_reshape_op(labels, output_shape=(-1,))
            ce = softmax_cross_entropy_sparse_op(logits, flat,
                                                 ignored_index=-1)
            loss = WeightedMeanOp(ce, flat, weights)
            return loss, {"ce": loss, "ce_masked": MaskedMeanOp(ce, flat)}

    @property
    def attention_layers(self):
        return self.config.num_layers
