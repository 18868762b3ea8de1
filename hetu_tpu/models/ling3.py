"""Ling-3.0 decoder LMs (the language model of ``inclusionAI/Ling-3.0-flash-VL``;
the family's text-only checkpoints share the layers): a hybrid of two kinds
of mixer over a dense layer or two and then sparse expert layers.

Layer ``i`` (0-based) is latent attention (``layers/latent_attention.py``)
where ``(i + 1) % layer_group_size == 0`` and Kimi Delta Attention
(``layers/kda.py``) otherwise; its FFN is a dense SwiGLU of
``intermediate_size`` where ``i < first_k_dense_replace`` and otherwise the
expert block: a sigmoid-scored router with a selection bias that picks
``topk_group`` of ``n_group`` groups and then ``num_experts_per_tok`` of
their experts, the weights renormalised and scaled by
``routed_scaling_factor``, SwiGLU experts of ``moe_intermediate_size`` and one
ungated shared expert of ``moe_shared_expert_intermediate_size``.  Pre-norm, RMSNorm:

    x = x + mixer(N(x));  x = x + ffn(N(x));  final N, untied head

The pretraining loss is the mean next-token cross-entropy alone: the load is
balanced by the router's bias (``router_bias_update_rate``), no auxiliary
term.  **Not modelled**: the vision tower of the ``-VL`` checkpoints (the
published language config has no key of it), multi-token prediction, and the
clamp of ``expert_swiglu_limit_list`` / ``share_expert_swiglu_limit_list``: a
non-zero limit for a layer that is built is refused, not ignored.  Serving
(a recurrent state and a latent page in the cache) is not here.

``experts_held=(first, count)`` builds one chip's share of an
expert-parallel job (``MoELayer(held=)``), everything else whole.  ``remat``
names what the backward pass recomputes: ``"layer"`` (whole decoder layers,
the expert block included: routing, dispatch and the grouped products run
again), ``"mixer"`` (the KDA mixers with their norm) or None.
"""

from __future__ import annotations

from contextlib import nullcontext

from ..graph.node import remat as remat_scope
from ..layers import RMSNorm
from ..layers.base import BaseLayer
from ..layers.kda import KimiDeltaAttention
from ..layers.latent_attention import LatentAttention
from ..layers.moe import MoELayer
from .llama import (BiasBalanced, LlamaForCausalLM, LlamaMLP, LlamaModel,
                    residual_sublayer)


class Ling3Config:
    """Arguments are the published keys of the language model's
    ``config.json`` under their own names; ``seq_len``, ``experts_held``, the
    bias's update rate and what the job recomputes (``remat``) are not in
    it."""

    def __init__(self, vocab_size=157184, hidden_size=2560,
                 num_hidden_layers=42, num_attention_heads=32, head_dim=128,
                 layer_group_size=6, first_k_dense_replace=2,
                 intermediate_size=6144, num_experts=512,
                 num_experts_per_tok=8, moe_intermediate_size=768,
                 moe_shared_expert_intermediate_size=768,
                 score_function="sigmoid",
                 moe_router_enable_expert_bias=True, n_group=8, topk_group=4,
                 norm_topk_prob=True, routed_scaling_factor=2.5,
                 kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=6000000.0,
                 use_qk_norm=True, short_conv_kernel_size=4,
                 kda_lower_bound=-5.0, kda_safe_gate=True,
                 rms_norm_eps=1e-6, tie_word_embeddings=False,
                 expert_swiglu_limit_list=None,
                 share_expert_swiglu_limit_list=None,
                 router_bias_update_rate=1e-3, seq_len=2048,
                 experts_held=None, remat="layer"):
        assert score_function == "sigmoid", score_function
        assert kda_safe_gate, "the chunked rule needs the bounded gate"
        for name, limits in (
                ("expert_swiglu_limit_list", expert_swiglu_limit_list),
                ("share_expert_swiglu_limit_list",
                 share_expert_swiglu_limit_list)):
            built = list(limits or ())[:num_hidden_layers]
            if any(built):
                raise NotImplementedError(
                    f"{name}: the clamp on SwiGLU is not modelled and layer "
                    f"{next(i for i, x in enumerate(built) if x)} sets one")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_hidden_layers
        self.num_heads = num_attention_heads
        self.head_dim = head_dim
        self.layer_group_size = layer_group_size
        self.layer_types = tuple(
            "attention" if (i + 1) % layer_group_size == 0 else "kda"
            for i in range(num_hidden_layers))
        self.first_k_dense_replace = first_k_dense_replace
        self.dense_intermediate_size = intermediate_size
        self.num_experts = num_experts
        self.moe_k = num_experts_per_tok
        self.intermediate_size = moe_intermediate_size
        self.shared_width = moe_shared_expert_intermediate_size
        self.router_bias_update_rate = (
            router_bias_update_rate if moe_router_enable_expert_bias
            else None)
        self.router_groups = (n_group, topk_group)
        self.moe_renorm_topk = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.kv_lora_rank = kv_lora_rank
        self.q_lora_rank = q_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = rope_theta
        self.use_qk_norm = use_qk_norm
        self.conv_kernel = short_conv_kernel_size
        self.kda_lower_bound = kda_lower_bound
        self.rms_eps = rms_norm_eps
        self.tie_embeddings = tie_word_embeddings
        self.seq_len = seq_len
        self.experts_held = experts_held
        assert remat in (None, "mixer", "layer"), remat
        self.remat = remat


#: published shapes
LING3_CONFIGS = {
    "ling-3.0-flash": dict(),           # the defaults above are its keys
}


class Ling3DecoderLayer(BaseLayer):
    def __init__(self, config, index, name):
        c = config
        self.kind = c.layer_types[index]

        def norm(n):
            return RMSNorm(c.hidden_size, eps=c.rms_eps, name=f"{name}_{n}")
        if self.kind == "attention":
            self.mixer = LatentAttention(
                c.hidden_size, c.num_heads, c.kv_lora_rank,
                c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                rope_theta=c.rope_theta, qk_norm=c.use_qk_norm,
                head_gate=True, eps=c.rms_eps, q_lora_rank=c.q_lora_rank,
                name=f"{name}_mla")
        else:
            self.mixer = KimiDeltaAttention(
                c.hidden_size, c.num_heads, c.head_dim,
                conv_kernel=c.conv_kernel, lower_bound=c.kda_lower_bound,
                eps=c.rms_eps, name=f"{name}_kda")
        self.dense = index < c.first_k_dense_replace
        if self.dense:
            self.mlp = LlamaMLP(c.hidden_size, c.dense_intermediate_size,
                                name=f"{name}_mlp")
        else:
            self.mlp = MoELayer(
                c.hidden_size, c.intermediate_size,
                num_experts=c.num_experts, k=c.moe_k, capacity_factor=None,
                expert_act="swiglu", renorm_topk=c.moe_renorm_topk,
                track_load=True, held=c.experts_held,
                shared_width=c.shared_width or None, shared_gate=False,
                router_score="sigmoid", router_scale=c.routed_scaling_factor,
                router_bias_rate=c.router_bias_update_rate,
                router_groups=c.router_groups, name=f"{name}_moe")
        self.input_norm, self.post_norm = norm("input_norm"), norm("post_norm")
        self._layer_scope = remat_scope if c.remat == "layer" else nullcontext
        self.recompute = c.remat == "mixer" and self.kind == "kda"

    def _mix(self, h):
        #: the mixer's output node of the last call (a benchmark fetches the
        #: latent layer's beside the logits)
        self.mixer_out = self.mixer(h)
        return self.mixer_out

    def __call__(self, x, seq_len=None):
        with self._layer_scope():       # the whole layer one recomputed group
            x = residual_sublayer(x, self.input_norm, self._mix,
                                  self.recompute)
            return residual_sublayer(x, self.post_norm, self.mlp)


class Ling3Model(LlamaModel):
    def _layer(self, i, name):
        return Ling3DecoderLayer(self.config, i, name)


class Ling3ForCausalLM(BiasBalanced, LlamaForCausalLM):
    """``moe_loads`` is the base class's over the expert layers (``[4,
    count]`` where a share of the experts is held); the loss is the
    cross-entropy alone (``BiasBalanced``)."""
    model_cls = Ling3Model

    def __init__(self, config, name="ling3", pipeline_stages=None):
        super().__init__(config, name=name, pipeline_stages=pipeline_stages)

    @property
    def attention_layers(self):
        return self.config.layer_types.count("attention")
