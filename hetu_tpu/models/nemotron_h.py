"""Nemotron-H decoder LMs (HF ``model_type`` ``nemotron_h``,
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B``): blocks of ONE sublayer each,
chosen by a pattern string.

    x = x + mixer_i(N(x))  for each character of hybrid_override_pattern;
    final N, untied head

``M`` is a Mamba-2 mixer (``layers/mamba2.py``), ``E`` a sparse expert layer,
``*`` attention; ``N`` is RMSNorm with the weight about one.  There is no
mixer-then-FFN pair: the published model is 23 ``M``, 23 ``E`` and 6 ``*``.

The expert layer: ``s = sigmoid(x W_r)`` in f32 over all routed experts, the
``num_experts_per_tok`` largest of ``s + bias`` chosen, their weights ``s``
(without the bias) over their sum, times ``routed_scaling_factor``; experts
and the one shared expert are ``W_d relu(W_u x)^2`` (``relu2``, not gated;
the shared expert has no sigmoid gate).  The bias is no weight: the training
step moves it by ``router_bias_update_rate * sign(mean(load) - load)``.
Attention is grouped-query (32 query heads on 2 KV heads of 128), causal,
without bias and without rotary: the family's modelling code applies none.

The pretraining loss is the mean next-token cross-entropy plus
``router_aux_loss_coef`` times the balance loss ``E sum_e f_e P_e`` (``f_e``
the share of the pairs routed to ``e``, ``P_e`` the mean of ``s_e / sum_j
s_j``; DeepSeek-V3's form) summed over the expert layers.  Serving (a
state-space state and a convolution's last inputs in the cache) is not
here.

``experts_held=(first, count)`` builds one chip's share of an
expert-parallel job: every expert layer holds ``count`` of the
``n_routed_experts`` experts (``MoELayer(held=)``: rows for twice the mean
share a pass, and further passes for what a batch routes here beyond
that), everything else whole.
``remat`` names what the backward pass recomputes: ``"mamba"`` (the Mamba-2
mixers: their projections, convolution and chunk matrices are most of the
activations a block keeps) or None.
"""

from __future__ import annotations

from ..layers import RMSNorm
from ..layers.attention import MultiHeadAttention
from ..layers.base import BaseLayer
from ..layers.mamba2 import Mamba2
from ..layers.moe import MoELayer
from .llama import (BiasBalanced, LlamaForCausalLM, LlamaModel,
                    residual_sublayer)

#: the published pattern: 23 Mamba-2 mixers, 23 expert layers, 6 attention
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


class NemotronHConfig:
    """Arguments are the published keys of ``config.json`` under their own
    names; ``seq_len``, ``experts_held``, the balance loss's weight, the
    bias's update rate and what the job recomputes (``remat``) are not in
    it."""

    def __init__(self, vocab_size=131072, hidden_size=2688,
                 num_hidden_layers=52, hybrid_override_pattern=PATTERN,
                 num_attention_heads=32, num_key_value_heads=2, head_dim=128,
                 mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128,
                 n_groups=8, conv_kernel=4, chunk_size=128,
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=0.0001, n_routed_experts=128,
                 num_experts_per_tok=6, moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 n_shared_experts=1, norm_topk_prob=True,
                 routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
                 rescale_prenorm_residual=True, tie_word_embeddings=False,
                 router_aux_loss_coef=1e-4, router_bias_update_rate=1e-3,
                 seq_len=2048, experts_held=None, remat="mamba"):
        assert len(hybrid_override_pattern) == num_hidden_layers, (
            hybrid_override_pattern, num_hidden_layers)
        assert set(hybrid_override_pattern) <= set("ME*"), (
            "blocks are M (Mamba-2), E (experts) or * (attention): "
            + hybrid_override_pattern)
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_hidden_layers
        self.pattern = hybrid_override_pattern
        self.num_heads = num_attention_heads
        self.num_kv_heads = num_key_value_heads
        self.head_dim = head_dim
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.ssm_state_size = ssm_state_size
        self.n_groups = n_groups
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.time_step = (time_step_min, time_step_max, time_step_floor)
        self.num_experts = n_routed_experts
        self.moe_k = num_experts_per_tok
        self.intermediate_size = moe_intermediate_size
        self.shared_width = (moe_shared_expert_intermediate_size
                             * n_shared_experts)
        self.moe_renorm_topk = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.rms_eps = layer_norm_epsilon
        self.rescale_prenorm_residual = rescale_prenorm_residual
        self.tie_embeddings = tie_word_embeddings
        self.moe_aux_coeff = router_aux_loss_coef
        self.moe_z_coeff = 0.0
        self.router_bias_update_rate = router_bias_update_rate
        self.seq_len = seq_len
        self.experts_held = experts_held
        assert remat in (None, "mamba"), remat
        self.remat = remat


#: published shapes
NEMOTRON_H_CONFIGS = {
    "nemotron-3-nano-30b-a3b": dict(),  # the defaults above are its keys
}


class NemotronHBlock(BaseLayer):
    """One sublayer behind one norm and one residual.  An expert block's
    layer is ``mlp`` (what the loss terms and the load read)."""

    def __init__(self, config, kind, name):
        c = config
        self.kind = kind
        self.norm = RMSNorm(c.hidden_size, eps=c.rms_eps, name=f"{name}_norm")
        self.mlp = None
        if kind == "M":
            dt_min, dt_max, dt_floor = c.time_step
            self.mixer = Mamba2(
                c.hidden_size, c.mamba_num_heads, c.mamba_head_dim,
                c.n_groups, c.ssm_state_size, conv_kernel=c.conv_kernel,
                chunk=c.chunk_size, eps=c.rms_eps, dt_min=dt_min,
                dt_max=dt_max, dt_floor=dt_floor,
                out_scale=(c.num_layers ** -0.5
                           if c.rescale_prenorm_residual else 1.0),
                name=f"{name}_mamba")
        elif kind == "*":
            self.mixer = MultiHeadAttention(
                c.hidden_size, c.num_heads, sequence_length=c.seq_len,
                causal_mask=True, num_kv_heads=c.num_kv_heads,
                rope_theta=None, bias=False, head_dim=c.head_dim,
                name=f"{name}_attn")
        else:
            self.mixer = self.mlp = MoELayer(
                c.hidden_size, c.intermediate_size,
                num_experts=c.num_experts, k=c.moe_k, capacity_factor=None,
                expert_act="relu2", renorm_topk=c.moe_renorm_topk,
                track_load=True, held=c.experts_held,
                shared_width=c.shared_width, shared_gate=False,
                router_score="sigmoid", router_scale=c.routed_scaling_factor,
                router_bias_rate=c.router_bias_update_rate,
                name=f"{name}_moe")
        self.recompute = c.remat == "mamba" and kind == "M"

    def __call__(self, x, seq_len=None):
        return residual_sublayer(x, self.norm, self.mixer, self.recompute,
                                 seq_len=seq_len)


class NemotronHModel(LlamaModel):
    def _layer(self, i, name):
        return NemotronHBlock(self.config, self.config.pattern[i], name)


class NemotronHForCausalLM(BiasBalanced, LlamaForCausalLM):
    """``router_biases`` and ``moe_loads`` are over the expert blocks, one
    node each (a load is ``[3, count]`` where a share of the experts is
    held)."""
    model_cls = NemotronHModel
    #: the bias balances AND the balance loss is kept: the base class's, summed
    #: over the expert blocks at ``moe_aux_coeff``
    loss_terms = LlamaForCausalLM.loss_terms

    def __init__(self, config, name="nemotronh", pipeline_stages=None):
        super().__init__(config, name=name, pipeline_stages=pipeline_stages)

    @property
    def attention_layers(self):
        return self.config.pattern.count("*")
