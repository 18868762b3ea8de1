"""Qwen3-Next decoder LMs (HF ``model_type`` ``qwen3_next``,
``Qwen/Qwen3-Next-80B-A3B``): a hybrid of two kinds of layer.

Layer ``i`` (0-based) is full attention where ``(i + 1) %
full_attention_interval == 0`` and a Gated DeltaNet (``layers/
gated_delta_net.py``) otherwise; every layer's FFN is the sparse block: top-k
of ``num_experts`` SwiGLU experts with the weights renormalised, plus one
shared expert gated by a sigmoid.  Norms are RMSNorm with the weight stored
about zero (``1 + w``).

    x = x + mixer(N(x));  x = x + moe(N(x));  final N, untied head

The full-attention mixer: 16 query heads and 2 KV heads of 256 (16 x 256 is
twice the hidden size), a sigmoid output gate carried in a doubled
``q_proj`` (per head: query, then gate), RMSNorm on each head's q and k,
rotary on the first ``partial_rotary_factor`` of a head's dimensions.

The pretraining loss is the mean next-token cross-entropy plus
``router_aux_loss_coef`` times the balance loss (the form of HF
``load_balancing_loss_func``) summed over layers; ``loss_terms`` and
``moe_loads`` are ``LlamaForCausalLM``'s.  The multi-token-prediction module
of the released checkpoints has no key in the published config and is not
modelled.  Serving (a recurrent state in the cache) is not here.

``experts_held=(first, count)`` builds one chip's share of an
expert-parallel job: every layer holds ``count`` of the ``num_experts``
experts (``MoELayer(held=)``), everything else whole.  ``remat`` names what
the backward pass recomputes: ``"gdn"`` (the DeltaNet mixers: their
projections, convolution and chunk matrices are most of the activations a
layer keeps; the attention layers keep theirs, so flash attention's forward
kernel runs once a layer and step) or None.
"""

from __future__ import annotations

from ..layers import RMSNorm
from ..layers.attention import MultiHeadAttention
from ..layers.base import BaseLayer
from ..layers.gated_delta_net import GatedDeltaNet
from ..layers.moe import MoELayer
from .llama import LlamaForCausalLM, LlamaModel, residual_sublayer


class Qwen3NextConfig:
    """Arguments are the published keys of ``config.json`` under their own
    names; ``seq_len``, ``experts_held``, the loss weight and what the job
    recomputes (``remat``) are not in it."""

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 num_hidden_layers=48, num_attention_heads=16,
                 num_key_value_heads=2, head_dim=256,
                 partial_rotary_factor=0.25, rope_theta=10000000.0,
                 rms_norm_eps=1e-6, full_attention_interval=4,
                 linear_conv_kernel_dim=4, linear_key_head_dim=128,
                 linear_value_head_dim=128, linear_num_key_heads=16,
                 linear_num_value_heads=32, num_experts=512,
                 num_experts_per_tok=10, moe_intermediate_size=512,
                 shared_expert_intermediate_size=512, norm_topk_prob=True,
                 router_aux_loss_coef=0.001, tie_word_embeddings=False,
                 seq_len=2048, experts_held=None, remat="gdn"):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_hidden_layers
        self.num_heads = num_attention_heads
        self.num_kv_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rotary_dim = int(head_dim * partial_rotary_factor)
        self.rope_theta = rope_theta
        self.rms_eps = rms_norm_eps
        self.full_attention_interval = full_attention_interval
        #: derived as HF derives it when the config gives none
        self.layer_types = tuple(
            "full_attention" if (i + 1) % full_attention_interval == 0
            else "linear_attention" for i in range(num_hidden_layers))
        self.conv_kernel = linear_conv_kernel_dim
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.num_experts = num_experts
        self.moe_k = num_experts_per_tok
        self.intermediate_size = moe_intermediate_size
        self.shared_width = shared_expert_intermediate_size
        self.moe_renorm_topk = norm_topk_prob
        self.moe_aux_coeff = router_aux_loss_coef
        self.moe_z_coeff = 0.0
        self.tie_embeddings = tie_word_embeddings
        self.seq_len = seq_len
        self.experts_held = experts_held
        assert remat in (None, "gdn"), remat
        self.remat = remat


#: published shapes; Instruct and Thinking share one config.json
QWEN3_NEXT_CONFIGS = {
    "qwen3-next-80b-a3b": dict(),       # the defaults above are its keys
}


class Qwen3NextDecoderLayer(BaseLayer):
    def __init__(self, config, kind, name):
        c = config
        self.kind = kind

        def norm(n):
            return RMSNorm(c.hidden_size, eps=c.rms_eps, zero_centered=True,
                           name=f"{name}_{n}")
        if kind == "full_attention":
            self.attn = MultiHeadAttention(
                c.hidden_size, c.num_heads, sequence_length=c.seq_len,
                causal_mask=True, num_kv_heads=c.num_kv_heads,
                rope_theta=c.rope_theta, bias=False, head_dim=c.head_dim,
                rotary_dim=c.rotary_dim, qk_norm="head",
                qk_norm_eps=c.rms_eps, qk_norm_zero_centered=True,
                output_gate=True, name=f"{name}_attn")
        else:
            self.gdn = GatedDeltaNet(
                c.hidden_size, c.linear_num_key_heads,
                c.linear_num_value_heads, c.linear_key_head_dim,
                c.linear_value_head_dim, conv_kernel=c.conv_kernel,
                eps=c.rms_eps, name=f"{name}_gdn")
        self.mlp = MoELayer(
            c.hidden_size, c.intermediate_size, num_experts=c.num_experts,
            k=c.moe_k, capacity_factor=None, expert_act="swiglu",
            renorm_topk=c.moe_renorm_topk, track_load=True,
            held=c.experts_held, shared_width=c.shared_width,
            name=f"{name}_moe")
        self.input_norm, self.post_norm = norm("input_norm"), norm("post_norm")
        self.recompute = c.remat == "gdn" and kind == "linear_attention"

    def __call__(self, x, seq_len=None):
        mixer = self.attn if self.kind == "full_attention" else self.gdn
        x = residual_sublayer(x, self.input_norm, mixer, self.recompute,
                              seq_len=seq_len)
        return residual_sublayer(x, self.post_norm, self.mlp)


class Qwen3NextModel(LlamaModel):
    def _layer(self, i, name):
        return Qwen3NextDecoderLayer(self.config, self.config.layer_types[i],
                                     name)

    def _norm(self, name):
        return RMSNorm(self.config.hidden_size, eps=self.config.rms_eps,
                       zero_centered=True, name=name)


class Qwen3NextForCausalLM(LlamaForCausalLM):
    """``loss``, ``loss_terms`` and ``moe_loads`` are the base class's: the
    balance loss summed over layers at ``moe_aux_coeff``, one load node a
    layer (``[3, count]`` where a share of the experts is held)."""
    model_cls = Qwen3NextModel

    def __init__(self, config, name="qwen3next", pipeline_stages=None):
        super().__init__(config, name=name, pipeline_stages=pipeline_stages)

    @property
    def attention_layers(self):
        return self.config.layer_types.count("full_attention")
