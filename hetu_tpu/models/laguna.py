"""Laguna decoder LMs (``poolside/Laguna-XS.2``, ``model_type`` "laguna"):
window and full attention mixed by layer, each kind with its own head count
and its own rotary parametrisation, a gate a head, and many small experts
behind a dense layer.

Layer ``l`` (0-based), pre-norm, RMSNorm::

    a = x + Attn_l(N(x));  y = a + F_l(N(a));  final N, untied head

``Attn_l`` has ``num_attention_heads_per_layer[l]`` query heads on
``num_key_value_heads`` key heads of ``head_dim``, no bias and no norm on q or
k.  Where ``layer_types[l]`` is ``sliding_attention`` position ``i`` sees the
keys ``j`` with ``0 <= i - j < sliding_window`` and rotary follows
``rope_parameters["sliding_attention"]``; where it is ``full_attention`` every
earlier key, and ``rope_parameters["full_attention"]`` (YaRN on the first
``partial_rotary_factor`` of a head).  With ``gating`` each head's context is
multiplied by ``sigmoid(x W_g)_h``, one number a head and a token, before
``W_o`` (``MultiHeadAttention(output_gate="head")``).  ``F_l`` is the dense
SwiGLU of ``intermediate_size`` where ``mlp_layer_types[l]`` is ``dense``, else
the expert block: sigmoid scores over all experts, the
``num_experts_per_tok`` largest, their scores normalised over the chosen and
times ``moe_routed_scaling_factor`` on the experts' outputs, SwiGLU experts of
``moe_intermediate_size`` and one ungated shared expert of
``shared_expert_intermediate_size``.  The loss is the mean next-token
cross-entropy alone.

``experts_held=(first, count)`` builds one chip's share of an expert-parallel
job (``MoELayer(held=)``), everything else whole.  ``remat`` names what the
backward pass recomputes: ``"layer"`` (whole decoder layers), ``"window"``
(the window layers' attention sublayer with its norm) or None.  **Not
modelled**: the cache at inference (a window layer's pages could be freed
behind the window: ``serving/kv_cache.py`` keeps them), lengths past the
tables', a router bias or groups (no published key names one).
"""

from __future__ import annotations

from contextlib import nullcontext

from ..graph.node import remat as remat_scope
from ..layers import RMSNorm
from ..layers.attention import MultiHeadAttention
from ..layers.base import BaseLayer
from ..layers.moe import MoELayer
from ..ops.rotary import yarn_scaling
from .llama import (BiasBalanced, LlamaForCausalLM, LlamaMLP, LlamaModel,
                    residual_sublayer)

KINDS = ("full_attention", "sliding_attention")


class LagunaConfig:
    """Arguments are the published keys of ``config.json`` under their own
    names; ``seq_len``, ``experts_held`` and what the job recomputes
    (``remat``) are not in it."""

    def __init__(self, vocab_size=100352, hidden_size=2048,
                 intermediate_size=8192, num_hidden_layers=40,
                 num_attention_heads=48, num_key_value_heads=8, head_dim=128,
                 max_position_embeddings=262144, attention_bias=False,
                 rms_norm_eps=1e-6, num_experts=256, num_experts_per_tok=8,
                 moe_intermediate_size=512,
                 shared_expert_intermediate_size=512,
                 tie_word_embeddings=False, gating=True, sliding_window=512,
                 rope_parameters=None, layer_types=None,
                 moe_apply_router_weight_on_input=False,
                 partial_rotary_factor=0.5, mlp_layer_types=None,
                 moe_routed_scaling_factor=2.5,
                 num_attention_heads_per_layer=None, seq_len=2048,
                 experts_held=None, remat=None, router_score="sigmoid",
                 expert_axis=None):
        n = num_hidden_layers
        assert not attention_bias, "the attention layer is built without bias"
        assert not moe_apply_router_weight_on_input, (
            "the router's weights go on the experts' outputs")
        assert gating in (True, False), gating
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = n
        self.dense_intermediate_size = intermediate_size
        self.num_kv_heads = num_key_value_heads
        self.head_dim = head_dim
        self.rms_eps = rms_norm_eps
        self.num_experts = num_experts
        self.moe_k = num_experts_per_tok
        self.intermediate_size = moe_intermediate_size
        self.shared_width = shared_expert_intermediate_size
        self.tie_embeddings = tie_word_embeddings
        self.gating = gating
        self.sliding_window = sliding_window
        self.routed_scaling_factor = moe_routed_scaling_factor
        #: how the router scores (``TopKGate(score=)``) and the mesh axis the
        #: experts are spread over (``MoELayer(ep_axis=)``): a family's, no
        #: published key of this one
        self.router_score = router_score
        self.expert_axis = expert_axis
        # a layer reads the three lists by its index: a model cut to its
        # first layers reads their first entries
        self.layer_types = tuple(
            layer_types or ("full_attention" if i % 4 == 0
                            else "sliding_attention" for i in range(n)))[:n]
        self.mlp_layer_types = tuple(
            mlp_layer_types or ("dense" if i == 0 else "sparse"
                                for i in range(n)))[:n]
        self.heads_per_layer = tuple(
            num_attention_heads_per_layer or [num_attention_heads] * n)[:n]
        for name in ("layer_types", "mlp_layer_types", "heads_per_layer"):
            assert len(getattr(self, name)) == n, (name, n)
        assert set(self.layer_types) <= set(KINDS), self.layer_types
        assert set(self.mlp_layer_types) <= {"dense", "sparse"}
        rope = rope_parameters or {
            "full_attention": {"rope_type": "default", "rope_theta": 10000.0,
                               "partial_rotary_factor":
                                   partial_rotary_factor},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000.0,
                                  "partial_rotary_factor": 1}}
        #: kind -> the rotary arguments of ``MultiHeadAttention``
        self.rope = {kind: self._rotary(rope[kind]) for kind in KINDS}
        self.seq_len = seq_len
        assert seq_len <= max_position_embeddings, seq_len
        self.experts_held = experts_held
        assert remat in (None, "window", "layer"), remat
        self.remat = remat

    def _rotary(self, p):
        """One ``rope_parameters`` group as the layer's keywords."""
        turned = int(self.head_dim * p.get("partial_rotary_factor", 1))
        kw = {"rope_theta": float(p["rope_theta"]),
              "rotary_dim": None if turned == self.head_dim else turned}
        kind = p.get("rope_type", "default")
        if kind == "yarn":
            kw["rope_scaling"] = yarn_scaling(
                p["factor"], p["original_max_position_embeddings"],
                p.get("beta_fast", 32), p.get("beta_slow", 1),
                p.get("attention_factor"))
        else:
            assert kind == "default", f"rope_type {kind!r} is not built"
        return kw


#: published shapes
LAGUNA_CONFIGS = {
    "laguna-xs.2": dict(rope_parameters={
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
        num_attention_heads_per_layer=[48 if i % 4 == 0 else 64
                                       for i in range(40)]),
}


class LagunaDecoderLayer(BaseLayer):
    def __init__(self, config, index, name, rope_tables=None):
        c = config
        self.kind = c.layer_types[index]
        window = c.sliding_window if self.kind == "sliding_attention" else None
        self.attn = MultiHeadAttention(
            c.hidden_size, c.heads_per_layer[index],
            sequence_length=c.seq_len, causal_mask=True,
            num_kv_heads=c.num_kv_heads, head_dim=c.head_dim, bias=False,
            output_gate="head" if c.gating else False, window=window,
            rope_tables=rope_tables, name=f"{name}_attn",
            **c.rope[self.kind])
        self.dense = c.mlp_layer_types[index] == "dense"
        if self.dense:
            self.mlp = LlamaMLP(c.hidden_size, c.dense_intermediate_size,
                                name=f"{name}_mlp")
        else:
            self.mlp = MoELayer(
                c.hidden_size, c.intermediate_size,
                num_experts=c.num_experts, k=c.moe_k, capacity_factor=None,
                expert_act="swiglu", renorm_topk=True, track_load=True,
                held=c.experts_held, shared_width=c.shared_width or None,
                shared_gate=False, router_score=c.router_score,
                router_scale=c.routed_scaling_factor, ep_axis=c.expert_axis,
                name=f"{name}_moe")
        self.input_norm, self.post_norm = (
            RMSNorm(c.hidden_size, eps=c.rms_eps, name=f"{name}_{n}")
            for n in ("input_norm", "post_norm"))
        self._layer_scope = remat_scope if c.remat == "layer" else nullcontext
        self.recompute = c.remat == "window" and window is not None

    def _attend(self, h):
        #: the attention sublayer's output node of the last call (a benchmark
        #: fetches a window layer's and a full layer's beside the logits)
        self.attn_out = self.attn(h, h, h, seq_len=self.attn.sequence_length)
        return self.attn_out

    def __call__(self, x, seq_len=None):
        with self._layer_scope():       # the whole layer one recomputed group
            x = residual_sublayer(x, self.input_norm, self._attend,
                                  self.recompute)
            return residual_sublayer(x, self.post_norm, self.mlp)


class LagunaModel(LlamaModel):
    def _layer(self, i, name):
        return LagunaDecoderLayer(self.config, i, name,
                                  rope_tables=self.rope_tables)


class LagunaForCausalLM(BiasBalanced, LlamaForCausalLM):
    """The loss is the cross-entropy alone (``BiasBalanced``) and
    ``moe_loads`` is over the expert layers (``[4, count]`` where a share of
    the experts is held)."""
    model_cls = LagunaModel

    def __init__(self, config, name="laguna", pipeline_stages=None):
        super().__init__(config, name=name, pipeline_stages=pipeline_stages)

    def layers_of(self, kind):
        """How many of the model's layers are of ``kind``
        (``"full_attention"`` or ``"sliding_attention"``)."""
        return self.config.layer_types.count(kind)

    @property
    def attention_layers(self):
        """The layers that see every earlier key (flash attention's)."""
        return self.layers_of("full_attention")
