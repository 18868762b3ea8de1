"""ZAYA1 decoder LMs (``Zyphra/ZAYA1-8B``, ``model_type`` "zaya";
arXiv:2511.17127): attention inside a compressed latent and top-1 experts
chosen by a router that carries its own state down the depth.

Layer ``l`` (``layer_types[l] == "hybrid"``), RMSNorm ``N`` with a weight::

    a = M_a(x, CCA(N(x)));   y = M_f(a, Experts(N(a), r_(l-1)));   final N, tied head

``M(x, f) = s_r (x + b_r) + s_f (f + b_f)`` is the residual-scaled merge
(``scale_residual_merge``; ``models/llama.py ResidualMerge``), ``CCA`` is
``layers/compressed_attention.py`` (``num_attention_heads`` query on
``num_key_value_heads`` key heads of ``head_dim``, ``cca_time0`` depthwise and
``cca_time1`` head-mixing taps, rotary on the first ``partial_rotary_factor``
of a head with ``rope_parameters["hybrid"]``'s base).  The expert sublayer's
router is ``layers/moe.py StateRouter`` of width ``router_hidden_size``: its
state ``r_l`` adds ``gamma_l * r_(l-1)`` (``use_eda``: the state of the layer
above AFTER its own sum), it has ``num_experts + 1`` outputs, the token takes
the ONE largest of ``softmax + bias`` with that probability as its weight, and
the last output is no expert (``use_mod``: the token's sublayer is then its
scaled residual and ``b_f`` alone).  Experts are SwiGLU of
``moe_intermediate_size``, no shared expert; the bias moves by
``router_bias_update_rate`` against the load after each step; the loss is the
mean next-token cross-entropy alone.

A decoder layer is called ``(x, r) -> (x', r')``; the model walks the pair
down the stack.  ``experts_held=(first, count)`` builds one chip's share of an
expert-parallel job (``MoELayer(held=)``), everything else whole.
``remat="layer"`` makes a layer one ``ht.remat()`` group whose boundary is the
pair ``(x, r)``.  **Not built**: pipeline stages (the router state is not sent
between stages yet), a layer of the ``hybrid_sliding`` kind (``sliding_window``
is null in the 8B), the cache at inference (latent keys and values, the
convolutions' and the shift's last inputs).
"""

from __future__ import annotations

from contextlib import nullcontext

from ..graph.node import remat as remat_scope, scope
from ..layers import RMSNorm
from ..layers.base import BaseLayer
from ..layers.compressed_attention import CompressedConvAttention
from ..layers.moe import MoELayer, StateRouter
from .llama import (BiasBalanced, LlamaForCausalLM, LlamaModel, ResidualMerge,
                    residual_sublayer)


class Zaya1Config:
    """Arguments are the published keys of ``config.json`` under their own
    names; ``use_eda``, ``use_mod`` and ``scale_residual_merge`` are the
    sibling configurations' ``zaya_use_eda``, ``zaya_use_mod`` and
    ``scale_residual_merge``; ``seq_len``, ``experts_held``, the bias's update
    rate and what the job recomputes (``remat``) are in none."""

    def __init__(self, vocab_size=262272, hidden_size=2048,
                 num_hidden_layers=40, num_attention_heads=8,
                 num_key_value_heads=2, head_dim=128, cca_time0=2,
                 cca_time1=2, partial_rotary_factor=0.5,
                 rope_parameters=None, layer_types=None, sliding_window=None,
                 router_hidden_size=256, num_experts=16,
                 num_experts_per_tok=1, moe_intermediate_size=2048,
                 rms_norm_eps=1e-5, tie_word_embeddings=True,
                 max_position_embeddings=131072, attention_bias=False,
                 hidden_act="silu", lm_head_bias=False, seq_len=2048,
                 experts_held=None, use_eda=True, use_mod=True,
                 scale_residual_merge=True, router_bias_update_rate=1e-3,
                 remat=None):
        n = num_hidden_layers
        assert hidden_act == "silu" and not attention_bias
        assert not lm_head_bias, "the head is built without a bias"
        assert sliding_window is None, "no layer with a window is built"
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = n
        self.num_heads = num_attention_heads
        self.num_kv_heads = num_key_value_heads
        self.head_dim = head_dim
        self.conv_taps = (cca_time0, cca_time1)
        self.layer_types = tuple(layer_types or ["hybrid"] * n)[:n]
        assert self.layer_types == ("hybrid",) * n, self.layer_types
        rope = (rope_parameters or {"hybrid": {
            "rope_type": "default", "rope_theta": 5000000,
            "partial_rotary_factor": partial_rotary_factor}})["hybrid"]
        assert rope.get("rope_type", "default") == "default", rope
        self.rope_theta = float(rope["rope_theta"])
        self.rotary_dim = int(head_dim * rope.get("partial_rotary_factor",
                                                  partial_rotary_factor))
        self.router_width = router_hidden_size
        self.num_experts = num_experts
        self.moe_k = num_experts_per_tok
        self.intermediate_size = moe_intermediate_size
        self.rms_eps = rms_norm_eps
        self.tie_embeddings = tie_word_embeddings
        self.seq_len = seq_len
        assert seq_len <= max_position_embeddings, seq_len
        self.experts_held = experts_held
        self.use_eda, self.use_mod = use_eda, use_mod
        self.scale_residual_merge = scale_residual_merge
        self.router_bias_update_rate = router_bias_update_rate
        assert remat in (None, "layer"), remat
        self.remat = remat


#: published shapes
ZAYA1_CONFIGS = {
    "zaya1-8b": dict(rope_parameters={
        "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"},
        "hybrid_sliding": {"partial_rotary_factor": 0.5, "rope_theta": 10000,
                           "rope_type": "default"},
        "rope_type": "default"}),
}


class Zaya1DecoderLayer(BaseLayer):
    def __init__(self, config, name, rope_tables=None):
        c = config
        self.attn = CompressedConvAttention(
            c.hidden_size, c.num_heads, c.num_kv_heads, c.head_dim,
            conv_taps=c.conv_taps, rotary_dim=c.rotary_dim,
            rope_theta=c.rope_theta, rope_tables=rope_tables,
            sequence_length=c.seq_len, name=f"{name}_cca")
        self.mlp = MoELayer(
            c.hidden_size, c.intermediate_size, num_experts=c.num_experts,
            k=c.moe_k, capacity_factor=None, expert_act="swiglu",
            renorm_topk=False, track_load=True, held=c.experts_held,
            router=StateRouter(c.hidden_size, c.num_experts, c.router_width,
                               skip=1 if c.use_mod else 0, eps=c.rms_eps,
                               bias_rate=c.router_bias_update_rate,
                               name=f"{name}_router"),
            name=f"{name}_moe")
        self.input_norm, self.post_norm = (
            RMSNorm(c.hidden_size, eps=c.rms_eps, name=f"{name}_{n}")
            for n in ("input_norm", "post_norm"))
        self.attn_merge, self.mlp_merge = (
            ResidualMerge(c.hidden_size, name=f"{name}_{n}")
            if c.scale_residual_merge else None
            for n in ("attn_merge", "mlp_merge"))
        self.use_eda = c.use_eda
        self._layer_scope = remat_scope if c.remat == "layer" else nullcontext

    def __call__(self, x, r=None):
        """``(x, r) -> (x', r')``: ``r`` the router state of the layer above
        (None: the stack's first layer), ``r'`` this layer's."""
        state = r if self.use_eda else None
        with self._layer_scope():       # the whole layer one recomputed group
            x = residual_sublayer(x, self.input_norm, self.attn,
                                  merge=self.attn_merge)
            x = residual_sublayer(x, self.post_norm,
                                  lambda h: self.mlp(h, state=state),
                                  merge=self.mlp_merge)
            return x, self.mlp.state


class Zaya1Model(LlamaModel):
    def _layer(self, i, name):
        return Zaya1DecoderLayer(self.config, name,
                                 rope_tables=self.rope_tables)

    def __call__(self, input_ids):
        x, r = self._embed(input_ids), None
        #: each layer's router state, as the last call handed it on
        self.states = []
        for layer in self.layers:
            x, r = layer(x, r)
            self.states.append(r)
        with scope("hetu_head"):
            return self.norm(x)


class Zaya1ForCausalLM(BiasBalanced, LlamaForCausalLM):
    """The loss is the cross-entropy alone (``BiasBalanced``); ``moe_loads``
    and ``router_biases`` are over every layer (a load is ``[5, count]``: the
    fifth row the skipped pairs and the state's RMS)."""
    model_cls = Zaya1Model

    def __init__(self, config, name="zaya1", pipeline_stages=None):
        if pipeline_stages and pipeline_stages > 1:
            raise NotImplementedError(
                "the router state is not sent between stages yet")
        super().__init__(config, name=name, pipeline_stages=None)

    def router_states(self):
        """One ``[B, S, router_hidden_size]`` f32 node a layer: the router
        state it handed on (a comparison's; never a train step's)."""
        return list(self.model.states)

    def cca_qk(self):
        """One pair of nodes a layer, ``(q^ [B, S, H d], k^ [B, S, J d])``
        behind the mixing and before the rotary (a comparison's)."""
        return [layer.attn.qk for layer in self.model.layers]

    @property
    def attention_layers(self):
        return len(self.model.layers)
