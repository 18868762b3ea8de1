"""Xing4.0 decoder LMs (``XingChen-AGI/Xing4.0-29B-A4B``, ``model_type``
``xing4_0``): a DeepSeek-V3-style mixture of experts whose residual is four
streams mixed by manifold-constrained hyper-connections
(``layers/hyper_connection.py``), with a multi-token-prediction depth.

    X_0 = (e, e, e, e);   per layer, two hyper-connected sublayers:
        X = HC_a(X, MLA(N(.)));  X = HC_f(X, FFN(N(.)))
    h = sum_i X_L[i];  logits = Head(N(h))

The mixer is latent attention with a low-rank query, YaRN on the rotary part
and DeepSeek's ``mscale`` in the scores' scale, no QK-norm, no gate
(``layers/latent_attention.py``).  Layer ``i``'s FFN is a dense SwiGLU of
``intermediate_size`` where ``i < first_k_dense_replace`` and otherwise the
expert block: sigmoid scores, the ``num_experts_per_tok`` largest ``s + bias``
(``n_group 1``: no groups), weights renormalised and scaled by
``routed_scaling_factor``, SwiGLU experts of ``moe_intermediate_size`` and
``n_shared_experts`` ungated shared ones as one SwiGLU; the bias moves
against the load (``router_bias_update_rate``), no auxiliary loss.

**Multi-token prediction**, depth ``num_nextn_predict_layers`` (1; DeepSeek-V3
eq. 21-25), on the same sequence one position on:

    h'_s = [ N_e(Emb(t_{s+1})) | N_h(h_s) ] W_eh;   h^1 = Layer_L(streams of h')
    P^1_{s+2} = Head(N_1(sum of h^1's streams));  embedding and head SHARED
    loss = CE(main; t_{s+1}) + mtp_loss_weight * CE(P^1; t_{s+2})

``h_s`` is the main stack's stream sum BEFORE its final norm; ``t_{s+1}`` are
the labels the caller hands (``-1`` reads row 0 and its position is masked),
``t_{s+2}`` those shifted by one, the last position masked.  The combine
(``N_e``, ``N_h``, ``W_eh``) stands under the scope ``hetu_mtp``; the MTP
layer, its head pass and its loss under the scopes those parts have
everywhere.

**Assumed** (the catalog has no modelling code for ``xing4_0``): the streams
start as copies of the embedding and end as their sum; columns before rows in
a Sinkhorn round; the hyper-connection's norm has no weight; the order of the
halves of ``W_eh``; ``mtp_loss_weight`` (no key).  **Not modelled**: serving
(latent page, absorbed decode, MTP as a draft head).

``experts_held=(first, count)`` builds one chip's share of an expert-parallel
job, everything else whole; ``remat="layer"`` recomputes whole decoder layers
in the backward pass (their boundary is the four streams).
"""

from __future__ import annotations

import math
from contextlib import nullcontext

from ..graph.node import (VariableOp, remat as remat_scope, scope,
                          scoped_init)
from .. import initializers as init
from ..layers import RMSNorm
from ..layers.base import BaseLayer
from ..layers.hyper_connection import HyperConnection, collapse, expand
from ..layers.latent_attention import LatentAttention
from ..layers.moe import MoELayer
from ..ops import array_reshape_op
from ..ops.base import ScopedOp, simple_op
from ..ops.rotary import yarn_scaling
from .llama import BiasBalanced, LlamaForCausalLM, LlamaMLP, LlamaModel


class Xing4Config:
    """Arguments are the published keys of ``config.json`` under their own
    names; ``seq_len``, ``experts_held``, the bias's update rate, the MTP
    term's weight and what the job recomputes (``remat``) are not in it."""

    def __init__(self, vocab_size=131072, hidden_size=3584,
                 num_hidden_layers=40, num_attention_heads=32,
                 num_key_value_heads=32, first_k_dense_replace=2,
                 intermediate_size=9216, moe_intermediate_size=1024,
                 moe_layer_freq=1, n_routed_experts=64, n_shared_experts=1,
                 num_experts_per_tok=4, n_group=1, topk_group=1,
                 norm_topk_prob=True, routed_scaling_factor=2.0,
                 scoring_func="sigmoid", topk_method="noaux_tc",
                 kv_lora_rank=512, q_lora_rank=768, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128, rope_theta=10000.0,
                 rope_scaling=None, max_position_embeddings=262144,
                 hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
                 mhc_h_res_clamp_min=-30.0, mhc_h_res_clamp_max=30.0,
                 num_nextn_predict_layers=1, rms_norm_eps=1e-6,
                 hidden_act="silu", attention_bias=False,
                 tie_word_embeddings=False, router_bias_update_rate=1e-3,
                 mtp_loss_weight=0.3, seq_len=2048, experts_held=None,
                 remat="layer"):
        assert scoring_func == "sigmoid" and topk_method == "noaux_tc"
        assert hidden_act == "silu" and not attention_bias
        assert moe_layer_freq == 1 and not tie_word_embeddings
        assert num_key_value_heads == num_attention_heads
        assert num_nextn_predict_layers in (0, 1), num_nextn_predict_layers
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_hidden_layers
        self.num_heads = num_attention_heads
        self.first_k_dense_replace = first_k_dense_replace
        self.dense_intermediate_size = intermediate_size
        self.intermediate_size = moe_intermediate_size
        self.num_experts = n_routed_experts
        self.shared_width = n_shared_experts * moe_intermediate_size
        self.moe_k = num_experts_per_tok
        self.router_groups = (n_group, topk_group)
        self.moe_renorm_topk = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.router_bias_update_rate = router_bias_update_rate
        self.kv_lora_rank, self.q_lora_rank = kv_lora_rank, q_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.rope_theta = float(rope_theta)
        self.rope_scaling, self.softmax_scale_mult = self._yarn(rope_scaling)
        self.hc_mult, self.hc_iters, self.hc_eps = (hc_mult,
                                                    hc_sinkhorn_iters, hc_eps)
        self.hc_clamp = (mhc_h_res_clamp_min, mhc_h_res_clamp_max)
        self.mtp_layers = num_nextn_predict_layers
        self.mtp_loss_weight = mtp_loss_weight
        self.rms_eps = rms_norm_eps
        self.tie_embeddings = False
        self.seq_len = seq_len
        assert seq_len <= max_position_embeddings, seq_len
        self.experts_held = experts_held
        assert remat in (None, "layer"), remat
        self.remat = remat

    @staticmethod
    def _yarn(p):
        """``(tables' scaling, the scores' multiplier)`` of a ``rope_scaling``
        group as DeepSeek-V3's modelling code reads it: ``cos`` and ``sin``
        times ``m(mscale) / m(mscale_all_dim)``, the softmax scale times
        ``m(mscale_all_dim)^2``, ``m(a) = 0.1 a ln(factor) + 1``."""
        if not p:
            return None, None
        assert p["type"] == "yarn", p

        def m(a):
            return 0.1 * a * math.log(p["factor"]) + 1.0 if a else 1.0
        all_dim = p.get("mscale_all_dim", 0)
        return (yarn_scaling(p["factor"],
                             p["original_max_position_embeddings"],
                             p.get("beta_fast", 32), p.get("beta_slow", 1),
                             m(p.get("mscale", 1)) / m(all_dim)),
                m(all_dim) ** 2 if all_dim else None)


#: published shapes
XING4_CONFIGS = {
    "xing4.0-29b-a4b": dict(rope_scaling={
        "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}),
}


class Xing4DecoderLayer(BaseLayer):
    def __init__(self, config, index, name):
        c = config

        def norm(n):
            return RMSNorm(c.hidden_size, eps=c.rms_eps, name=f"{name}_{n}")

        def hc(n):
            return HyperConnection(c.hidden_size, c.hc_mult, c.hc_iters,
                                   c.hc_eps, c.hc_clamp, name=f"{name}_{n}")
        self.mixer = LatentAttention(
            c.hidden_size, c.num_heads, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim, rope_theta=c.rope_theta,
            qk_norm=False, head_gate=False, eps=c.rms_eps,
            q_lora_rank=c.q_lora_rank, rope_scaling=c.rope_scaling,
            softmax_scale_mult=c.softmax_scale_mult, name=f"{name}_mla")
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
        self.attn_hc, self.mlp_hc = hc("attn_hc"), hc("mlp_hc")
        self._layer_scope = remat_scope if c.remat == "layer" else nullcontext

    def _mix(self, h):
        #: the mixer's output node of the last call (a benchmark fetches it)
        self.mixer_out = self.mixer(h)
        return self.mixer_out

    def __call__(self, x, seq_len=None):
        with self._layer_scope():       # the whole layer one recomputed group
            x = self.attn_hc.sublayer(x, self.input_norm, self._mix)
            return self.mlp_hc.sublayer(x, self.post_norm, self.mlp)


class Xing4Model(LlamaModel):
    def _layer(self, i, name):
        return Xing4DecoderLayer(self.config, i, name)

    def walk(self, x, layers):
        """``[B, S, C]`` through ``layers`` on the streams: the streams' sum
        ``[B, S, C]``, before any norm."""
        n = self.config.hc_mult
        x = expand(x, n)
        for layer in layers:
            x = layer(x, seq_len=self.config.seq_len)
        return collapse(x, n)

    def __call__(self, input_ids):
        assert not self.pipeline_stages, "a staged Xing4 is not built"
        #: the stream sum before the final norm (the MTP depth reads it)
        self.hidden = self.walk(self._embed(input_ids), self.layers)
        with scope("hetu_head"):
            return self.norm(self.hidden)


def _combine(e, h, w):
    """``[N_e(Emb) | N_h(h)] W_eh``."""
    import jax.numpy as jnp
    return jnp.concatenate([e, h], -1) @ w


def _labelled(labels):
    """The ids the depth embeds: the labels, a masked position's row 0."""
    import jax.numpy as jnp
    return jnp.maximum(labels, 0)


def _shifted(labels):
    """The depth's labels: one position on, the last position masked."""
    import jax.numpy as jnp
    return jnp.concatenate(
        [labels[:, 1:], jnp.full_like(labels[:, :1], -1)], 1)


_next_ids = simple_op(_labelled, "mtp_ids")
_shift_labels = simple_op(_shifted, "mtp_labels")


class Xing4ForCausalLM(BiasBalanced, LlamaForCausalLM):
    """``loss_terms`` returns ``(loss, {"ce", "mtp"})``; ``moe_layers`` (and
    so ``router_biases``, ``moe_loads``) are the main stack's expert layers
    and then the MTP depth's."""
    model_cls = Xing4Model

    @scoped_init
    def __init__(self, config, name="xing4", pipeline_stages=None):
        super().__init__(config, name=name, pipeline_stages=pipeline_stages)
        c = config
        self.mtp_layer = None
        if c.mtp_layers:
            norm = lambda n: RMSNorm(c.hidden_size, eps=c.rms_eps,
                                     name=f"{name}_mtp_{n}")
            self.mtp_enorm, self.mtp_hnorm = norm("enorm"), norm("hnorm")
            self.mtp_norm = norm("norm")
            self.mtp_proj = VariableOp(
                f"{name}_mtp_eh_weight", (2 * c.hidden_size, c.hidden_size),
                init.xavier_normal())
            # the published index of the depth's layer: behind the stack
            self.mtp_layer = Xing4DecoderLayer(
                c, max(c.num_layers, c.first_k_dense_replace),
                f"{name}_mtp_layer")

    def decoder_layers(self):
        return self.model.layers + ([self.mtp_layer] if self.mtp_layer
                                    else [])

    def moe_layers(self):
        # an override only to walk the MTP depth's layer behind the stack's
        return [layer.mlp for layer in self.decoder_layers()
                if isinstance(layer.mlp, MoELayer)]

    def hc_maps(self):
        """One ``[B, S, n, n]`` node a hyper-connected sublayer, ``Hres`` as
        the last walk computed it (a comparison's; never a train step's)."""
        return [hc.hres for layer in self.decoder_layers()
                for hc in (layer.attn_hc, layer.mlp_hc)]

    def mtp_logits(self, next_ids):
        """The depth's logits ``[B S, V]`` from the hidden states of the last
        call of the model and the ids one position on."""
        c = self.config
        emb = self.model._embed(next_ids)
        with scope("hetu_mtp"):
            h = ScopedOp(_combine, "hetu_mtp", self.mtp_enorm(emb),
                         self.mtp_hnorm(self.model.hidden), self.mtp_proj)
        h = self.model.walk(h, [self.mtp_layer])
        with scope("hetu_head"):
            h = array_reshape_op(self.mtp_norm(h),
                                 output_shape=(-1, c.hidden_size))
            return self.lm_head(h)

    def loss_terms(self, input_ids, labels, logits=None):
        """``(loss, {"ce": ..., "mtp": ...})``: the next token's mean
        cross-entropy and, weighted by ``mtp_loss_weight``, that of the token
        after it from the MTP depth; no balance term, the bias balances."""
        ce, terms = super().loss_terms(input_ids, labels, logits)
        if self.mtp_layer is None:
            return ce, terms
        #: the depth's logits of the last call (a benchmark fetches them)
        self.mtp_out = self.mtp_logits(_next_ids(labels))
        mtp = self.cross_entropy(self.mtp_out, _shift_labels(labels))
        with scope("hetu_loss"):
            return ce + mtp * self.config.mtp_loss_weight, {"ce": ce,
                                                            "mtp": mtp}

    @property
    def attention_layers(self):
        return len(self.decoder_layers())
