"""Granite 4.0-H decoder LMs (HF ``model_type`` ``granitemoehybrid`` with
``num_local_experts`` 0: ``ibm-granite/granite-4.0-h-micro``): a layer is a
mixer THEN a gated MLP, each behind a norm and a SCALED residual sum, the
mixer chosen by ``layer_types``.

    h = embedding_multiplier * E[ids]
    h = h + residual_multiplier * mixer_i(N1(h))     mamba | attention
    h = h + residual_multiplier * W_out(silu(g) * u),  [g | u] = N2(h) W_in
    logits = N(h) E^T / logits_scaling               (the head is E: tied)

``mamba`` is ``layers/mamba2.py`` with ALL heads in ``mamba_n_groups`` = 1
group: 64 heads of 64 read one ``B`` and one ``C``, and the gated norm runs
over all 4,096 channels.  ``attention`` is grouped-query (32 query heads on 8
KV heads of 64), causal, without bias and without any position encoding
(``position_embedding_type`` ``nope``), its scores scaled by
``attention_multiplier`` (1/64, not ``64 ** -0.5``).  The MLP is
``LlamaMLP``: HF's one fused ``input_linear`` of ``2 x
shared_intermediate_size`` is its gate and up matrices side by side.  The
loss is the mean next-token cross-entropy; there is no auxiliary term.  The
vocabulary may be a slice: ids, logits and the loss are over the rows of
``E`` that are there.

The scan runs at chunks of ``ops.ssd.CHUNK`` = 128 positions whatever
``mamba_chunk_size`` says (256 published): the result does not depend on the
chunk, and the kernels are written for 128.  ``remat`` names what the
backward pass recomputes: ``"mamba"`` (a Mamba layer's first norm and its
mixer, as ``NemotronHBlock`` does) or None.  Serving (a state and a
convolution's last inputs in the cache) is not here.
"""

from __future__ import annotations

from ..graph.node import scope
from ..layers import RMSNorm
from ..layers.attention import MultiHeadAttention
from ..layers.base import BaseLayer
from ..layers.mamba2 import Mamba2
from ..ops import ssd
from .llama import (LlamaForCausalLM, LlamaMLP, LlamaModel,
                    residual_sublayer)

#: the published ``layer_types``: attention at layers 5, 15, 25 and 35
LAYER_TYPES = tuple("attention" if i % 10 == 5 else "mamba"
                    for i in range(40))


class GraniteHybridConfig:
    """Arguments are the published keys of ``config.json`` under their own
    names; ``seq_len`` and what the job recomputes (``remat``) are not in
    it."""

    def __init__(self, vocab_size=100352, hidden_size=2048,
                 num_hidden_layers=40, layer_types=LAYER_TYPES,
                 num_attention_heads=32, num_key_value_heads=8,
                 shared_intermediate_size=8192, mamba_n_heads=64,
                 mamba_d_head=64, mamba_d_state=128, mamba_n_groups=1,
                 mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=256,
                 attention_multiplier=0.015625, embedding_multiplier=12,
                 residual_multiplier=0.22, logits_scaling=8,
                 rms_norm_eps=1e-5, tie_word_embeddings=True, seq_len=2048,
                 remat="mamba"):
        assert len(layer_types) == num_hidden_layers, (
            layer_types, num_hidden_layers)
        assert set(layer_types) <= {"mamba", "attention"}, layer_types
        assert mamba_n_heads * mamba_d_head == mamba_expand * hidden_size, (
            "the mixer's inner width is mamba_expand x hidden_size")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_hidden_layers
        self.layer_types = tuple(layer_types)
        self.num_heads = num_attention_heads
        self.num_kv_heads = num_key_value_heads
        self.intermediate_size = shared_intermediate_size
        self.mamba_num_heads = mamba_n_heads
        self.mamba_head_dim = mamba_d_head
        self.ssm_state_size = mamba_d_state
        self.n_groups = mamba_n_groups
        self.conv_kernel = mamba_d_conv
        self.mamba_chunk_size = mamba_chunk_size      # published; not read
        self.attention_multiplier = attention_multiplier
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.logits_scaling = logits_scaling
        self.rms_eps = rms_norm_eps
        self.tie_embeddings = tie_word_embeddings
        self.num_experts = None               # dense: num_local_experts 0
        self.seq_len = seq_len
        assert remat in (None, "mamba"), remat
        self.remat = remat


#: published shapes
GRANITE_HYBRID_CONFIGS = {
    "granite-4.0-h-micro": dict(),  # the defaults above are its keys
}


class GraniteHybridDecoderLayer(BaseLayer):
    def __init__(self, config, kind, name):
        c = config
        self.kind = kind
        self.scale = float(c.residual_multiplier)
        if kind == "mamba":
            self.mixer = Mamba2(
                c.hidden_size, c.mamba_num_heads, c.mamba_head_dim,
                c.n_groups, c.ssm_state_size, conv_kernel=c.conv_kernel,
                chunk=ssd.CHUNK, eps=c.rms_eps, name=f"{name}_mamba")
        else:
            self.mixer = MultiHeadAttention(
                c.hidden_size, c.num_heads, sequence_length=c.seq_len,
                causal_mask=True, num_kv_heads=c.num_kv_heads,
                rope_theta=None, bias=False,
                scale=float(c.attention_multiplier), name=f"{name}_attn")
        self.mlp = LlamaMLP(c.hidden_size, c.intermediate_size,
                            name=f"{name}_mlp")
        self.input_norm = RMSNorm(c.hidden_size, eps=c.rms_eps,
                                  name=f"{name}_input_norm")
        self.post_norm = RMSNorm(c.hidden_size, eps=c.rms_eps,
                                 name=f"{name}_post_norm")
        self.recompute = c.remat == "mamba" and kind == "mamba"

    def __call__(self, x, seq_len=None):
        x = residual_sublayer(x, self.input_norm, self.mixer, self.recompute,
                              self.scale, seq_len=seq_len)
        return residual_sublayer(x, self.post_norm, self.mlp,
                                 scale=self.scale)


class GraniteHybridModel(LlamaModel):
    def _layer(self, i, name):
        return GraniteHybridDecoderLayer(self.config,
                                         self.config.layer_types[i], name)

    def _embed(self, input_ids):
        x = self.embed(input_ids)
        with scope("hetu_embed"):
            return x * float(self.config.embedding_multiplier)


class GraniteHybridForCausalLM(LlamaForCausalLM):
    model_cls = GraniteHybridModel

    def __init__(self, config, name="granite", pipeline_stages=None):
        super().__init__(config, name=name, pipeline_stages=pipeline_stages)

    def __call__(self, input_ids):
        logits = super().__call__(input_ids)
        with scope("hetu_head"):
            return logits * (1.0 / float(self.config.logits_scaling))

    @property
    def attention_layers(self):
        return self.config.layer_types.count("attention")
