"""Llama / Baichuan decoder LMs (reference: tools/Hetu-Galvatron/galvatron/
models/llama/LlamaModel_sequential.py, models/baichuan/ — the reference's
modern-LLM tier under hybrid parallelism).

TPU-native rebuild: RMSNorm pre-norm blocks, SwiGLU FFN, rotary position
embeddings (or ALiBi for the Baichuan-13B shape), optional grouped-query
attention.  No learned position table — positions live in the rotation, so
the model serves any sequence length the attention envelope admits.
Parallelism comes from strategy annotations (parallel/strategies.py
MegatronLM) or a searched Galvatron config; ``pipeline_stages=k`` stages
construction for the graph pipeline executor exactly like GPTModel.
"""

from __future__ import annotations

from contextlib import nullcontext
from functools import reduce
from operator import add

import numpy as np

from ..graph.node import VariableOp, remat, scope, stage, scoped_init
from .. import initializers as init
from ..layers import Embedding, Linear, RMSNorm
from ..layers.base import BaseLayer, fresh_name
from ..layers.attention import MultiHeadAttention
from ..layers.moe import MoELayer
from ..ops.base import ScopedOp
from ..ops.rotary import RopeTables
from ..ops import (array_reshape_op, matmul_op, silu_op,
                   softmax_cross_entropy_sparse_op)
from .bert import MaskedMeanOp


class LlamaConfig:
    def __init__(self, vocab_size=32000, hidden_size=4096, num_layers=32,
                 num_heads=32, num_kv_heads=None, intermediate_size=11008,
                 seq_len=2048, rope_theta=10000.0, rms_eps=1e-5,
                 position_embedding="rope", tie_embeddings=False,
                 num_experts=None, moe_k=2, moe_capacity_factor=2.0,
                 moe_aux_coeff=0.01, ep_axis=None, qk_norm=False,
                 moe_renorm_topk=True, moe_z_coeff=0.0):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        self.intermediate_size = intermediate_size
        self.seq_len = seq_len
        self.rope_theta = rope_theta
        self.rms_eps = rms_eps
        assert position_embedding in ("rope", "alibi")
        assert hidden_size % num_heads == 0, (hidden_size, num_heads)
        if position_embedding == "rope":
            # rotate_half pairs dimensions: an odd head_dim silently
            # broadcasts the tables to the wrong width downstream
            assert (hidden_size // num_heads) % 2 == 0, (
                f"RoPE needs an even head_dim; got "
                f"{hidden_size // num_heads} (hidden {hidden_size}, "
                f"heads {num_heads})")
        self.position_embedding = position_embedding
        self.tie_embeddings = tie_embeddings
        # num_experts turns each block's FFN into a top-k sparse-MoE of
        # SwiGLU experts (Mixtral-style; the reference's MoE tier is a
        # plain transformer, examples/moe — this composes it with Llama)
        self.num_experts = num_experts
        self.moe_k = moe_k
        self.moe_capacity_factor = moe_capacity_factor
        self.moe_aux_coeff = moe_aux_coeff
        self.ep_axis = ep_axis
        # the three things OLMoE's block has beyond Mixtral's: an RMSNorm
        # on the projected queries and keys, top-k softmax weights used as
        # they are (norm_topk_prob false), and a router z-loss.
        # moe_capacity_factor=None is dropless routing (layers/moe.py):
        # no pair dropped, the balance loss over top-k counts
        self.qk_norm = qk_norm
        self.moe_renorm_topk = moe_renorm_topk
        self.moe_z_coeff = moe_z_coeff
        assert not moe_z_coeff or moe_capacity_factor is None, (
            "the router z-loss is read from the dropless routing")


# published shapes (match the reference's meta_configs/hf_configs)
LLAMA_CONFIGS = {
    "llama-7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                     intermediate_size=11008),
    "llama-13b": dict(hidden_size=5120, num_layers=40, num_heads=40,
                      intermediate_size=13824),
    "llama-30b": dict(hidden_size=6656, num_layers=60, num_heads=52,
                      intermediate_size=17920),
    # llama3-style GQA shape
    "llama3-8b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                      num_kv_heads=8, intermediate_size=14336,
                      vocab_size=128256, rope_theta=500000.0),
    # GQA shapes of the Mistral family (sliding-window attention not
    # modeled; full causal within seq_len)
    "mistral-7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                       num_kv_heads=8, intermediate_size=14336,
                       vocab_size=32000),
    # moe_capacity_factor = E/k: the no-drop point Mixtral parity needs,
    # at E/k times the expert work (the buffer is E x C rows for T k
    # pairs); moe_capacity_factor=None, the dropless path of
    # layers/moe.py, runs the same routing on the T k pairs alone
    "mixtral-8x7b": dict(hidden_size=4096, num_layers=32, num_heads=32,
                         num_kv_heads=8, intermediate_size=14336,
                         vocab_size=32000, num_experts=8, moe_k=2,
                         moe_capacity_factor=4.0),
    # allenai/OLMoE-1B-7B-0125 config.json: 64 experts of width 1,024,
    # 8 a token with the softmax weights not renormalised, QK-norm,
    # trained dropless with 0.01 of the balance loss and 0.001 of the
    # router z-loss a layer (arXiv:2409.02060)
    "olmoe-1b-7b": dict(vocab_size=50304, hidden_size=2048, num_layers=16,
                        num_heads=16, num_kv_heads=16,
                        intermediate_size=1024, rope_theta=10000.0,
                        rms_eps=1e-5, qk_norm=True, num_experts=64,
                        moe_k=8, moe_renorm_topk=False,
                        moe_capacity_factor=None, moe_aux_coeff=0.01,
                        moe_z_coeff=0.001),
    # reference models/baichuan: 7B is rope, 13B is alibi
    "baichuan-7b": dict(vocab_size=64000, hidden_size=4096, num_layers=32,
                        num_heads=32, intermediate_size=11008),
    "baichuan-13b": dict(vocab_size=64000, hidden_size=5120, num_layers=40,
                         num_heads=40, intermediate_size=13696,
                         position_embedding="alibi"),
}


class LlamaMLP(BaseLayer):
    """SwiGLU: down(silu(gate(x)) * up(x)) (HF LlamaMLP semantics).

    Names follow the TP contract — gate/up are column-parallel, the down
    projection is `_out` (row-parallel) so MegatronLM.annotate shards it
    without model-specific rules.
    """

    def __init__(self, hidden_size, intermediate_size, name):
        self.gate = Linear(hidden_size, intermediate_size, bias=False,
                           name=f"{name}_gate")
        self.up = Linear(hidden_size, intermediate_size, bias=False,
                         name=f"{name}_up")
        self.down = Linear(intermediate_size, hidden_size, bias=False,
                           name=f"{name}_out")

    def __call__(self, x):
        with scope("hetu_mlp"):
            return self.down(silu_op(self.gate(x)) * self.up(x))


def _merge(x, y, s_r, b_r, s_f, b_f):
    """``s_r (x + b_r) + s_f (y + b_f)`` in f32, one rounding to ``x``'s
    type."""
    import jax.numpy as jnp
    out = x.dtype
    x, y, s_r, b_r, s_f, b_f = (a.astype(jnp.float32)
                                for a in (x, y, s_r, b_r, s_f, b_f))
    return (s_r * (x + b_r) + s_f * (y + b_f)).astype(out)


class ResidualMerge(BaseLayer):
    """The four ``hidden``-vectors of a residual-scaled sublayer (ZAYA1's
    ``scale_residual_merge``): ``x' = s_r (x + b_r) + s_f (F(N(x)) + b_f)``,
    the scales ones and the biases zeros at the start (the plain residual),
    bias before scale (assumed).  One node under ``hetu_norm``, f32 inside,
    the stream's type out."""

    def __init__(self, hidden_size, name=None):
        name = fresh_name(name or "merge")
        self.s_r, self.b_r, self.s_f, self.b_f = (
            VariableOp(f"{name}_{n}", (hidden_size,), how)
            for n, how in (("res_scale", init.ones()),
                           ("res_bias", init.zeros()),
                           ("out_scale", init.ones()),
                           ("out_bias", init.zeros())))

    def __call__(self, x, y):
        return ScopedOp(_merge, "hetu_norm", x, y, self.s_r, self.b_r,
                        self.s_f, self.b_f)


def residual_sublayer(x, norm, sublayer, recompute=False, scale=None,
                      seq_len=None, post_norm=None, merge=None):
    """One pre-norm residual sublayer, ``x + sublayer(norm(x))`` (``* scale``
    where a family multiplies its residual branches): the norm and the sum
    under the block `hetu_norm`, the sublayer under the names it gives
    itself; a ``MultiHeadAttention`` is called ``(h, h, h, seq_len=)``,
    anything else, a wrapper around one too, ``(h)``.  ``recompute`` puts
    the norm and the sublayer inside one recomputed group: what the backward
    pass keeps of it is what enters it, the residual stream alone.
    ``post_norm`` is a second norm BEHIND the sublayer (sandwich),
    ``x + post_norm(sublayer(norm(x)))``: it and the sum then stand inside
    the recomputed group too.  ``merge`` (a ``ResidualMerge``) stands where
    the sum stands: ``s_r (x + b_r) + s_f (sublayer(norm(x)) + b_f)``."""
    with (remat() if recompute else nullcontext()):
        with scope("hetu_norm"):
            h = norm(x)
        y = (sublayer(h, h, h, seq_len=seq_len)
             if isinstance(sublayer, MultiHeadAttention) else sublayer(h))
        if post_norm is not None:
            with scope("hetu_norm"):
                y = post_norm(y)
                return x + (y if scale is None else y * scale)
    if merge is not None:
        assert scale is None and post_norm is None
        return merge(x, y)
    with scope("hetu_norm"):
        return x + (y if scale is None else y * scale)


class LlamaDecoderLayer(BaseLayer):
    def __init__(self, config, name, rope_tables=None):
        c = config
        self.attn = MultiHeadAttention(
            c.hidden_size, c.num_heads, sequence_length=c.seq_len,
            causal_mask=True, num_kv_heads=c.num_kv_heads,
            rope_theta=(c.rope_theta
                        if c.position_embedding == "rope" else None),
            alibi=c.position_embedding == "alibi", bias=False,
            qk_norm=c.qk_norm, qk_norm_eps=c.rms_eps,
            rope_tables=rope_tables, name=f"{name}_attn")
        if c.num_experts:
            self.mlp = MoELayer(c.hidden_size, c.intermediate_size,
                                num_experts=c.num_experts, k=c.moe_k,
                                capacity_factor=c.moe_capacity_factor,
                                expert_act="swiglu", ep_axis=c.ep_axis,
                                renorm_topk=c.moe_renorm_topk,
                                track_load=True, name=f"{name}_moe")
        else:
            self.mlp = LlamaMLP(c.hidden_size, c.intermediate_size,
                                name=f"{name}_mlp")
        self.input_norm = RMSNorm(c.hidden_size, eps=c.rms_eps,
                                  name=f"{name}_input_norm")
        self.post_norm = RMSNorm(c.hidden_size, eps=c.rms_eps,
                                 name=f"{name}_post_norm")

    def __call__(self, x, seq_len=None):
        x = residual_sublayer(x, self.input_norm, self.attn, seq_len=seq_len)
        return residual_sublayer(x, self.post_norm, self.mlp)


class LlamaModel:
    @scoped_init
    def __init__(self, config, name="llama", pipeline_stages=None):
        c = config
        self.config = c
        self.pipeline_stages = pipeline_stages
        self.embed = Embedding(c.vocab_size, c.hidden_size,
                               initializer=init.normal(0.0, 0.02),
                               name=f"{name}_embed")
        #: the rotary tables' nodes, one for all layers
        self.rope_tables = RopeTables()
        self.layers = [self._layer(i, f"{name}_layer{i}")
                       for i in range(c.num_layers)]
        self.norm = self._norm(f"{name}_norm")

    def _layer(self, i, name):
        """Decoder layer ``i``; a family whose layers differ overrides it."""
        return LlamaDecoderLayer(self.config, name=name,
                                 rope_tables=self.rope_tables)

    def _norm(self, name):
        return RMSNorm(self.config.hidden_size, eps=self.config.rms_eps,
                       name=name)

    def _scope(self, layer_idx=None):
        S = self.pipeline_stages
        if not S:
            return nullcontext()
        if layer_idx is None:
            return stage(0)
        bounds = np.array_split(np.arange(len(self.layers)), S)
        for s, chunk in enumerate(bounds):
            if layer_idx in chunk:
                return stage(s)
        return stage(S - 1)

    def _embed(self, input_ids):
        """The hidden states the first layer reads; a family that scales its
        embeddings overrides it."""
        return self.embed(input_ids)

    def __call__(self, input_ids):
        with self._scope():
            x = self._embed(input_ids)
        for i, layer in enumerate(self.layers):
            with self._scope(i):
                x = layer(x, seq_len=self.config.seq_len)
        with (stage(self.pipeline_stages - 1) if self.pipeline_stages
              else nullcontext()), scope("hetu_head"):
            return self.norm(x)


class LlamaForCausalLM:
    #: the decoder under the head; a family with its own layers sets its own
    model_cls = LlamaModel

    @scoped_init
    def __init__(self, config, name="llama", pipeline_stages=None):
        self.model = self.model_cls(config, name=name,
                                    pipeline_stages=pipeline_stages)
        self.config = config
        with (stage(pipeline_stages - 1) if pipeline_stages
              else nullcontext()):
            self.lm_head = (None if config.tie_embeddings else
                            Linear(config.hidden_size, config.vocab_size,
                                   bias=False,
                                   initializer=init.normal(0.0, 0.02),
                                   name=f"{name}_lm_head"))

    def __call__(self, input_ids):
        h = self.model(input_ids)
        with scope("hetu_head"):
            h = array_reshape_op(h,
                                 output_shape=(-1, self.config.hidden_size))
            if self.lm_head is None:
                return matmul_op(h, self.model.embed.weight, trans_B=True)
            return self.lm_head(h)

    def loss(self, input_ids, labels):
        """labels: [B, S] next-token ids with -1 at ignored positions
        (caller shifts, matching GPTLMHeadModel's convention)."""
        return self.loss_terms(input_ids, labels)[0]

    def loss_terms(self, input_ids, labels, logits=None):
        """``(loss, {"ce": ..., "lbl": ..., "z": ...})``: the training loss
        and the nodes it is the weighted sum of: the mean cross-entropy
        over labelled positions and, for an MoE model, the balance loss and
        the router z-loss, each summed over layers (the z term only where
        ``moe_z_coeff`` is set).  ``logits``: this model's output on
        ``input_ids`` where the caller holds it already (to fetch it beside
        the loss without a second forward graph)."""
        c = self.config
        if logits is None:
            logits = self(input_ids)
        terms = {"ce": self.cross_entropy(logits, labels)}
        loss = terms["ce"]
        mlps = self.moe_layers() if c.num_experts else []
        with scope("hetu_loss"):
            if mlps:
                terms["lbl"] = reduce(add, [m.aux_loss() for m in mlps])
                loss = loss + c.moe_aux_coeff * terms["lbl"]
                if c.moe_z_coeff:
                    terms["z"] = reduce(add, [m.z_loss() for m in mlps])
                    loss = loss + c.moe_z_coeff * terms["z"]
            return loss, terms

    def cross_entropy(self, logits, labels):
        """The mean cross-entropy of ``logits [B S, V]`` over the labelled
        positions of ``labels [B, S]`` (-1: ignored)."""
        with scope("hetu_loss"):
            flat = array_reshape_op(labels, output_shape=(-1,))
            return MaskedMeanOp(softmax_cross_entropy_sparse_op(
                logits, flat, ignored_index=-1), flat)

    def moe_layers(self):
        """The model's sparse expert layers, in order: the ``mlp`` of every
        layer that holds expert weights (not a dense layer's, and not the
        None of a block that has no FFN)."""
        return [layer.mlp for layer in self.model.layers
                if isinstance(layer.mlp, MoELayer)]

    def moe_loads(self):
        """One ``[2, E]`` node a layer of (pairs routed, pairs computed) by
        expert, to fetch beside the loss (layers/moe.py ``MoELoadOp``)."""
        return [m.load() for m in self.moe_layers()]


class BiasBalanced:
    """A model whose routers are balanced by a selection bias that each step
    updates and not by a loss; before ``LlamaForCausalLM`` in a family's
    bases."""

    def router_biases(self):
        """One ``[num_experts]`` node an expert layer: the router's selection
        bias as this step left it, to fetch beside the loads
        (``layers/moe.py record_moe_load(bias=)``)."""
        return [m.router_bias() for m in self.moe_layers()]

    def loss_terms(self, input_ids, labels, logits=None):
        """``(loss, {"ce": ...})``: no balance term, the bias balances."""
        if logits is None:
            logits = self(input_ids)
        loss = self.cross_entropy(logits, labels)
        return loss, {"ce": loss}


def BaichuanForCausalLM(config, name="baichuan", pipeline_stages=None):
    """The Baichuan family is the Llama architecture with its own vocab
    and (for 13B) ALiBi positions — config-level, not code-level, variants
    (reference models/baichuan/BaiChuanModel_sequential.py)."""
    return LlamaForCausalLM(config, name=name,
                            pipeline_stages=pipeline_stages)
