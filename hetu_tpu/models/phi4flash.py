"""SambaY decoder-hybrid-decoder LMs (``microsoft/Phi-4-mini-flash-reasoning``,
``model_type`` "phi4flash"; arXiv:2507.06607): a self-decoder of Mamba-1 mixers
and differential attention under a window, ONE layer of differential attention
over all earlier keys, and a cross-decoder whose layers read what two layers of
the self-decoder computed: Gated Memory Units on the last Mamba layer's scan
output and differential cross-attention on the full layer's keys and values.

Every layer ``l`` (``LN``: LayerNorm with scale and bias; no dropout; no
position encoding anywhere)::

    x <- x + Mixer_l(LN1(x));   x <- x + W2 (up * silu(gate)),
                                [gate | up] = LN2(x) W1 (held as two matrices)

then ``LN_f`` and the logits on the tied embedding.  The mixer follows from the
layer's PUBLISHED index, with ``h = published_layers / 2`` (16 of 32) and
``mb_per_layer`` 2 (``Phi4FlashConfig.kind``): even ``l <= h`` ``mamba``
(``layers/mamba1.py Mamba1``; layer ``h`` hands out its scan output ``M``
before the gate); odd ``l < h`` ``window`` (``layers/attention.py
DifferentialAttention`` over the last ``sliding_window`` keys); ``l = h + 1``
``full`` (the same over all earlier keys; hands out its projected K and V);
even ``l > h`` ``gmu`` (``GatedMemoryUnit`` on ``M``); odd ``l > h + 1``
``cross`` (differential attention with a query projection alone, on layer ``h
+ 1``'s K and V).  ``lambda_init`` of a differential layer is ``0.8 - 0.6
exp(-0.3 l)`` at the published ``l``.

``first_layer_index`` and ``num_hidden_layers`` build a run of consecutive
layers under their published indices (a pipeline stage: ``first_layer_index=14,
num_hidden_layers=6`` is layers 14-19, every kind once or twice); a run that
holds a reader holds what it reads.  ``remat="layer"`` makes each decoder
layer one recomputed group: ``M``, K and V leave their group as kept values,
and every group that reads one sends its cotangent back to be summed before
the handing layer's backward pass runs (``graph/trace.py``).
``hetu_shared_value_readers{value="scan"|"kv"}`` counts the layers that read
each (its own layer among them: 8 and 8 in the published model).

**Not modelled**: generation (a decode step and a cache for the Mamba layers'
state, ONE key-value cache read by every cross layer, a prefill that stops at
the full layer), packed documents, the tokenizer and real weights.
"""

from __future__ import annotations

from contextlib import nullcontext

from .. import telemetry
from ..graph.node import remat as remat_scope, scope, stage
from ..layers import LayerNorm
from ..layers.attention import DifferentialAttention
from ..layers.base import BaseLayer
from ..layers.mamba1 import GatedMemoryUnit, Mamba1
from .llama import (LlamaForCausalLM, LlamaMLP, LlamaModel,
                    residual_sublayer)


def count_readers(value, readers):
    """``hetu_shared_value_readers{value}``: the layers of the model built
    last that read one kept value of another layer (``scan``: a Mamba layer's
    output before its gate; ``kv``: an attention layer's keys and values)."""
    telemetry.get_registry().gauge(
        "hetu_shared_value_readers",
        "Layers of the model built last that read a value another layer "
        "handed out (scan: a state-space layer's output before its gate; kv: "
        "an attention layer's projected keys and values), its own layer "
        "among them", labels=("value",)).labels(value=value).set(readers)


class Phi4FlashConfig:
    """Arguments are the published keys of ``config.json`` under their own
    names; the Mamba sizes are the family's configuration defaults
    (``mamba_dt_rank`` None: ``ceil(hidden_size / 16)``); ``seq_len``,
    ``first_layer_index``, ``published_layers`` (the model's own depth where
    ``num_hidden_layers`` is a cut's) and what the job recomputes (``remat``)
    are not in it."""

    def __init__(self, vocab_size=200064, hidden_size=2560,
                 intermediate_size=10240, num_hidden_layers=32,
                 num_attention_heads=40, num_key_value_heads=20,
                 layer_norm_eps=1e-5, max_position_embeddings=262144,
                 mb_per_layer=2, sliding_window=512,
                 tie_word_embeddings=True, hidden_act="silu", embd_pdrop=0,
                 resid_pdrop=0, mlp_bias=False, lm_head_bias=False,
                 model_type="phi4flash", mamba_d_state=16, mamba_d_conv=4,
                 mamba_expand=2, mamba_dt_rank=None, first_layer_index=0,
                 published_layers=None, seq_len=2048, remat=None):
        assert model_type == "phi4flash" and hidden_act == "silu"
        assert not (embd_pdrop or resid_pdrop), "built without dropout"
        assert not (mlp_bias or lm_head_bias), "built without those biases"
        assert mb_per_layer == 2, "a Mamba layer every second layer"
        assert seq_len <= max_position_embeddings, seq_len
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.intermediate_size = intermediate_size
        self.num_layers = num_hidden_layers
        self.num_heads = num_attention_heads
        self.num_kv_heads = num_key_value_heads
        self.head_dim = hidden_size // num_attention_heads
        self.layer_norm_eps = layer_norm_eps
        self.mb_per_layer = mb_per_layer
        self.sliding_window = sliding_window
        self.tie_embeddings = tie_word_embeddings
        self.mamba_d_state, self.mamba_d_conv = mamba_d_state, mamba_d_conv
        self.mamba_expand = mamba_expand
        self.mamba_dt_rank = mamba_dt_rank or -(-hidden_size // 16)
        self.first_layer_index = first_layer_index
        self.published_layers = (published_layers
                                 or first_layer_index + num_hidden_layers)
        self.seq_len = seq_len
        self.num_experts = None         # ``LlamaForCausalLM.loss_terms``
        assert remat in (None, "layer"), remat
        self.remat = remat
        kinds = self.kinds
        for reader, source in (("gmu", self.published_layers // 2),
                               ("cross", self.published_layers // 2 + 1)):
            assert reader not in kinds or source in self.indices, (
                f"a {reader} layer reads layer {source}, which this run of "
                f"layers {self.indices} leaves out")

    @property
    def indices(self):
        """The published indices of the layers built."""
        return list(range(self.first_layer_index,
                          self.first_layer_index + self.num_layers))

    def kind(self, index):
        """The mixer of the layer with the PUBLISHED ``index``."""
        half = self.published_layers // 2
        if index % self.mb_per_layer == 0:
            return "mamba" if index <= half else "gmu"
        if index < half:
            return "window"
        return "full" if index == half + 1 else "cross"

    @property
    def kinds(self):
        return [self.kind(i) for i in self.indices]


#: published shapes
PHI4FLASH_CONFIGS = {
    "phi-4-mini-flash-reasoning": dict(),   # the defaults above are its keys
}


class Phi4FlashDecoderLayer(BaseLayer):
    """Called ``(x, shared)``: ``shared`` is the model's dict of handed-out
    nodes (``memory``, ``keys``, ``values``), filled by the layers that hand
    out and read by the layers behind them."""

    def __init__(self, config, index, name):
        c = config
        self.index, self.kind = index, c.kind(index)
        half = c.published_layers // 2
        if self.kind == "mamba":
            self.mixer = Mamba1(
                c.hidden_size, expand=c.mamba_expand,
                state_size=c.mamba_d_state, dt_rank=c.mamba_dt_rank,
                conv_kernel=c.mamba_d_conv, hand_out_scan=index == half,
                name=f"{name}_mamba")
        elif self.kind == "gmu":
            self.mixer = GatedMemoryUnit(
                c.hidden_size, c.mamba_expand * c.hidden_size,
                name=f"{name}_gmu")
        else:
            self.mixer = DifferentialAttention(
                c.hidden_size, c.num_heads, c.num_kv_heads, index,
                sequence_length=c.seq_len,
                window=c.sliding_window if self.kind == "window" else None,
                cross=self.kind == "cross", eps=c.layer_norm_eps,
                name=f"{name}_attn")
        self.mlp = LlamaMLP(c.hidden_size, c.intermediate_size,
                            name=f"{name}_mlp")
        self.input_norm, self.post_norm = (
            LayerNorm(c.hidden_size, eps=c.layer_norm_eps,
                      name=f"{name}_{n}")
            for n in ("input_norm", "post_norm"))
        self._layer_scope = remat_scope if c.remat == "layer" else nullcontext
        #: the mixer's own output node of the last call (a benchmark fetches
        #: some beside the logits)
        self.mixer_out = None

    def _mix(self, shared):
        def mix(h):
            if self.kind == "gmu":
                out = self.mixer(h, shared["memory"])
            elif self.kind == "cross":
                out = self.mixer(h, shared["keys"], shared["values"])
            else:
                out = self.mixer(h)
            if self.kind == "mamba" and self.mixer.hand_out_scan:
                shared["memory"] = self.mixer.memory
            if self.kind == "full":
                shared["keys"] = self.mixer.keys
                shared["values"] = self.mixer.values
            self.mixer_out = out
            return out
        return mix

    def __call__(self, x, shared):
        with self._layer_scope():       # the whole layer one recomputed group
            x = residual_sublayer(x, self.input_norm, self._mix(shared))
            return residual_sublayer(x, self.post_norm, self.mlp)


class Phi4FlashModel(LlamaModel):
    def _layer(self, i, name):
        index = self.config.first_layer_index + i
        return Phi4FlashDecoderLayer(self.config, index,
                                     f"{name[:-len(str(i))]}{index}")

    def _norm(self, name):
        return LayerNorm(self.config.hidden_size,
                         eps=self.config.layer_norm_eps, name=name)

    def __call__(self, input_ids):
        with self._scope():
            x = self._embed(input_ids)
        #: what the layers hand to the layers behind them, by name
        self.shared = {}
        for i, layer in enumerate(self.layers):
            with self._scope(i):
                x = layer(x, self.shared)
        kinds = self.config.kinds
        count_readers("scan", kinds.count("gmu") + ("memory" in self.shared))
        count_readers("kv", kinds.count("cross") + ("keys" in self.shared))
        with (stage(self.pipeline_stages - 1) if self.pipeline_stages
              else nullcontext()), scope("hetu_head"):
            return self.norm(x)

    def layers_of(self, *kinds):
        """The layers built whose mixer is one of ``kinds``."""
        return [layer for layer in self.layers if layer.kind in kinds]


class Phi4FlashForCausalLM(LlamaForCausalLM):
    model_cls = Phi4FlashModel

    def __init__(self, config, name="phi4flash", pipeline_stages=None):
        super().__init__(config, name=name, pipeline_stages=pipeline_stages)

    def record_lambdas(self, params):
        """``hetu_diff_attn_lambda{layer}``: every differential layer's
        ``lambda`` under ``params``; returns ``{published index: lambda}``."""
        gauge = telemetry.get_registry().gauge(
            "hetu_diff_attn_lambda",
            "lambda of a differential attention layer (exp(lq1 . lk1) - "
            "exp(lq2 . lk2) + lambda_init), by the layer's published index",
            labels=("layer",))
        out = {}
        for layer in self.model.layers_of("window", "full", "cross"):
            out[layer.index] = layer.mixer.lambda_value(params)
            gauge.labels(layer=str(layer.index)).set(out[layer.index])
        return out
