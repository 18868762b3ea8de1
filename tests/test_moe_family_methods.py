"""What an MoE family takes from ``models/llama.py`` and does not write out
again: ``LlamaForCausalLM.moe_layers()`` (the ``mlp`` of every layer that
holds expert weights) and, where the routers are balanced by a bias,
``BiasBalanced``'s ``router_biases()`` and cross-entropy-only
``loss_terms``.  On each family's toy model (its reference test's
configuration) against the rule the family's own method spelled before."""

import importlib

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import models
from hetu_tpu.layers.moe import MoELayer
from hetu_tpu.models.llama import BiasBalanced, LlamaForCausalLM

S = 32

#: the rule each family's ``moe_layers`` stated before this fold
not_dense = lambda m: [l.mlp for l in m.model.layers if not l.dense]
every = lambda m: [l.mlp for l in m.model.layers]
RULES = {
    "ling3": not_dense,
    "laguna": not_dense,
    "mellum": not_dense,
    "zaya1": every,
    "nemotron_h": lambda m: [b.mlp for b in m.model.layers
                             if b.mlp is not None],
    "xing4": lambda m: [l.mlp for l in m.decoder_layers() if not l.dense],
    "qwen3_next": every,
    "llama": every,
}

#: (class, configuration class, what its reference test adds to REF_CONFIG)
FAMILIES = {
    "ling3": ("Ling3ForCausalLM", "Ling3Config",
              dict(num_experts=16, experts_held=(4, 8))),
    "laguna": ("LagunaForCausalLM", "LagunaConfig",
               dict(num_experts=16, experts_held=(4, 4))),
    "mellum": ("MellumForCausalLM", "MellumConfig", {}),
    "zaya1": ("Zaya1ForCausalLM", "Zaya1Config",
              dict(num_experts=8, experts_held=(4, 4))),
    "nemotron_h": ("NemotronHForCausalLM", "NemotronHConfig",
                   dict(n_routed_experts=16, experts_held=(4, 8))),
    "xing4": ("Xing4ForCausalLM", "Xing4Config",
              dict(n_routed_experts=16, num_key_value_heads=2,
                   experts_held=(4, 8))),
    "qwen3_next": ("Qwen3NextForCausalLM", "Qwen3NextConfig",
                   dict(num_experts=16, experts_held=(4, 8))),
}


def toy(family):
    if family == "llama":       # OLMoE's shape: every FFN sparse
        config = models.LlamaConfig(
            vocab_size=64, hidden_size=32, intermediate_size=16,
            num_layers=2, num_heads=2, seq_len=S, num_experts=4, moe_k=2)
        return LlamaForCausalLM(config, name="fam_llama")
    cls, config_cls, extra = FAMILIES[family]
    ref = importlib.import_module(f"test_{family}_reference").REF_CONFIG
    config = getattr(models, config_cls)(seq_len=S, **dict(ref, **extra))
    return getattr(models, cls)(config, name=f"fam_{family}")


@pytest.mark.parametrize("family", sorted(RULES))
def test_moe_layers_are_the_layers_that_hold_expert_weights(family):
    """``moe_layers()`` is the list the family's own method gave, exactly
    the ``mlp`` that are ``MoELayer``s, in order; ``moe_loads()`` is one node
    each; a bias-balanced family's ``router_biases()`` is one node a layer
    on that layer's selection bias, and its loss is the cross-entropy
    alone."""
    model = toy(family)
    got = model.moe_layers()
    want = RULES[family](model)
    assert len(got) == len(want) > 0
    assert all(a is b for a, b in zip(got, want))
    walked = (model.decoder_layers() if family == "xing4"
              else model.model.layers)
    held = [l.mlp for l in walked if isinstance(l.mlp, MoELayer)]
    assert all(a is b for a, b in zip(got, held)) and len(got) == len(held)
    if family in ("ling3", "laguna", "nemotron_h", "xing4"):
        assert len(got) < len(walked)       # a dense or FFN-less layer
    assert len(model.moe_loads()) == len(got)
    balanced = isinstance(model, BiasBalanced)
    assert balanced == (family not in ("llama", "qwen3_next"))
    if not balanced:
        assert not hasattr(model, "router_biases")
        return
    if family == "mellum":      # Laguna's decoder under a softmax router:
        with pytest.raises(AssertionError):     # no bias to fetch, as before
            model.router_biases()
    else:
        biases = model.router_biases()
        assert len(biases) == len(got)
        for node, layer in zip(biases, got):
            assert node.inputs[0] is layer.gate.bias
            assert len(node.inputs[0].shape) == 1
    ids = ht.placeholder_op(f"fam_{family}_ids", (2, S), dtype=np.int32)
    labels = ht.placeholder_op(f"fam_{family}_labels", (2, S),
                               dtype=np.int32)
    loss, terms = model.loss_terms(ids, labels)
    if family == "nemotron_h":      # the bias AND the balance loss
        assert set(terms) == {"ce", "lbl"} and loss is not terms["ce"]
    elif family == "xing4":         # the MTP depth's term beside it
        assert set(terms) == {"ce", "mtp"} and loss is not terms["ce"]
    else:
        assert set(terms) == {"ce"} and loss is terms["ce"]
