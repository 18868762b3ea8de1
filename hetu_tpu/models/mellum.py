"""Mellum 2 decoder LMs (``JetBrains/Mellum2-12B-A2.5B-Instruct``,
``model_type`` "mellum"): window and full attention mixed by layer, every FFN
an expert block, nothing shared.

Layer ``l`` (0-based), pre-norm, RMSNorm, no bias anywhere::

    a = x + Attn_l(N(x));  y = a + MoE(N(a));  final N, untied head

``Attn_l`` has ``num_attention_heads`` query heads on ``num_key_value_heads``
key heads of ``head_dim``, rotary on all of a head (half-split pairs), no norm
on q or k (assumed: no key names one).  Where ``layer_types[l]`` is
``sliding_attention`` position ``i`` sees the keys ``j`` with ``0 <= i - j <
sliding_window`` (its own among them: assumed) under plain rotary; where it
is ``full_attention`` every earlier key under YaRN, cos and sin times
``attention_factor``.  ``MoE``: softmax over all ``num_experts`` in f32, the
``num_experts_per_tok`` largest, their probabilities normalised over the
chosen (``norm_topk_prob``), SwiGLU experts of ``moe_intermediate_size``; no
shared expert, no gate a head, no scaling factor.  ``intermediate_size``
names a dense MLP that no layer has (every ``mlp_layer_types`` entry is
``sparse``).  The loss is the mean next-token cross-entropy alone.

The model is ``models/laguna.py``'s decoder layer under these arguments and
nothing of its own; ``expert_axis`` names the mesh axis the experts are spread
over (``MoELayer(ep_axis=)``, ``parallel.ExpertParallel``), ``experts_held``
one device's share without the others (``MoELayer(held=)``).  **Not
modelled**: an MTP head (the model card says one, ``config.json`` has no key
for it and the published parameter count closes without it), the cache at
inference (a window layer's pages freed behind the window), lengths past the
tables'.
"""

from __future__ import annotations

from .laguna import LagunaConfig, LagunaForCausalLM


class MellumConfig(LagunaConfig):
    """Arguments are the published keys of ``config.json`` under their own
    names; ``seq_len``, ``experts_held``, ``expert_axis`` and what the job
    recomputes (``remat``) are not in it."""

    def __init__(self, vocab_size=98304, hidden_size=2304,
                 intermediate_size=7168, num_hidden_layers=28,
                 num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                 max_position_embeddings=131072, attention_bias=False,
                 rms_norm_eps=1e-6, num_experts=64, num_experts_per_tok=8,
                 moe_intermediate_size=896, norm_topk_prob=True,
                 tie_word_embeddings=False, sliding_window=1024,
                 rope_parameters=None, layer_types=None, mlp_layer_types=None,
                 hidden_act="silu", use_sliding_window=True,
                 max_window_layers=0, seq_len=2048, experts_held=None,
                 expert_axis=None, remat=None):
        n = num_hidden_layers
        assert hidden_act == "silu" and norm_topk_prob and use_sliding_window
        # ``max_window_layers`` 0: no leading run of layers is exempt from
        # ``layer_types``
        assert max_window_layers == 0, max_window_layers
        kinds = tuple(layer_types or (
            "full_attention" if i % 4 == 3 else "sliding_attention"
            for i in range(n)))
        sparse = tuple(mlp_layer_types or ("sparse",) * n)
        assert set(sparse[:n]) == {"sparse"}, (
            "every FFN is an expert block: the dense MLP has no layer")
        super().__init__(
            vocab_size=vocab_size, hidden_size=hidden_size,
            intermediate_size=intermediate_size, num_hidden_layers=n,
            num_attention_heads=num_attention_heads,
            num_key_value_heads=num_key_value_heads, head_dim=head_dim,
            max_position_embeddings=max_position_embeddings,
            attention_bias=attention_bias, rms_norm_eps=rms_norm_eps,
            num_experts=num_experts, num_experts_per_tok=num_experts_per_tok,
            moe_intermediate_size=moe_intermediate_size,
            shared_expert_intermediate_size=0,
            tie_word_embeddings=tie_word_embeddings, gating=False,
            sliding_window=sliding_window,
            rope_parameters=rope_parameters or MELLUM_CONFIGS[
                "mellum2-12b-a2.5b"]["rope_parameters"],
            layer_types=kinds, mlp_layer_types=sparse,
            moe_routed_scaling_factor=None, seq_len=seq_len,
            experts_held=experts_held, remat=remat, router_score="softmax",
            expert_axis=expert_axis)


#: published shapes, the keys of ``config.json`` under their own names
MELLUM_CONFIGS = {
    "mellum2-12b-a2.5b": dict(
        vocab_size=98304, hidden_size=2304, intermediate_size=7168,
        num_hidden_layers=28, num_attention_heads=32, num_key_value_heads=4,
        head_dim=128, max_position_embeddings=131072, rms_norm_eps=1e-6,
        num_experts=64, num_experts_per_tok=8, moe_intermediate_size=896,
        norm_topk_prob=True, tie_word_embeddings=False, sliding_window=1024,
        layer_types=["full_attention" if i % 4 == 3 else "sliding_attention"
                     for i in range(28)],
        mlp_layer_types=["sparse"] * 28,
        rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}}),
}


class MellumForCausalLM(LagunaForCausalLM):
    """``LagunaForCausalLM`` on a ``MellumConfig``: the loss, ``moe_loads``
    (``[4, num_experts]`` over the host's counts where the experts are
    spread over an axis) and ``layers_of`` are its."""

    def __init__(self, config, name="mellum", pipeline_stages=None):
        if pipeline_stages and pipeline_stages > 1:
            raise NotImplementedError(
                "a Mellum model over pipeline stages: one stage is built")
        super().__init__(config, name=name, pipeline_stages=pipeline_stages)
