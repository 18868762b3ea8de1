"""Import huggingface BERT weights into a hetu_tpu BertModel.

The reference's migration story for pretrained weights is its ONNX bridge
plus per-example conversion scripts (examples/nlp/bert load paths); for
modern checkpoints the lingua franca is huggingface.  This mapping is
validated bit-tight (5e-4) by tests/test_torch_parity.py.

Usage:
    model = BertModel(cfg, name="bert")
    ex = ht.Executor([...])
    load_hf_bert_weights(ex, model, hf_state_dict, name="bert")
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp


def _numpy_state(state_dict):
    """A transformers state_dict as numpy arrays (torch tensors or arrays
    in), without the ``model.`` prefix where the keys have it."""
    sd = {}
    for k, v in state_dict.items():
        v = v.detach().cpu().numpy() if hasattr(v, "detach") else \
            np.asarray(v)
        sd[k[6:] if k.startswith("model.") else k] = v
    return sd


def _put(params, name, value):
    if name not in params:
        raise KeyError(f"no variable {name!r} in executor params")
    value = np.asarray(value)
    if tuple(params[name].shape) != tuple(value.shape):
        raise ValueError(f"{name}: shape {params[name].shape} vs "
                         f"checkpoint {value.shape}")
    params[name] = jnp.asarray(value, dtype=params[name].dtype)


def load_hf_bert_weights(executor, model, state_dict, name="bert"):
    """Copy a transformers.BertModel state_dict into the executor.

    ``state_dict`` values may be torch tensors or numpy arrays.  torch
    Linear stores (out, in); our linear computes x @ w, so weights are
    transposed on the way in.
    """
    sd = {}
    for k, v in state_dict.items():
        sd[k] = v.detach().cpu().numpy() if hasattr(v, "detach") else \
            np.asarray(v)
    p = executor.params
    e = f"{name}_embeddings"
    _put(p, f"{e}_word_table", sd["embeddings.word_embeddings.weight"])
    _put(p, f"{e}_position", sd["embeddings.position_embeddings.weight"])
    _put(p, f"{e}_tok_type_table",
         sd["embeddings.token_type_embeddings.weight"])
    _put(p, f"{e}_ln_scale", sd["embeddings.LayerNorm.weight"])
    _put(p, f"{e}_ln_bias", sd["embeddings.LayerNorm.bias"])
    for i in range(model.config.num_hidden_layers):
        hf = f"encoder.layer.{i}."
        our = f"{name}_layer{i}"
        for proj, hname in (("q", "attention.self.query"),
                            ("k", "attention.self.key"),
                            ("v", "attention.self.value"),
                            ("out", "attention.output.dense")):
            _put(p, f"{our}_attn_{proj}_weight",
                 sd[hf + hname + ".weight"].T)
            _put(p, f"{our}_attn_{proj}_bias", sd[hf + hname + ".bias"])
        _put(p, f"{our}_ln1_scale",
             sd[hf + "attention.output.LayerNorm.weight"])
        _put(p, f"{our}_ln1_bias",
             sd[hf + "attention.output.LayerNorm.bias"])
        _put(p, f"{our}_ffn_in_weight",
             sd[hf + "intermediate.dense.weight"].T)
        _put(p, f"{our}_ffn_in_bias", sd[hf + "intermediate.dense.bias"])
        _put(p, f"{our}_ffn_out_weight", sd[hf + "output.dense.weight"].T)
        _put(p, f"{our}_ffn_out_bias", sd[hf + "output.dense.bias"])
        _put(p, f"{our}_ln2_scale", sd[hf + "output.LayerNorm.weight"])
        _put(p, f"{our}_ln2_bias", sd[hf + "output.LayerNorm.bias"])
    if "pooler.dense.weight" in sd:
        _put(p, f"{name}_pooler_weight", sd["pooler.dense.weight"].T)
        _put(p, f"{name}_pooler_bias", sd["pooler.dense.bias"])
    else:
        import warnings
        warnings.warn(
            f"checkpoint has no pooler weights; {name}'s pooler stays "
            f"randomly initialized (checkpoint saved with "
            f"add_pooling_layer=False?)", stacklevel=2)
    return executor


def load_hf_gpt2_weights(executor, model, state_dict, name="gpt"):
    """Copy a transformers.GPT2Model state_dict into a GPTModel.

    GPT-2 convs (Conv1D) already store (in, out) — no transpose.  Works
    when the architectures align (pre-LN blocks, learned positions).
    """
    sd = {}
    for k, v in state_dict.items():
        sd[k] = v.detach().cpu().numpy() if hasattr(v, "detach") else \
            np.asarray(v)
    p = executor.params
    H = model.config.hidden_size
    _put(p, f"{name}_wte_table", sd["wte.weight"])
    # our learned positions cover seq_len rows; HF ships max_positions
    _put(p, f"{name}_wpe", sd["wpe.weight"][:model.config.seq_len])
    for i in range(model.config.num_layers):
        hf = f"h.{i}."
        our = f"{name}_h{i}"
        qkv_w = sd[hf + "attn.c_attn.weight"]          # (H, 3H)
        qkv_b = sd[hf + "attn.c_attn.bias"]
        for j, proj in enumerate(("q", "k", "v")):
            _put(p, f"{our}_attn_{proj}_weight",
                 qkv_w[:, j * H:(j + 1) * H])
            _put(p, f"{our}_attn_{proj}_bias", qkv_b[j * H:(j + 1) * H])
        _put(p, f"{our}_attn_out_weight", sd[hf + "attn.c_proj.weight"])
        _put(p, f"{our}_attn_out_bias", sd[hf + "attn.c_proj.bias"])
        _put(p, f"{our}_ln1_scale", sd[hf + "ln_1.weight"])
        _put(p, f"{our}_ln1_bias", sd[hf + "ln_1.bias"])
        _put(p, f"{our}_ffn_in_weight", sd[hf + "mlp.c_fc.weight"])
        _put(p, f"{our}_ffn_in_bias", sd[hf + "mlp.c_fc.bias"])
        _put(p, f"{our}_ffn_out_weight", sd[hf + "mlp.c_proj.weight"])
        _put(p, f"{our}_ffn_out_bias", sd[hf + "mlp.c_proj.bias"])
        _put(p, f"{our}_ln2_scale", sd[hf + "ln_2.weight"])
        _put(p, f"{our}_ln2_bias", sd[hf + "ln_2.bias"])
    _put(p, f"{name}_ln_f_scale", sd["ln_f.weight"])
    _put(p, f"{name}_ln_f_bias", sd["ln_f.bias"])
    return executor


def load_hf_llama_weights(executor, model, state_dict, name="llama"):
    """Copy a transformers Llama-family state_dict into a
    LlamaForCausalLM.  Baichuan checkpoints also fit: their fused
    ``self_attn.W_pack`` projection is split into equal q/k/v thirds
    (Baichuan has no GQA, so the thirds are all hidden-sized).

    Accepts state_dicts with or without the ``model.`` prefix.  Our
    rotary op follows HF's rotate_half convention, so q/k come over
    unpermuted.
    """
    sd = _numpy_state(state_dict)
    p = executor.params
    cfg = model.config
    _put(p, f"{name}_embed_table", sd["embed_tokens.weight"])
    for i in range(cfg.num_layers):
        hf = f"layers.{i}."
        our = f"{name}_layer{i}"
        if hf + "self_attn.W_pack.weight" in sd:   # Baichuan fused qkv
            wp = sd[hf + "self_attn.W_pack.weight"]       # (3H, H)
            h3 = wp.shape[0] // 3
            for j, proj in enumerate(("q", "k", "v")):
                sd[hf + f"self_attn.{proj}_proj.weight"] = \
                    wp[j * h3:(j + 1) * h3]
        for proj, hname in (("q", "self_attn.q_proj"),
                            ("k", "self_attn.k_proj"),
                            ("v", "self_attn.v_proj"),
                            ("out", "self_attn.o_proj")):
            _put(p, f"{our}_attn_{proj}_weight", sd[hf + hname + ".weight"].T)
        _put(p, f"{our}_mlp_gate_weight", sd[hf + "mlp.gate_proj.weight"].T)
        _put(p, f"{our}_mlp_up_weight", sd[hf + "mlp.up_proj.weight"].T)
        _put(p, f"{our}_mlp_out_weight", sd[hf + "mlp.down_proj.weight"].T)
        _put(p, f"{our}_input_norm_scale", sd[hf + "input_layernorm.weight"])
        _put(p, f"{our}_post_norm_scale",
             sd[hf + "post_attention_layernorm.weight"])
    _put(p, f"{name}_norm_scale", sd["norm.weight"])
    if model.lm_head is not None:
        if "lm_head.weight" in sd:
            _put(p, f"{name}_lm_head_weight", sd["lm_head.weight"].T)
        else:  # tied checkpoint into an untied model
            _put(p, f"{name}_lm_head_weight", sd["embed_tokens.weight"].T)
    elif ("lm_head.weight" in sd
          and not np.array_equal(sd["lm_head.weight"],
                                 sd["embed_tokens.weight"])):
        raise ValueError(
            "checkpoint has an untied lm_head.weight but the model was "
            "built with tie_embeddings=True — its logits would silently "
            "diverge; rebuild with tie_embeddings=False")
    return executor


def load_hf_granite_hybrid_weights(executor, model, state_dict,
                                   name="granite"):
    """Copy a transformers ``granitemoehybrid`` state_dict (dense:
    ``num_local_experts`` 0) into a ``GraniteHybridForCausalLM``.

    A Mamba layer's ``mamba.in_proj`` ``[z | xBC | dt]`` and ``out_proj``
    come over transposed, ``mamba.conv1d.weight`` ``[C, 1, K]`` as ``[K,
    C]``, ``dt_bias``, ``A_log``, ``D`` and the gated norm's weight as they
    are; an attention layer's four projections transposed; every layer's
    fused ``shared_mlp.input_linear`` ``[2 I, H]`` is cut into the gate and
    the up matrix (HF chunks its output in that order).  The head is tied.
    Accepts state_dicts with or without the ``model.`` prefix; where the
    model holds a slice of the vocabulary, the first rows of the table."""
    sd = _numpy_state(state_dict)
    p = executor.params
    cfg = model.config
    _put(p, f"{name}_embed_table",
         sd["embed_tokens.weight"][:cfg.vocab_size])
    for i, kind in enumerate(cfg.layer_types):
        hf = f"layers.{i}."
        our = f"{name}_layer{i}"
        if kind == "mamba":
            m = hf + "mamba."
            _put(p, f"{our}_mamba_in_weight", sd[m + "in_proj.weight"].T)
            _put(p, f"{our}_mamba_conv_weight",
                 sd[m + "conv1d.weight"][:, 0, :].T)
            _put(p, f"{our}_mamba_conv_bias", sd[m + "conv1d.bias"])
            for ours, theirs in (("dt_bias", "dt_bias"), ("a_log", "A_log"),
                                 ("d", "D"), ("norm_scale", "norm.weight")):
                _put(p, f"{our}_mamba_{ours}", sd[m + theirs])
            _put(p, f"{our}_mamba_out_weight", sd[m + "out_proj.weight"].T)
        else:
            for proj, hname in (("q", "q_proj"), ("k", "k_proj"),
                                ("v", "v_proj"), ("out", "o_proj")):
                _put(p, f"{our}_attn_{proj}_weight",
                     sd[hf + f"self_attn.{hname}.weight"].T)
        gate, up = np.split(sd[hf + "shared_mlp.input_linear.weight"], 2)
        _put(p, f"{our}_mlp_gate_weight", gate.T)
        _put(p, f"{our}_mlp_up_weight", up.T)
        _put(p, f"{our}_mlp_out_weight",
             sd[hf + "shared_mlp.output_linear.weight"].T)
        _put(p, f"{our}_input_norm_scale", sd[hf + "input_layernorm.weight"])
        _put(p, f"{our}_post_norm_scale",
             sd[hf + "post_attention_layernorm.weight"])
    _put(p, f"{name}_norm_scale", sd["norm.weight"])
    return executor


def export_hf_llama_weights(executor, model, name="llama"):
    """Inverse of ``load_hf_llama_weights``: an executor's Llama params as
    a transformers-layout state_dict of numpy arrays (``model.`` prefix,
    (out, in) weight orientation) — loadable by
    transformers.LlamaForCausalLM.load_state_dict after torch.from_numpy.
    Round-trip interop is the reference's ONNX-bridge role for modern
    checkpoints (tests/test_torch_parity.py proves both directions)."""
    p = executor.params
    cfg = model.config

    def get(n):
        return np.asarray(p[n])

    sd = {"model.embed_tokens.weight": get(f"{name}_embed_table"),
          "model.norm.weight": get(f"{name}_norm_scale")}
    for i in range(cfg.num_layers):
        hf = f"model.layers.{i}."
        our = f"{name}_layer{i}"
        for proj, hname in (("q", "self_attn.q_proj"),
                            ("k", "self_attn.k_proj"),
                            ("v", "self_attn.v_proj"),
                            ("out", "self_attn.o_proj")):
            sd[hf + hname + ".weight"] = get(f"{our}_attn_{proj}_weight").T
        sd[hf + "mlp.gate_proj.weight"] = get(f"{our}_mlp_gate_weight").T
        sd[hf + "mlp.up_proj.weight"] = get(f"{our}_mlp_up_weight").T
        sd[hf + "mlp.down_proj.weight"] = get(f"{our}_mlp_out_weight").T
        sd[hf + "input_layernorm.weight"] = get(f"{our}_input_norm_scale")
        sd[hf + "post_attention_layernorm.weight"] = \
            get(f"{our}_post_norm_scale")
    if model.lm_head is not None:
        sd["lm_head.weight"] = get(f"{name}_lm_head_weight").T
    else:
        sd["lm_head.weight"] = sd["model.embed_tokens.weight"]
    return sd


def load_hf_mixtral_weights(executor, model, state_dict, name="llama"):
    """Copy a transformers.MixtralForCausalLM state_dict into a
    LlamaForCausalLM built with ``num_experts`` (SwiGLU sparse-MoE
    blocks).  Router gate -> TopKGate wg; per-expert w1/w3/w2 stack into
    the MoELayer's [E, H, F]/[E, F, H] tensors.  Gating math matches:
    top-2 renormalization of full-softmax probs equals Mixtral's softmax
    over the top-2 logits, and capacity_factor >= E/k drops nothing."""
    sd = _numpy_state(state_dict)
    p = executor.params
    cfg = model.config
    E = cfg.num_experts
    _put(p, f"{name}_embed_table", sd["embed_tokens.weight"])
    for i in range(cfg.num_layers):
        hf = f"layers.{i}."
        our = f"{name}_layer{i}"
        for proj, hname in (("q", "self_attn.q_proj"),
                            ("k", "self_attn.k_proj"),
                            ("v", "self_attn.v_proj"),
                            ("out", "self_attn.o_proj")):
            _put(p, f"{our}_attn_{proj}_weight", sd[hf + hname + ".weight"].T)
        moe = hf + "block_sparse_moe."
        # variable names come from the layer object (fresh_name may have
        # suffixed the gate), not from string reconstruction
        mlp = model.model.layers[i].mlp
        _put(p, mlp.gate.wg.name, sd[moe + "gate.weight"].T)   # [H, E]
        _put(p, mlp.w1.name, np.stack(
            [sd[moe + f"experts.{j}.w1.weight"].T for j in range(E)]))
        _put(p, mlp.w3.name, np.stack(
            [sd[moe + f"experts.{j}.w3.weight"].T for j in range(E)]))
        _put(p, mlp.w2.name, np.stack(
            [sd[moe + f"experts.{j}.w2.weight"].T for j in range(E)]))
        _put(p, f"{our}_input_norm_scale", sd[hf + "input_layernorm.weight"])
        _put(p, f"{our}_post_norm_scale",
             sd[hf + "post_attention_layernorm.weight"])
    _put(p, f"{name}_norm_scale", sd["norm.weight"])
    if model.lm_head is not None:
        _put(p, f"{name}_lm_head_weight", sd["lm_head.weight"].T)
    return executor


#: key prefixes of a Ling-3.0 checkpoint that name what is not modelled
LING3_REFUSED = ("visual.", "vision", "image_", "video_", "audio", "mtp.",
                 "mtp_", "nextn", "eh_proj", "enorm", "hnorm")


def load_hf_ling3_weights(executor, model, state_dict, name="ling3"):
    """Copy a Ling-3.0 (``bailing_hybrid``) language-model state_dict into a
    ``Ling3ForCausalLM``, for the layers that are modelled.

    The catalog row shows no modelling code, so the key names are a reading:
    Ling 2.0's (``BailingMoeV2``: ``word_embeddings``, ``attention.``,
    ``mlp.gate.weight`` with ``expert_bias``, ``mlp.experts.<j>.``,
    ``mlp.shared_experts.``) and, for a KDA layer, flash-linear-attention's
    (``q_proj`` .. ``o_proj``, ``q_conv1d``, ``f_proj``, ``g_proj``,
    ``b_proj``, ``A_log``, ``dt_bias``, ``o_norm``).  Matrices come over
    transposed; a KDA layer's five projections are laid side by side (``q |
    k | v | f | g``) and its three ``[C, 1, K]`` convolutions as one ``[K, 3
    C]``; a latent layer's ``kv_a_proj_with_mqa``, ``kv_a_layernorm`` and
    ``kv_b_proj`` as they are named in DeepSeek-V2's.  With
    ``experts_held`` the held experts alone are read; where the model holds a
    slice of the vocabulary, the first rows.  **A checkpoint's vision and
    multi-token-prediction weights are refused by name** (``LING3_REFUSED``),
    not skipped: the model that would come out is not the checkpoint's."""
    sd = _numpy_state(state_dict)
    bad = sorted(k for k in sd if any(k.startswith(r) or f".{r}" in k
                                      for r in LING3_REFUSED))
    if bad:
        raise ValueError(
            "Ling-3.0 import: the vision tower and multi-token prediction "
            f"are not modelled; the checkpoint holds {bad[:4]} "
            f"({len(bad)} such keys)")
    p = executor.params
    cfg = model.config
    t = lambda key: sd[key].T
    _put(p, f"{name}_embed_table",
         sd["word_embeddings.weight"][:cfg.vocab_size])
    for i, layer in enumerate(model.model.layers):
        hf = f"layers.{i}."
        a, m, f = hf + "attention.", layer.mixer, layer.mlp
        if layer.kind == "attention":
            _put(p, m.q_proj.name, t(a + "q_proj.weight"))
            _put(p, m.kva_proj.name, t(a + "kv_a_proj_with_mqa.weight"))
            _put(p, m.kv_norm.name, sd[a + "kv_a_layernorm.weight"])
            _put(p, m.kvb_proj.name, t(a + "kv_b_proj.weight"))
            _put(p, m.q_norm.name, sd[a + "query_layernorm.weight"])
            _put(p, m.k_norm.name, sd[a + "key_layernorm.weight"])
            _put(p, m.gate_proj.name, t(a + "g_proj.weight"))
            _put(p, m.out_proj.name, t(a + "dense.weight"))
        else:
            _put(p, m.in_proj.name, np.concatenate(
                [t(a + f"{n}_proj.weight") for n in "qkvfg"], axis=1))
            _put(p, m.beta_proj.name, t(a + "b_proj.weight"))
            _put(p, m.conv.name, np.concatenate(
                [sd[a + f"{n}_conv1d.weight"][:, 0, :].T for n in "qkv"],
                axis=1))
            _put(p, m.a_log.name, sd[a + "A_log"].reshape(-1))
            _put(p, m.dt_bias.name, sd[a + "dt_bias"].reshape(-1))
            _put(p, m.norm.name, sd[a + "o_norm.weight"])
            _put(p, m.out_proj.name, t(a + "o_proj.weight"))
        if layer.dense:
            for ours, theirs in ((f.gate, "gate_proj"), (f.up, "up_proj"),
                                 (f.down, "down_proj")):
                _put(p, ours.weight.name, t(hf + f"mlp.{theirs}.weight"))
        else:
            first, count = cfg.experts_held or (0, cfg.num_experts)
            _put(p, f.gate.wg.name, t(hf + "mlp.gate.weight"))
            _put(p, f.gate.bias.name, sd[hf + "mlp.gate.expert_bias"])
            for var, theirs in ((f.w1, "gate_proj"), (f.w3, "up_proj"),
                                (f.w2, "down_proj")):
                _put(p, var.name, np.stack(
                    [t(hf + f"mlp.experts.{j}.{theirs}.weight")
                     for j in range(first, first + count)]))
            for var, theirs in zip(f.shared, ("gate_proj", "up_proj",
                                              "down_proj")):
                _put(p, var.name,
                     t(hf + f"mlp.shared_experts.{theirs}.weight"))
        _put(p, layer.input_norm.scale.name,
             sd[hf + "input_layernorm.weight"])
        _put(p, layer.post_norm.scale.name,
             sd[hf + "post_attention_layernorm.weight"])
    _put(p, model.model.norm.scale.name, sd["norm.weight"])
    _put(p, model.lm_head.weight.name,
         t("lm_head.weight")[:, :cfg.vocab_size])
    return executor
