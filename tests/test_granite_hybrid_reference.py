"""Granite 4.0-H through ``GraniteHybridForCausalLM`` against the plain
reference (``chipbench/reference/granite_hybrid.py``) at a small size on the
CPU on the cell's period ``MMMMM*MMMM``: seeded weights with every norm
weight, ``A_log``, ``dt_bias``, ``D`` and the convolution's bias moved off its
initial value, f32 compute, all eight heads of a mixer in ONE group, 160
positions (a whole chunk of 128 and a ragged second).

Tolerances.  Program and reference both compute in f32 here, in different
orders (the scan in chunks against token by token, flash-style against
blocked attention), so they differ by rounding alone: the limit on logits of
size ~1 is 2e-4, the loss 1e-5 relative, gradients 5e-4 of the leaf's largest
entry.  The negative controls show how far that is from getting the
architecture wrong: each of the four multipliers and the attention scale left
out, an untied or unsliced head and a group a head move some logit, or the
loss, by many times the limit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.models import GraniteHybridConfig, GraniteHybridForCausalLM

from chipbench.builders.granite_hybrid import reference_params
from chipbench.reference import granite_hybrid as ref

B, S = 2, 160
LOGIT_TOL = 2e-4
KINDS = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
REF_CONFIG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=10, layer_types=KINDS,
    num_attention_heads=4, num_key_value_heads=2,
    shared_intermediate_size=128, mamba_n_heads=8, mamba_d_head=16,
    mamba_d_state=16, mamba_n_groups=1, mamba_d_conv=4, mamba_expand=2,
    mamba_chunk_size=256, attention_multiplier=0.015625,
    embedding_multiplier=12, residual_multiplier=0.22, logits_scaling=8,
    rms_norm_eps=1e-5, tie_word_embeddings=True)

TOKENS = np.random.default_rng(0).integers(0, 256, (B, S + 1))
IDS, LABELS = TOKENS[:, :-1], TOKENS[:, 1:]


def build(compute_dtype=None, **over):
    ids = ht.placeholder_op("ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op("labels", (B, S), dtype=np.int32)
    model = GraniteHybridForCausalLM(GraniteHybridConfig(
        seq_len=S, **dict(REF_CONFIG, **over)))
    loss = model.loss(ids, labels)
    variables = graph_variables([loss], trainable_only=True)
    ex = ht.Executor({"forward": [model(ids), loss],
                      "grads": [loss] + ht.gradients(loss, variables)},
                     seed=3, compute_dtype=compute_dtype)
    # off the initial values: a norm weight or D of exactly 1 would hide a
    # dropped scale, a bias of exactly 0 a dropped bias
    r = np.random.default_rng(7)
    for name, value in list(ex.params.items()):
        if name.endswith(("_scale", "_a_log", "_dt_bias", "_d",
                          "_conv_bias")):
            ex.params[name] = value + jnp.asarray(
                r.normal(0, 0.2, value.shape), value.dtype)
        # at its initial steps (0.001 to 0.1) the state is a small part of a
        # mixer's output beside the skip D x: steps about 0.5 and decays of
        # 0.03 to 0.5 a position make the state remember and matter
        if name.endswith("_dt_bias"):
            ex.params[name] = ex.params[name] + 4.0
        if name.endswith("_a_log"):
            ex.params[name] = ex.params[name] - 3.0
        # an embedding of 0.02 times 12 is small beside the layers' sums, and
        # logits over 8 from a tied 0.02 table are flat: make both count
        if name.endswith("_embed_table"):
            ex.params[name] = value * 8.0
    return model, ex, variables, {ids: IDS, labels: LABELS}


def reference_logits(params, config=REF_CONFIG, **kwargs):
    return np.asarray(jax.jit(lambda p: ref.forward(
        p, config, IDS, **kwargs))(params))


def reference_loss(params, config=REF_CONFIG):
    return float(jax.jit(lambda p: ref.pretraining_loss(
        p, config, IDS, LABELS))(params))


@pytest.fixture(scope="module")
def granite():
    model, ex, variables, feed = build()
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    grads = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    # host copies: a later run of the executor may donate its buffers
    params = {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}
    # reference name <- variable name, by walking the model as the builder does
    names = {v: k for k, v in reference_params(
        model, {n: n for n in ex.params}).items()}
    return dict(model=model, ex=ex, variables=variables, feed=feed, out=out,
                grads=dict(zip((v.name for v in variables), grads[1:])),
                params=params, names=names,
                ref_logits=reference_logits(params))


def test_logits_match_reference(granite):
    assert np.abs(granite["ref_logits"]).max() > 0.5
    assert np.abs(granite["out"][0] - granite["ref_logits"]).max() < LOGIT_TOL


def test_loss_matches_reference(granite):
    want = reference_loss(granite["params"])
    assert abs(float(granite["out"][1]) - want) < 1e-5 * abs(want)


def test_every_gradient_leaf_matches_reference(granite):
    """Each trainable variable's gradient against the reference's: nine
    mixers' eight weights, the attention layer's four, ten layers' two norms
    and three MLP matrices, the final norm and the ONE tied matrix."""
    params, got = granite["params"], granite["grads"]
    want = jax.jit(jax.grad(lambda p: ref.pretraining_loss(
        p, REF_CONFIG, IDS, LABELS)))(params)
    assert len(params) == len(ref.WEIGHTS) + sum(
        len(ref.LAYER_WEIGHTS) + len(ref.MIXER_WEIGHTS[k]) for k in KINDS)
    assert len(got) == len(params) == 2 + 9 * 13 + 9
    for name, g in got.items():
        w = np.asarray(want[granite["names"][name]])
        assert np.abs(w).max() > 0, name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, name


def test_the_tied_matrix_gradient_is_the_sum_of_both_uses(granite):
    """The reference with an untied copy of the matrix as its head gives the
    gradient of each use apart: the program's gradient of its one matrix is
    their sum, and neither alone."""
    params = dict(granite["params"])
    params["lm_head"] = params["embed"].T.copy()
    g = jax.jit(jax.grad(lambda p: ref.pretraining_loss(
        p, REF_CONFIG, IDS, LABELS)))(params)
    as_input, as_head = np.asarray(g["embed"]), np.asarray(g["lm_head"]).T
    got = granite["grads"]["granite_embed_table"]
    scale = np.abs(as_input + as_head).max()
    assert np.abs(got - (as_input + as_head)).max() < 5e-4 * scale
    assert np.abs(got - as_input).max() > 0.05 * scale
    assert np.abs(got - as_head).max() > 0.05 * scale


#: what to get wrong in the reference: a configuration key to change
WRONG = {
    "no embedding multiplier": {"embedding_multiplier": 1.0},
    "no residual multiplier": {"residual_multiplier": 1.0},
    "logits not scaled": {"logits_scaling": 1.0},
    "no attention multiplier (d^-1/2 in its place)":
        {"attention_multiplier": 16 ** -0.5},
    "a group of B and C a head": {"mamba_n_groups": 8},
}


@pytest.mark.parametrize("what", list(WRONG))
def test_tolerance_refuses(granite, what):
    """The logits tolerance is tight enough that each of these fails it, and
    each moves the loss."""
    config = dict(REF_CONFIG, **WRONG[what])
    params = granite["params"]
    if what.startswith("a group"):
        # the same weights read as eight groups: B and C widened by copies
        h, p, n = 8, 16, 16
        d = h * p
        params = dict(params)
        for key in list(params):
            if key.endswith((".in_proj", ".conv", ".conv_bias")):
                w = params[key]
                off = d if key.endswith(".in_proj") else 0
                xs, Bs, Cs, rest = np.split(
                    w, [off + d, off + d + n, off + d + 2 * n], axis=-1)
                params[key] = np.concatenate(
                    [xs] + [Bs * (1 + 0.1 * j) for j in range(8)]
                    + [Cs] * 8 + [rest], axis=-1)
    logits = reference_logits(params, config)
    gap = np.abs(logits - granite["out"][0]).max()
    assert gap > 100 * LOGIT_TOL, (what, gap)
    want = float(granite["out"][1])
    # (the loss's own limit is 1e-5 of it; the one attention layer of ten
    # moves it least, by 8e-5)
    assert abs(reference_loss(params, config) - want) > 5e-5 * want, what


@pytest.mark.parametrize("key,value", [
    ("embedding_multiplier", 6), ("residual_multiplier", 0.5),
    ("logits_scaling", 4), ("attention_multiplier", 0.25)])
def test_each_multiplier_moved_alone_changes_the_programs_loss(granite, key,
                                                               value):
    """The program reads each of the four multipliers (and the softmax
    scale): built with one of them changed, on the same weights, its loss is
    the reference's with the same change and not the unchanged one's."""
    model, ex, _, feed = build(**{key: value})
    for name in list(ex.params):
        ex.params[name] = jnp.asarray(
            granite["params"][granite["names"][name]])
    loss = float(ex.run("forward", feed_dict=feed,
                        convert_to_numpy_ret_vals=True)[1])
    want = reference_loss(granite["params"], dict(REF_CONFIG, **{key: value}))
    assert abs(loss - want) < 1e-5 * want
    assert abs(loss - float(granite["out"][1])) > 5e-5 * want, key


def test_the_slice_is_the_vocabulary(granite):
    """Ids, logits and the loss are over the rows held: the reference given
    the held rows of a table four times as long agrees, and given the whole
    table (the same ids) does not."""
    params = dict(granite["params"])
    r = np.random.default_rng(11)
    whole = np.concatenate([params["embed"], r.normal(
        0, 0.16, (768, 64)).astype(np.float32)])
    held = dict(params, embed=whole[:256])
    assert np.abs(reference_logits(held) - granite["out"][0]).max() < LOGIT_TOL
    loss = reference_loss(dict(params, embed=whole))
    assert loss - float(granite["out"][1]) > 0.1


def test_remat_changes_nothing(granite):
    """``remat="mamba"`` (the fixture's, the default) recomputes and changes
    nothing: the loss of ``remat=None`` to the last bit in f32, and every
    gradient to the rounding of a sum's order."""
    model, ex, variables, feed = build(remat=None)
    assert not any(layer.recompute for layer in model.model.layers)
    assert sum(layer.recompute
               for layer in granite["model"].model.layers) == 9
    for name in list(ex.params):
        ex.params[name] = jnp.asarray(
            granite["params"][granite["names"][name]])
    out = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    again = granite["ex"].run("grads", feed_dict=granite["feed"],
                              convert_to_numpy_ret_vals=True)
    np.testing.assert_array_equal(out[0], again[0])
    for var, a, b in zip(variables, out[1:], again[1:]):
        # XLA's CPU backend fuses the recomputed step otherwise and adds in
        # another order: the last bits of a sum, 1e-6 of a leaf's largest
        assert np.abs(a - b).max() <= 2e-6 * np.abs(b).max(), var.name


def test_bf16_compute_fails_the_tolerance(granite):
    _, ex, _, feed = build(compute_dtype=jnp.bfloat16)
    logits = ex.run("forward", feed_dict=feed,
                    convert_to_numpy_ret_vals=True)[0]
    gap = np.abs(np.asarray(logits, np.float32)
                 - granite["ref_logits"]).max()
    assert gap > 20 * LOGIT_TOL, gap


def test_published_config_entry():
    """The defaults of ``GraniteHybridConfig`` are config.json's keys."""
    from hetu_tpu.models import GRANITE_HYBRID_CONFIGS
    c = GraniteHybridConfig(**GRANITE_HYBRID_CONFIGS["granite-4.0-h-micro"])
    assert (c.hidden_size, c.num_layers, c.num_heads, c.num_kv_heads,
            c.vocab_size, c.intermediate_size) == (2048, 40, 32, 8, 100352,
                                                   8192)
    assert (c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size,
            c.n_groups, c.conv_kernel) == (64, 64, 128, 1, 4)
    assert (c.attention_multiplier, c.embedding_multiplier,
            c.residual_multiplier, c.logits_scaling) == (0.015625, 12, 0.22,
                                                         8)
    assert c.tie_embeddings and c.num_experts is None
    assert [i for i, k in enumerate(c.layer_types) if k == "attention"] == [
        5, 15, 25, 35]
    assert c.layer_types[:10] == KINDS


def attention_step_text(**kw):
    """Lowered text of a tiny attention layer's train step."""
    from hetu_tpu.graph import node as graph_node
    from hetu_tpu.layers.attention import MultiHeadAttention
    from hetu_tpu.optim import optimizer
    counter, opt_counter = (graph_node._node_counter[0],
                            optimizer._opt_counter[0])
    graph_node._node_counter[0] = 10 ** 6
    optimizer._opt_counter[0] = 10 ** 3
    try:
        with ht.name_scope():
            layer = MultiHeadAttention(64, 4, sequence_length=32,
                                       causal_mask=True, num_kv_heads=2,
                                       bias=False, name="sc_attn", **kw)
            x = ht.placeholder_op("sc_x", (2, 32, 64))
            y = layer(x, x, x)
            loss = ht.reduce_sum_op(y * y, axes=[0, 1, 2])
            ex = ht.Executor({"train": [loss, ht.SGDOptimizer(0.1).minimize(
                loss)]}, seed=0)
        sub = ex.subexecutor["train"]
        sub._build()
        op = y.inputs[0]
        while not hasattr(op, "scale"):
            op = op.inputs[0]
        return sub._jitted.lower(*sub._abstract_args(None)).as_text(), op
    finally:
        graph_node._node_counter[0] = max(counter,
                                          graph_node._node_counter[0])
        optimizer._opt_counter[0] = opt_counter


def test_attention_without_a_scale_is_the_layer_as_it_was():
    """``MultiHeadAttention(scale=None)``, the default, hands the attention
    op no scale (the op then takes ``head_dim ** -0.5``, as before the layer
    had the argument) and lowers to the text of the layer built without the
    argument; the same number given outright lowers to the same text, and
    Granite's 1/64 to another."""
    plain, op = attention_step_text()
    assert op.scale is None
    given_none, op = attention_step_text(scale=None)
    assert op.scale is None and given_none == plain
    same, op = attention_step_text(scale=16 ** -0.5)
    assert op.scale == 0.25 and same == plain
    other, op = attention_step_text(scale=0.015625)
    assert op.scale == 0.015625 and other != plain


def test_hf_state_dict_names_map_onto_the_model(granite):
    """A toy ``granitemoehybrid`` state dict (HF's names and orientations:
    ``[out, in]`` matrices, the convolution ``[C, 1, K]``, one fused
    ``shared_mlp.input_linear``) loads into the model: the weights the plain
    reference reads are then the dict's, and the logits the reference's."""
    from hetu_tpu.models import load_hf_granite_hybrid_weights
    r = np.random.default_rng(13)
    want = {k: (v + r.normal(0, 0.02, v.shape)).astype(np.float32)
            for k, v in granite["params"].items()}
    sd = {"model.embed_tokens.weight": want["embed"],
          "model.norm.weight": want["norm"]}
    for i, kind in enumerate(KINDS):
        w = {k[len(f"layers.{i}."):]: v for k, v in want.items()
             if k.startswith(f"layers.{i}.")}
        hf = f"model.layers.{i}."
        sd[hf + "input_layernorm.weight"] = w["input_norm"]
        sd[hf + "post_attention_layernorm.weight"] = w["post_norm"]
        sd[hf + "shared_mlp.input_linear.weight"] = np.concatenate(
            [w["mlp_gate"].T, w["mlp_up"].T])
        sd[hf + "shared_mlp.output_linear.weight"] = w["mlp_down"].T
        if kind == "mamba":
            sd.update({
                hf + "mamba.in_proj.weight": w["in_proj"].T,
                hf + "mamba.conv1d.weight": w["conv"].T[:, None, :],
                hf + "mamba.conv1d.bias": w["conv_bias"],
                hf + "mamba.dt_bias": w["dt_bias"],
                hf + "mamba.A_log": w["a_log"], hf + "mamba.D": w["d"],
                hf + "mamba.norm.weight": w["ssm_norm"],
                hf + "mamba.out_proj.weight": w["out_proj"].T})
        else:
            sd.update({hf + f"self_attn.{n}_proj.weight": w[n].T
                       for n in "qkvo"})
    model, ex, _, feed = build()
    load_hf_granite_hybrid_weights(ex, model, sd)
    got = reference_params(model, ex.params)
    assert sorted(got) == sorted(want)
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(got[k]), v, err_msg=k)
    logits = ex.run("forward", feed_dict=feed,
                    convert_to_numpy_ret_vals=True)[0]
    assert np.abs(logits - reference_logits(want)).max() < LOGIT_TOL
    with pytest.raises(KeyError):
        load_hf_granite_hybrid_weights(ex, model, {
            k: v for k, v in sd.items() if "layers.5.self_attn.q" not in k})
