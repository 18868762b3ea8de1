"""Mellum through ``MellumForCausalLM`` against the plain reference
(``chipbench/reference/mellum.py``) at a small size on the CPU: seeded weights
with every norm weight moved off its initial value, f32 compute, one device,
all experts; with and without recomputation, and with a window smaller than
and larger than the sequence.  Program and reference both compute in f32 here,
in different orders (sorted grouped products against every-expert-masked sums,
one softmax against blocked attention), so they differ by rounding alone; the
negative controls show how far that is from getting the architecture wrong.
The experts over an axis are ``tests/test_moe_expert_axis.py``'s, the cell's
program under its strategy ``tests/test_mellum_cell.py``'s."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.models import (MELLUM_CONFIGS, MellumConfig,
                             MellumForCausalLM)
from hetu_tpu.ops.rotary import _rope_tables

from chipbench.builders.mellum import reference_nodes
from chipbench.reference import mellum as ref

B, S = 2, 48
LOGIT_TOL = 2e-4
ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 32, "beta_fast": 8,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
REF_CONFIG = dict(
    vocab_size=256, hidden_size=48, intermediate_size=96, num_hidden_layers=2,
    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
    rms_norm_eps=1e-6, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=24, sliding_window=16, rope_parameters=ROPE,
    layer_types=["sliding_attention", "full_attention"],
    mlp_layer_types=["sparse"] * 2)
TOKENS = np.random.default_rng(0).integers(0, 256, (B, S + 1))
#: (what is recomputed, the window): below and above the sequence (the
#: published period of four layers runs through the cell's builder in
#: ``test_mellum_cell.py``)
CASES = [(None, 16), ("layer", 64)]


def ref_params(model, params):
    """The reference's flat names (``head.`` is ``walk``'s group, not
    ``pretraining_loss``'s)."""
    return {k.replace("head.", ""): np.asarray(params[v.name])
            for k, v in reference_nodes(model).items()}


def build(name, remat=None, **over):
    ids = ht.placeholder_op(f"{name}_ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op(f"{name}_labels", (B, S), dtype=np.int32)
    model = MellumForCausalLM(MellumConfig(
        seq_len=S, remat=remat, **dict(REF_CONFIG, **over)), name=name)
    loss, _ = model.loss_terms(ids, labels)
    variables = graph_variables([loss], trainable_only=True)
    ex = ht.Executor(
        {"forward": [model(ids), loss] + model.moe_loads(),
         "grads": [loss] + ht.gradients(loss, variables)}, seed=3)
    r = np.random.default_rng(7)
    for key, value in list(ex.params.items()):
        if key.endswith("_scale"):
            ex.params[key] = value + jnp.asarray(
                r.normal(0, 0.2, value.shape), value.dtype)
    feed = {ids: TOKENS[:, :-1], labels: TOKENS[:, 1:]}
    return model, ex, variables, feed


def weights_of(params):
    """``walk``'s loader over the flat names."""
    def weights(prefix):
        if prefix == "embed":
            return params["embed"]
        if prefix == "head.":
            return {k: params[k] for k in ref.HEAD_WEIGHTS}
        return {k[len(prefix):]: v for k, v in params.items()
                if k.startswith(prefix)}
    return weights


def reference_logits(params, config, **kwargs):
    """``[B S, V]`` by ``walk``, the path the cell's comparison takes."""
    _, kept = ref.walk(weights_of(params), config, TOKENS[:, :-1],
                       TOKENS[:, 1:], keep=tuple(range(B)), **kwargs)
    return kept["logits"]


@pytest.fixture(scope="module", params=CASES,
                ids=[f"remat_{r}-window_{w}" for r, w in CASES])
def mellum(request):
    remat, window = request.param
    config = dict(REF_CONFIG, sliding_window=window)
    model, ex, variables, feed = build(f"melref_{remat}_{window}", remat,
                                       sliding_window=window)
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    params = ref_params(model, ex.params)
    return dict(model=model, ex=ex, variables=variables, feed=feed, out=out,
                params=params, config=config,
                ref_logits=reference_logits(params, config))


def test_layer_kinds_and_weights(mellum):
    layers = mellum["model"].model.layers
    assert [l.kind for l in layers] == REF_CONFIG["layer_types"]
    assert not any(l.dense for l in layers)
    assert [l.attn.window for l in layers] == [
        mellum["config"]["sliding_window"], None]
    assert all(l.attn.gate_proj is None and l.mlp.shared is None
               and l.mlp.gate.score == "softmax" and l.mlp.gate.renorm
               and l.mlp.gate.scale is None for l in layers)
    want = 1 + len(ref.HEAD_WEIGHTS) + 2 * len(ref.LAYER_WEIGHTS)
    assert len(mellum["params"]) == want == len(mellum["variables"])


def test_logits_and_loss_match_reference(mellum):
    assert np.abs(mellum["ref_logits"]).max() > 0.3
    assert np.abs(mellum["out"][0] - mellum["ref_logits"]).max() < LOGIT_TOL
    want = float(jax.jit(lambda p: ref.pretraining_loss(
        p, mellum["config"], TOKENS[:, :-1], TOKENS[:, 1:]))(
            mellum["params"]))
    assert abs(float(mellum["out"][1]) - want) < 1e-5 * abs(want)


def test_every_gradient_leaf_matches_reference(mellum):
    """One parameter of each kind and every other: 5e-4 of the leaf's largest
    entry, f32 sums in two orders (where nothing is recomputed: the recomputed
    program's forward pass is held above, its gradients by the cell's
    rehearsal)."""
    if mellum["model"].config.remat is not None:
        pytest.skip("the gradients are compared where nothing is recomputed")
    ex, variables = mellum["ex"], mellum["variables"]
    got = ex.run("grads", feed_dict=mellum["feed"],
                 convert_to_numpy_ret_vals=True)[1:]
    want = jax.jit(jax.grad(lambda p: ref.pretraining_loss(
        p, mellum["config"], TOKENS[:, :-1], TOKENS[:, 1:])))(
            mellum["params"])
    names = {v.name: k.replace("head.", "")
             for k, v in reference_nodes(mellum["model"]).items()}
    for var, g in zip(variables, got):
        w = np.asarray(want[names[var.name]])
        assert np.abs(w).max() > 0, var.name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, var.name


def test_load_vector_is_the_references(mellum):
    _, kept = ref.walk(weights_of(mellum["params"]), mellum["config"],
                       TOKENS[:, :-1], TOKENS[:, 1:])
    assert len(mellum["out"][2:]) == 2
    for load, ch in zip(mellum["out"][2:], kept["chosen"]):
        np.testing.assert_array_equal(
            load[0], np.bincount(ch.reshape(-1), minlength=16))
        np.testing.assert_array_equal(load[1], load[0])
        assert load[0].sum() == B * S * 4


@pytest.mark.parametrize("what", ["attention_factor", "norm_topk",
                                  "returned_order", "window_less", "bf16"])
def test_tolerance_refuses(mellum, what):
    """A changed piece of each kind (the others run on the chip and in the
    cell's rehearsal) and bf16 operands move some logit by more than five
    times the tolerance."""
    params, logits, config = (mellum[k] for k in ("params", "ref_logits",
                                                  "config"))
    if what == "window_less" and config["sliding_window"] > S:
        pytest.skip("a window that holds every key has no edge to move")
    how = ({"matmul_inputs": jnp.bfloat16} if what == "bf16"
           else {"without": (what,)})
    wrong = reference_logits(params, config, ranks=4, **how)
    # the least, the probabilities left unnormalised, moves one by 8.7 times
    assert np.abs(wrong - logits).max() > 5 * LOGIT_TOL, what


def test_the_windows_edge():
    """Position ``i`` sees ``0 <= i - j < w``, its own among them: the
    program's window layer agrees with the reference's at ``w`` and with
    neither neighbour."""
    from hetu_tpu.layers.attention import MultiHeadAttention
    c = MellumConfig(seq_len=S, **REF_CONFIG)
    x = ht.placeholder_op("meledge_x", (B, S, 48))
    layer = MultiHeadAttention(
        48, 8, sequence_length=S, causal_mask=True, num_kv_heads=2,
        head_dim=16, bias=False, window=16, name="meledge",
        **c.rope["sliding_attention"])
    ex = ht.Executor([layer(x, x, x)], seed=1)
    u = np.random.default_rng(3).normal(0, 1, (B, S, 48)).astype(np.float32)
    (got,) = ex.run(feed_dict={x: u}, convert_to_numpy_ret_vals=True)
    w = {k: np.asarray(ex.params[v.weight.name]) for k, v in (
        ("q", layer.q_proj), ("k", layer.k_proj), ("v", layer.v_proj),
        ("o", layer.out_proj))}
    with jax.default_matmul_precision("highest"):
        at, less, more = (np.asarray(ref.attention(
            jnp.asarray(u[0]), w, REF_CONFIG, "sliding_attention",
            lambda a, b: a @ b, widen=by)) for by in (0, -1, 1))
    assert np.abs(got[0] - at).max() < 1e-5
    assert min(np.abs(got[0] - less).max(),
               np.abs(got[0] - more).max()) > 1e-3


def test_yarn_table_is_the_references():
    """The program's cos and sin under YaRN (``ops/rotary.py``) are the
    reference's own, at the published parameters and the toy's; the published
    factor is ``0.1 ln(factor) + 1``."""
    for p in (MELLUM_CONFIGS["mellum2-12b-a2.5b"]["rope_parameters"][
            "full_attention"], ROPE["full_attention"]):
        d = 128
        kw = MellumConfig(seq_len=S, **dict(
            REF_CONFIG, head_dim=d, rope_parameters=dict(
                ROPE, full_attention=p))).rope["full_attention"]
        mine = _rope_tables(64, d, kw["rope_theta"],
                            scaling=kw["rope_scaling"])
        theirs = ref.rotary_tables(64, d, p)
        for a, b in zip(mine, theirs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=2e-5)   # f32 angles to 63 rad
        assert abs(p["attention_factor"]
                   - (0.1 * np.log(p["factor"]) + 1.0)) < 1e-12


def test_published_configuration_counts_its_parameters():
    """12.15 G in all and 2.44 G active from the published keys: no shared
    expert, no gate and no dense MLP are needed to close the count."""
    c = MELLUM_CONFIGS["mellum2-12b-a2.5b"]
    h, d = c["hidden_size"], c["head_dim"]
    attention = h * d * (2 * c["num_attention_heads"]
                         + 2 * c["num_key_value_heads"])
    expert = 3 * h * c["moe_intermediate_size"]
    router = h * c["num_experts"]
    ends = 2 * c["vocab_size"] * h
    layers = c["num_hidden_layers"]
    whole = layers * (attention + router + c["num_experts"] * expert) + ends
    active = layers * (attention + router
                       + c["num_experts_per_tok"] * expert) + ends
    assert round(whole / 1e9, 2) == 12.15 and round(active / 1e9, 2) == 2.44
    with pytest.raises(NotImplementedError):
        MellumForCausalLM(MellumConfig(**REF_CONFIG), name="melpp",
                          pipeline_stages=2)
