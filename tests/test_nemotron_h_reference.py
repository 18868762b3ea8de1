"""Nemotron-H through ``NemotronHForCausalLM`` against the plain reference
(``chipbench/reference/nemotron_h.py``) at a small size on the CPU on the
cell's pattern ``MEMEM*EME``: seeded weights with every norm weight,
``A_log``, ``dt_bias``, ``D``, the convolution's bias and the router's bias
moved off its initial value, f32 compute, one chip's share of the experts
held.  (The chunked scan against the token-by-token form:
``tests/test_ssd_scan.py``.)

Tolerances.  Program and reference both compute in f32 here, in different
orders (the scan in chunks of 16 against token by token, sorted grouped
products against every-expert-masked sums, flash-style against blocked
attention), so they differ by rounding alone: the limit on logits of size ~1
is 2e-4, loss terms 1e-5 relative, gradients 5e-4 of the leaf's largest
entry.  The negative controls show how far that is from getting the
architecture wrong: each way listed moves some logit by 100 times the limit
or more (a bf16 state-space state by 20 times).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.models import NemotronHConfig, NemotronHForCausalLM

from chipbench.builders.nemotron_h import reference_params
from chipbench.reference import nemotron_h as ref

B, S = 2, 40                 # two chunks of 16 and a ragged third
HELD = (4, 8)                # experts 4..11 of 16
LBL_W = 1e-4
LOGIT_TOL = 2e-4
PATTERN = "MEMEM*EME"
REF_CONFIG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=9,
    hybrid_override_pattern=PATTERN, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, mamba_num_heads=8, mamba_head_dim=16,
    ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=16,
    num_experts_per_tok=3, moe_intermediate_size=32,
    moe_shared_expert_intermediate_size=64, n_shared_experts=1,
    norm_topk_prob=True, routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
    tie_word_embeddings=False)

TOKENS = np.random.default_rng(0).integers(0, 256, (B, S + 1))


def build(compute_dtype=None):
    ids = ht.placeholder_op("ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op("labels", (B, S), dtype=np.int32)
    model = NemotronHForCausalLM(NemotronHConfig(
        seq_len=S, n_routed_experts=16, experts_held=HELD,
        router_aux_loss_coef=LBL_W, **REF_CONFIG))
    loss, terms = model.loss_terms(ids, labels)
    variables = graph_variables([loss], trainable_only=True)
    ex = ht.Executor(
        {"forward": [model(ids), loss, terms["ce"], terms["lbl"]]
         + model.moe_loads(),
         "grads": [loss] + ht.gradients(loss, variables)},
        seed=3, compute_dtype=compute_dtype)
    # off the initial values: a norm weight or D of exactly 1 would hide a
    # dropped scale, a bias of exactly 0 a dropped bias
    r = np.random.default_rng(7)
    for name, value in list(ex.params.items()):
        if name.endswith(("_scale", "_a_log", "_dt_bias", "_d", "_conv_bias",
                          "_bias")):
            ex.params[name] = value + jnp.asarray(
                r.normal(0, 0.2, value.shape), value.dtype)
        # at this width a squared relu of Xavier weights and a Mamba output
        # divided by sqrt(depth) barely reach the logits: make them count
        if name.endswith(("_w1", "_w2", "_mamba_out_weight")):
            ex.params[name] = value * 4.0
        # and at its initial steps (0.001 to 0.1) the state is a small part
        # of a mixer's output beside the skip D x: steps about 0.5 and decays
        # of 0.03 to 0.5 a position make the state remember and matter
        if name.endswith("_dt_bias"):
            ex.params[name] = ex.params[name] + 4.0
        if name.endswith("_a_log"):
            ex.params[name] = ex.params[name] - 3.0
    feed = {ids: TOKENS[:, :-1], labels: TOKENS[:, 1:]}
    return model, ex, variables, feed


@pytest.fixture(scope="module")
def nemo():
    model, ex, variables, feed = build()
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    # host copies: a later run of the executor may donate its buffers
    params = {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}
    sums = jax.jit(lambda p: ref.loss_sums(
        p, REF_CONFIG, TOKENS[:, :-1], TOKENS[:, 1:], held=HELD))(params)
    want = ref.loss_from_sums(sums, LBL_W)
    return dict(model=model, ex=ex, variables=variables, feed=feed, out=out,
                params=params, sums=sums, want=want,
                ref_logits=reference_logits(params))


def reference_logits(params, config=REF_CONFIG, **kwargs):
    return np.asarray(jax.jit(lambda p: ref.forward(
        p, config, TOKENS[:, :-1], held=HELD, **kwargs)[0])(params))


def test_logits_match_reference(nemo):
    assert np.abs(nemo["ref_logits"]).max() > 0.5
    assert np.abs(nemo["out"][0] - nemo["ref_logits"]).max() < LOGIT_TOL


@pytest.mark.parametrize("term,index", [("loss", 1), ("ce", 2), ("lbl", 3)])
def test_loss_term_matches_reference(nemo, term, index):
    want = float(nemo["want"][term])
    assert abs(float(nemo["out"][index]) - want) < 1e-5 * abs(want)


def test_load_vector_is_the_references(nemo):
    """The [3, held] vector fetched beside the loss, one an expert block:
    pairs routed to each held expert as the reference counts them, all kept,
    and the rest of the T k pairs counted as routed elsewhere."""
    first, count = HELD
    loads = nemo["out"][4:]
    assert len(loads) == PATTERN.count("E")
    for block, load in enumerate(loads):
        theirs = np.asarray(nemo["sums"]["load"][block])
        np.testing.assert_array_equal(load[0], theirs[first:first + count])
        np.testing.assert_array_equal(load[1], load[0])
        assert load[2, 0] == theirs.sum() - load[0].sum()
        assert theirs.sum() == B * S * REF_CONFIG["num_experts_per_tok"]


def test_every_gradient_leaf_matches_reference(nemo):
    """One weight of each kind, and every other: each trainable variable's
    gradient against the reference's.  The router's bias is not among
    them."""
    ex, variables = nemo["ex"], nemo["variables"]
    got = ex.run("grads", feed_dict=nemo["feed"],
                 convert_to_numpy_ret_vals=True)[1:]
    params = nemo["params"]
    want = jax.jit(jax.grad(lambda p: ref.pretraining_loss(
        p, REF_CONFIG, TOKENS[:, :-1], TOKENS[:, 1:], LBL_W, held=HELD)))(
            params)
    # reference name <- variable name, by walking the model as the builder does
    names = {v: k for k, v in reference_params(
        nemo["model"], {n: n for n in ex.params}).items()}
    kinds = {k: PATTERN.count(k) for k in "ME*"}
    assert len(params) == len(ref.WEIGHTS) + sum(
        n * (len(ref.BLOCK_WEIGHTS) + len(ref.KIND_WEIGHTS[k]))
        for k, n in kinds.items())
    assert len(variables) == len(params) - kinds["E"]     # the biases
    assert not any(v.name.endswith("moe1_bias") for v in variables)
    for var, g in zip(variables, got):
        w = np.asarray(want[names[var.name]])
        assert np.abs(w).max() > 0, var.name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, var.name


#: what to get wrong in the reference: a module attribute to replace, a
#: configuration key to change, or an argument of ``forward``
WRONG = {
    "gated experts (silu in place of relu2)": dict(
        patch=("_relu2", jax.nn.silu)),
    "top-k not renormalised": dict(patch=("_renormalise", lambda top: top)),
    "no routed scaling factor": dict(config={"routed_scaling_factor": 1.0}),
    "experts chosen without the bias": dict(
        patch=("_selection", lambda scores, bias: scores)),
    "the bias in the weights": dict(patch=("_renormalise", lambda top: (
        top + 0.2) / jnp.sum(top + 0.2, -1, keepdims=True))),
    "no convolution bias": dict(),                  # see the test
    "one group of B and C for all heads": dict(config={"n_groups": 1}),
    # the grouped norm after the scan takes most of it: 20 times the limit
    "bf16 state-space state": dict(kwargs={"state_dtype": jnp.bfloat16},
                                   times=10),
}


@pytest.mark.parametrize("what", list(WRONG))
def test_tolerance_refuses(nemo, monkeypatch, what):
    """The logits tolerance is tight enough that each of these fails it."""
    wrong = WRONG[what]
    config = dict(REF_CONFIG, **wrong.get("config", {}))
    params = nemo["params"]
    if what.startswith("no convolution bias"):
        plain = ref.causal_conv
        monkeypatch.setattr(ref, "causal_conv",
                            lambda x, w, b: plain(x, w, 0.0))
    elif what.startswith("one group"):
        # the same weights read as one group: B and C of group 0 for all
        h, p = REF_CONFIG["mamba_num_heads"], REF_CONFIG["mamba_head_dim"]
        n, d = REF_CONFIG["ssm_state_size"], h * p
        params = dict(params)
        for key in list(params):
            if key.endswith((".in_proj", ".conv", ".conv_bias")):
                w = params[key]
                off = d if key.endswith(".in_proj") else 0
                keep = np.r_[0:off + d + n, off + d + 2 * n:off + d + 3 * n,
                             off + d + 4 * n:w.shape[-1]]
                params[key] = w[..., keep]
    elif "patch" in wrong:
        monkeypatch.setattr(ref, *wrong["patch"])
    logits = reference_logits(params, config, **wrong.get("kwargs", {}))
    gap = np.abs(logits - nemo["out"][0]).max()
    assert gap > wrong.get("times", 100) * LOGIT_TOL, (what, gap)


def test_bf16_compute_fails_the_tolerance(nemo):
    _, ex, _, feed = build(compute_dtype=jnp.bfloat16)
    logits = ex.run("forward", feed_dict=feed,
                    convert_to_numpy_ret_vals=True)[0]
    gap = np.abs(np.asarray(logits, np.float32) - nemo["ref_logits"]).max()
    assert gap > 50 * LOGIT_TOL, gap


def test_published_config_entry():
    """The defaults of ``NemotronHConfig`` are config.json's keys."""
    from hetu_tpu.models import NEMOTRON_H_CONFIGS
    c = NemotronHConfig(**NEMOTRON_H_CONFIGS["nemotron-3-nano-30b-a3b"])
    assert (c.hidden_size, c.num_layers, c.num_heads, c.num_kv_heads,
            c.head_dim, c.vocab_size) == (2688, 52, 32, 2, 128, 131072)
    assert (c.mamba_num_heads, c.mamba_head_dim, c.ssm_state_size,
            c.n_groups, c.conv_kernel, c.chunk_size) == (64, 64, 128, 8, 4,
                                                         128)
    assert (c.num_experts, c.moe_k, c.intermediate_size, c.shared_width,
            c.moe_renorm_topk, c.routed_scaling_factor) == (
                128, 6, 1856, 3712, True, 2.5)
    assert [c.pattern.count(k) for k in "ME*"] == [23, 23, 6]
    assert c.pattern[:9] == PATTERN
