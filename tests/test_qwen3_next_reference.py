"""Qwen3-Next through ``Qwen3NextForCausalLM`` against the plain reference
(``chipbench/reference/qwen3_next.py``) at a small size on the CPU, seeded
weights with every norm weight, ``A_log`` and ``dt_bias`` moved off its
initial value, f32 compute, one chip's share of the experts held.  (The
chunked delta rule against the token-by-token form and the convolution's
causality: ``tests/test_gated_delta_rule.py``.)

Tolerances.  Program and reference both compute in f32 here, in different
orders (the delta rule in a chunk of 64 against token by token, sorted grouped
products against every-expert-masked sums, flash-style against blocked
attention), so they differ by rounding alone: of logits of size ~1 half agree
to 2e-7 and all to 1e-4 (the chunk's decays are differences of running sums
as large as 800), so the limit is 2e-4; loss terms to 1e-5 relative;
gradients to 5e-4 of the leaf's largest entry (the worst, 3e-4, is a DeltaNet
layer's dt_bias under three more layers; the head's own is 4e-5).  The
negative controls show how far that is from getting the architecture wrong:
each way the issue lists, and a bf16 DeltaNet state, moves some logit by
0.07 or more.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM

from chipbench.builders.qwen3_next import reference_params
from chipbench.reference import qwen3_next as ref

B, S = 2, 40                 # one padded chunk of 64 here; several: test_gated_delta_rule.py
HELD = (4, 8)                # experts 4..11 of 16
LBL_W = 0.001
LOGIT_TOL = 2e-4
REF_CONFIG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    partial_rotary_factor=0.25, rope_theta=10000000.0, rms_norm_eps=1e-6,
    full_attention_interval=4, linear_conv_kernel_dim=4,
    linear_key_head_dim=16, linear_value_head_dim=16, linear_num_key_heads=2,
    linear_num_value_heads=4, num_experts_per_tok=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32,
    norm_topk_prob=True, tie_word_embeddings=False)


TOKENS = np.random.default_rng(0).integers(0, 256, (B, S + 1))[:, :-1]


def build(compute_dtype=None):
    ids = ht.placeholder_op("ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op("labels", (B, S), dtype=np.int32)
    model = Qwen3NextForCausalLM(Qwen3NextConfig(
        seq_len=S, num_experts=16, experts_held=HELD,
        router_aux_loss_coef=LBL_W, **REF_CONFIG))
    loss, terms = model.loss_terms(ids, labels)
    variables = graph_variables([loss], trainable_only=True)
    ex = ht.Executor(
        {"forward": [model(ids), loss, terms["ce"], terms["lbl"]]
         + model.moe_loads(),
         "grads": [loss] + ht.gradients(loss, variables)},
        seed=3, compute_dtype=compute_dtype)
    # off the initial values: a norm weight of exactly 0 or 1 would hide
    # `w` for `1 + w`, dt_bias of exactly 1 a dropped bias
    r = np.random.default_rng(7)
    for name, value in list(ex.params.items()):
        if name.endswith(("_scale", "_a_log", "_dt_bias")):
            ex.params[name] = value + jnp.asarray(
                r.normal(0, 0.2, value.shape), value.dtype)
    tok = np.random.default_rng(0).integers(0, 256, (B, S + 1))
    feed = {ids: tok[:, :-1], labels: tok[:, 1:]}
    return model, ex, variables, feed, tok


@pytest.fixture(scope="module")
def qwen():
    model, ex, variables, feed, tok = build()
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    # host copies: a later run of the executor may donate its buffers
    params = {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}
    sums = jax.jit(lambda p: ref.loss_sums(
        p, REF_CONFIG, tok[:, :-1], tok[:, 1:], held=HELD))(params)
    want = ref.loss_from_sums(sums, LBL_W)
    return dict(model=model, ex=ex, variables=variables, feed=feed, tok=tok,
                out=out, params=params, sums=sums, want=want,
                ref_logits=reference_logits(params))


def reference_logits(params, config=REF_CONFIG, **kwargs):
    return np.asarray(jax.jit(lambda p: ref.forward(
        p, config, TOKENS, held=HELD, **kwargs)[0])(params))


def test_logits_match_reference(qwen):
    assert np.abs(qwen["ref_logits"]).max() > 0.5
    assert np.abs(qwen["out"][0] - qwen["ref_logits"]).max() < LOGIT_TOL


@pytest.mark.parametrize("term,index", [("loss", 1), ("ce", 2), ("lbl", 3)])
def test_loss_term_matches_reference(qwen, term, index):
    want = float(qwen["want"][term])
    assert abs(float(qwen["out"][index]) - want) < 1e-5 * abs(want)


def test_load_vector_is_the_references(qwen):
    """The [3, held] vector fetched beside the loss: pairs routed to each
    held expert as the reference counts them, all kept, and the rest of the
    T k pairs counted as routed elsewhere."""
    first, count = HELD
    for layer, load in enumerate(qwen["out"][4:]):
        theirs = np.asarray(qwen["sums"]["load"][layer])
        np.testing.assert_array_equal(load[0], theirs[first:first + count])
        np.testing.assert_array_equal(load[1], load[0])
        assert load[2, 0] == theirs.sum() - load[0].sum()
        assert theirs.sum() == B * S * REF_CONFIG["num_experts_per_tok"]


def test_every_gradient_leaf_matches_reference(qwen):
    ex, variables, tok = qwen["ex"], qwen["variables"], qwen["tok"]
    got = ex.run("grads", feed_dict=qwen["feed"],
                 convert_to_numpy_ret_vals=True)[1:]
    params = qwen["params"]
    want = jax.jit(jax.grad(lambda p: ref.pretraining_loss(
        p, REF_CONFIG, tok[:, :-1], tok[:, 1:], LBL_W, held=HELD)))(params)
    # reference name <- variable name, by walking the model as the builder does
    names = {v: k for k, v in reference_params(
        qwen["model"], {n: n for n in ex.params}).items()}
    per_layer = len(ref.LAYER_WEIGHTS)
    assert len(variables) == len(params) == (
        len(ref.WEIGHTS) + 4 * per_layer + 3 * len(ref.DELTANET_WEIGHTS)
        + len(ref.ATTENTION_WEIGHTS))
    for var, g in zip(variables, got):
        w = np.asarray(want[names[var.name]])
        assert np.abs(w).max() > 0, var.name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, var.name


def _halves(qg, d):
    """Query and gate as two halves of the whole projection."""
    flat = qg.reshape(qg.shape[:2] + (-1,))
    half = flat.shape[-1] // 2
    return (flat[..., :half].reshape(qg.shape[:3] + (d,)),
            flat[..., half:].reshape(qg.shape[:3] + (d,)))


#: what to get wrong in the reference: a module attribute to replace, a
#: configuration key to change, or an argument of ``forward``
WRONG = {
    "norm weight w, not 1 + w": dict(patch=("_norm", lambda x, w, eps: (
        x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w))),
    "rotary over the whole head": dict(config={"partial_rotary_factor": 1.0}),
    "q and gate as two halves": dict(patch=("_query_and_gate", _halves)),
    "no L2 norm of q and k": dict(patch=("_unit", lambda t: t)),
    "no shared-expert gate": dict(patch=("_shared_scale",
                                         lambda h, w, mm: 1.0)),
    "top-k not renormalised": dict(patch=("_renormalise", lambda top: top)),
    "bf16 DeltaNet state": dict(kwargs={"state_dtype": jnp.bfloat16}),
}


@pytest.mark.parametrize("what", list(WRONG))
def test_tolerance_refuses(qwen, monkeypatch, what):
    """The logits tolerance is tight enough that each of these fails it."""
    wrong = WRONG[what]
    if "patch" in wrong:
        monkeypatch.setattr(ref, *wrong["patch"])
    logits = reference_logits(
        qwen["params"], dict(REF_CONFIG, **wrong.get("config", {})),
        **wrong.get("kwargs", {}))
    gap = np.abs(logits - qwen["out"][0]).max()
    assert gap > 100 * LOGIT_TOL, (what, gap)


def test_bf16_compute_fails_the_tolerance(qwen):
    _, ex, _, feed, _ = build(compute_dtype=jnp.bfloat16)
    logits = ex.run("forward", feed_dict=feed,
                    convert_to_numpy_ret_vals=True)[0]
    gap = np.abs(np.asarray(logits, np.float32) - qwen["ref_logits"]).max()
    assert gap > 50 * LOGIT_TOL, gap


def test_published_config_entry():
    """The defaults of ``Qwen3NextConfig`` are config.json's keys."""
    from hetu_tpu.models import QWEN3_NEXT_CONFIGS
    c = Qwen3NextConfig(**QWEN3_NEXT_CONFIGS["qwen3-next-80b-a3b"])
    assert (c.hidden_size, c.num_layers, c.num_heads, c.num_kv_heads,
            c.head_dim, c.rotary_dim, c.vocab_size) == (
                2048, 48, 16, 2, 256, 64, 151936)
    assert (c.linear_num_key_heads, c.linear_num_value_heads,
            c.linear_key_head_dim, c.linear_value_head_dim,
            c.conv_kernel) == (16, 32, 128, 128, 4)
    assert (c.num_experts, c.moe_k, c.intermediate_size, c.shared_width,
            c.moe_renorm_topk) == (512, 10, 512, 512, True)
    assert c.layer_types.count("full_attention") == 12
    assert c.layer_types[:4] == ("linear_attention",) * 3 + (
        "full_attention",)
