"""Xing4.0 through ``Xing4ForCausalLM`` against the plain reference
(``chipbench/reference/xing4.py``) at a small size on the CPU: seeded weights
with every norm weight and the routers' biases moved off their initial values
and every hyper-connection's maps drawn from the seed, f32 compute, one chip's
share of the experts held, whole layers recomputed.  Both loss terms, the
logits, every parameter's gradient, one AdamW step and the bias's move; the
MTP labels' alignment on a hand-made sequence.

Program and reference both compute in f32 here, in different orders (sorted
grouped products against every-expert-masked sums, flash-style against blocked
attention, slices of lanes against einsums over streams), so they differ by
rounding alone.  The negative controls show how far that is from getting the
architecture wrong."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.models import Xing4Config, Xing4ForCausalLM

from chipbench.builders.xing4 import reference_params, seed_maps
from chipbench.reference import xing4 as ref

B, S = 2, 40
HELD = (4, 8)                # experts 4..11 of 16
WEIGHT = 0.3
LOGIT_TOL = 2e-4
YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 16,
        "type": "yarn"}
#: the published keys the reference reads, at toy sizes; ``n_routed_experts``
#: is the experts HELD, as in the configuration file
REF_CONFIG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=2, first_k_dense_replace=1, intermediate_size=96,
    moe_intermediate_size=32, n_shared_experts=1, num_experts_per_tok=4,
    n_group=1, topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.0,
    kv_lora_rank=24, q_lora_rank=20, qk_nope_head_dim=32,
    qk_rope_head_dim=16, v_head_dim=24, rope_theta=10000, rope_scaling=YARN,
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, num_nextn_predict_layers=1, rms_norm_eps=1e-6)
TOKENS = np.random.default_rng(0).integers(0, 256, (B, S + 1))


def build(name="xing4ref", lr=1e-2, **over):
    ids = ht.placeholder_op(f"{name}_ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op(f"{name}_labels", (B, S), dtype=np.int32)
    model = Xing4ForCausalLM(Xing4Config(
        seq_len=S, n_routed_experts=16, num_key_value_heads=2,
        experts_held=HELD, remat="layer", mtp_loss_weight=WEIGHT,
        router_bias_update_rate=1e-3, **dict(REF_CONFIG, **over)), name=name)
    logits = model(ids)
    loss, terms = model.loss_terms(ids, labels, logits=logits)
    variables = graph_variables([loss], trainable_only=True)
    opt = ht.AdamWOptimizer(learning_rate=lr, weight_decay=0.1)
    ex = ht.Executor(
        {"forward": ([logits, loss, terms["ce"], terms["mtp"], model.mtp_out]
                     + model.hc_maps() + model.moe_loads()),
         "grads": [loss] + ht.gradients(loss, variables),
         "train": [loss, opt.minimize(loss)] + model.router_biases()},
        seed=3)
    r = np.random.default_rng(7)
    for key, value in list(ex.params.items()):
        if key.endswith(("_scale", "_bias")):
            ex.params[key] = value + jnp.asarray(
                r.normal(0, 0.2, value.shape), value.dtype)
    seed_maps(ex, model, 7, beyond_clamp=False)
    feed = {ids: TOKENS[:, :-1], labels: TOKENS[:, 1:]}
    return model, ex, variables, feed


def host(model, ex):
    return {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}


@pytest.fixture(scope="module")
def xing():
    model, ex, variables, feed = build()
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    params = host(model, ex)
    return dict(model=model, ex=ex, variables=variables, feed=feed, out=out,
                params=params, ref=reference_sums(params))


def reference_sums(params, config=REF_CONFIG, **kwargs):
    return jax.device_get(jax.jit(lambda p: ref.loss_sums(
        p, config, TOKENS[:, :-1], TOKENS[:, 1:], held=HELD,
        keep_logits=True, **kwargs))(params))


def test_layers_and_weights(xing):
    model = xing["model"]
    assert [l.dense for l in model.decoder_layers()] == [True, False, False,
                                                         False]
    assert len(model.model.layers) == 3 and model.mtp_layer is not None
    want = (len(ref.WEIGHTS) + len(ref.MTP_WEIGHTS)
            + 4 * len(ref.LAYER_WEIGHTS) + len(ref.DENSE_WEIGHTS)
            + 3 * len(ref.EXPERT_WEIGHTS))
    assert len(xing["params"]) == want
    # the router's bias is no weight: it has no gradient
    assert len(xing["variables"]) == want - 3
    assert len(model.hc_maps()) == 8 and len(model.moe_layers()) == 3


def test_logits_and_both_terms_match_the_reference(xing):
    out, sums = xing["out"], xing["ref"]
    assert np.abs(sums["logits"]).max() > 0.3
    assert np.abs(out[0] - sums["logits"]).max() < LOGIT_TOL
    want = {k: float(v) for k, v in ref.loss_from_sums(sums, WEIGHT).items()}
    assert int(sums["n"]) == B * S and int(sums["n_mtp"]) == B * (S - 1)
    for got, term in zip(out[1:4], ("loss", "ce", "mtp")):
        assert abs(float(got) - want[term]) < 1e-5 * want[term], term
    assert abs(want["loss"] - want["ce"] - WEIGHT * want["mtp"]) < 1e-5
    assert abs(want["ce"] - want["mtp"]) > 1e-3


def test_every_hres_is_the_references_and_doubly_stochastic(xing):
    mine = np.stack(xing["out"][5:13])
    assert mine.shape == (8, B, S, 4, 4)
    assert np.abs(mine - xing["ref"]["hres"]).max() < 2e-5
    assert np.abs(mine.sum(-1) - 1).max() < 1e-4
    assert np.abs(mine.sum(-2) - 1).max() < 1e-4
    assert mine.std(axis=(1, 2)).min() > 0.02     # no map near a constant


def test_load_vector_is_the_references(xing):
    chosen = xing["ref"]["chosen"]
    first, count = HELD
    loads = xing["out"][13:]
    assert len(loads) == 3 and chosen.shape == (3, B * S, 4)
    for load, ch in zip(loads, chosen):
        theirs = np.bincount(ch.reshape(-1), minlength=16)
        np.testing.assert_array_equal(load[0], theirs[first:first + count])
        np.testing.assert_array_equal(load[1], load[0])
        assert theirs.sum() == B * S * 4


def test_every_gradient_leaf_matches_reference(xing):
    ex, variables = xing["ex"], xing["variables"]
    got = ex.run("grads", feed_dict=xing["feed"],
                 convert_to_numpy_ret_vals=True)[1:]
    want = jax.jit(jax.grad(lambda p: ref.pretraining_loss(
        p, REF_CONFIG, TOKENS[:, :-1], TOKENS[:, 1:], WEIGHT, held=HELD)))(
            xing["params"])
    names = {v: k for k, v in reference_params(
        xing["model"], {n: n for n in ex.params}).items()}
    for var, g in zip(variables, got):
        w = np.asarray(want[names[var.name]])
        assert np.abs(w).max() > 0, var.name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, var.name


def test_one_adamw_step_and_the_biases_move():
    """One step of AdamW (lr 0.01, decay 0.1, the repo's defaults otherwise)
    from the reference's gradient moves every weight as the program's step
    does, and each router's bias moves by the rate against the load."""
    model, ex, variables, feed = build(name="xing4step")
    before = host(model, ex)
    grads = jax.jit(jax.grad(lambda p: ref.pretraining_loss(
        p, REF_CONFIG, TOKENS[:, :-1], TOKENS[:, 1:], WEIGHT, held=HELD)))(
            before)
    chosen = reference_sums(before)["chosen"]
    out = ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    after = host(model, ex)
    moved = 0
    for key, w in before.items():
        if key.endswith("router_bias"):
            continue
        g = np.asarray(grads[key], np.float64)
        step = g / (np.abs(g) + 1e-7)        # the first Adam step: sign-like
        want = w - 1e-2 * (step + 0.1 * w)
        # an entry whose gradient is rounding alone may turn either way
        off = np.abs(after[key] - want) > 2e-4 * max(1.0, np.abs(w).max())
        assert off.mean() < 0.01, (key, off.mean())
        assert np.abs(after[key] - w).max() > 1e-3, key
        moved += 1
    assert moved == len(variables)
    for i, (bias, ch) in enumerate(zip(out[2:], chosen)):
        key = f"layers.{i + 1}.router_bias"
        load = np.bincount(ch.reshape(-1), minlength=16)
        want = before[key] + 1e-3 * np.sign(load.mean() - load)
        np.testing.assert_allclose(bias, want, atol=1e-6)
        np.testing.assert_allclose(after[key], want, atol=1e-6)


def test_the_mtp_labels_are_the_tokens_two_on():
    """A hand-made sequence 0, 1, 2, ..: the main labels are ``s + 1``, the
    depth embeds ``s + 1`` and is asked for ``s + 2``; its last position has
    no label; a masked main label masks the position before it in the
    depth."""
    from hetu_tpu.models.xing4 import _labelled, _shifted
    tok = np.arange(10)[None]
    labels = jnp.asarray(tok[:, 1:])
    np.testing.assert_array_equal(_labelled(labels), tok[:, 1:])
    np.testing.assert_array_equal(_shifted(labels)[0],
                                  list(range(2, 10)) + [-1])
    np.testing.assert_array_equal(ref.mtp_labels(labels), _shifted(labels))
    np.testing.assert_array_equal(ref.mtp_labels(labels, ("mtp_shift",)),
                                  labels)
    masked = labels.at[0, 4].set(-1)
    assert int(_labelled(masked)[0, 4]) == 0
    assert int(_shifted(masked)[0, 3]) == -1


@pytest.mark.parametrize("what,kwargs,least", [
    ("two Sinkhorn rounds", dict(without=("sinkhorn_2",)), "hres"),
    ("no mscale in the scores", dict(without=("mscale",)), "logits"),
    ("bf16 operands", dict(matmul_inputs=jnp.bfloat16), "logits"),
    ("MTP labels one shift short", dict(without=("mtp_shift",)), "mtp"),
])
def test_tolerance_refuses(xing, what, kwargs, least):
    """Each omission or lower precision moves the quantity that holds it by
    far more than the tolerance."""
    base, wrong = xing["ref"], reference_sums(xing["params"], **kwargs)
    if least == "mtp":
        gap = abs(float(wrong["mtp"] / wrong["n_mtp"])
                  - float(base["mtp"] / base["n_mtp"]))
        assert gap > 1e-3, what
    else:
        gap = np.abs(wrong[least] - base[least]).max()
        assert gap > 10 * LOGIT_TOL, (what, gap)


def test_without_the_clamp_a_logit_of_a_hundred_is_not_finite():
    model, ex, _, _ = build(name="xing4clamp")
    seed_maps(ex, model, 7, beyond_clamp=True)
    params = host(model, ex)
    held = reference_sums(params)
    assert np.isfinite(held["hres"]).all() and np.isfinite(held["mtp"])
    free = reference_sums(params, without=("clamp",))
    assert not np.isfinite(free["hres"]).all()


def test_the_queries_low_rank_and_the_scale(xing):
    mla = xing["model"].model.layers[0].mixer
    assert mla.qa_proj.shape == (64, 20) and mla.q_proj.shape == (20, 2 * 48)
    assert mla.q_norm is None and mla.gate_proj is None
    m = 0.1 * np.log(64) + 1
    assert abs(mla.scale - 48 ** -0.5 * m * m) < 1e-12
    assert abs(ref.softmax_scale(REF_CONFIG) - mla.scale) < 1e-12
    assert mla.rope_scaling == ("yarn", 64.0, 16, 32.0, 1.0, 1.0)
