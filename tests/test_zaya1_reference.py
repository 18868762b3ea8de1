"""ZAYA1 through ``Zaya1ForCausalLM`` against the plain reference
(``chipbench/reference/zaya1.py``) at a small size on the CPU: seeded weights
with every norm weight moved off its initial value and everything that starts
at an identity (the taps, the temperature, ``gamma``, the merges, the
selection bias) drawn from the seed, f32 compute, one chip's share of the
experts held.  The loss, the logits, every layer's router state and choice,
every parameter's gradient, one AdamW step and the bias's move.

Program and reference both compute in f32 here, in different orders (sorted
grouped products against every-expert-masked sums, slices of lanes against
einsums over heads, the shift of a product against the product of a shift), so
they differ by rounding alone.  The negative controls show how far that is
from getting the architecture wrong."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.models import Zaya1Config, Zaya1ForCausalLM

from chipbench.builders.zaya1 import reference_params, seed_parts
from chipbench.reference import zaya1 as ref

B, S = 2, 40
HELD = (4, 4)                # experts 4..7 of 8
LOGIT_TOL = 2e-4
ROPE = {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                   "rope_type": "default"}}
#: the published keys the reference reads, at toy sizes
REF_CONFIG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=3,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, cca_time0=2,
    cca_time1=2, partial_rotary_factor=0.5, rope_parameters=ROPE,
    router_hidden_size=16, num_experts_per_tok=1, moe_intermediate_size=32,
    rms_norm_eps=1e-5)
TOKENS = np.random.default_rng(0).integers(0, 256, (B, S + 1))


def build(name="zaya1ref", lr=1e-2, held=HELD, **over):
    ids = ht.placeholder_op(f"{name}_ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op(f"{name}_labels", (B, S), dtype=np.int32)
    model = Zaya1ForCausalLM(Zaya1Config(
        seq_len=S, num_experts=8, experts_held=held,
        router_bias_update_rate=1e-3, **dict(REF_CONFIG, **over)), name=name)
    logits = model(ids)
    loss, _ = model.loss_terms(ids, labels, logits=logits)
    variables = graph_variables([loss], trainable_only=True)
    opt = ht.AdamWOptimizer(learning_rate=lr, weight_decay=0.1)
    ex = ht.Executor(
        {"forward": ([logits, loss] + model.router_states()
                     + [m.chosen() for m in model.moe_layers()]
                     + model.moe_loads()),
         "grads": [loss] + ht.gradients(loss, variables),
         "train": [loss, opt.minimize(loss)] + model.router_biases()},
        seed=3)
    r = np.random.default_rng(7)
    for key, value in list(ex.params.items()):
        if key.endswith(("_scale", "_bias")):
            ex.params[key] = value + jnp.asarray(
                r.normal(0, 0.2, value.shape), value.dtype)
    seed_parts(ex, model, 7, skip_bias=0.08)   # a toy router: 9 outputs
    feed = {ids: TOKENS[:, :-1], labels: TOKENS[:, 1:]}
    return model, ex, variables, feed


def host(model, ex):
    return {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}


def reference_sums(params, held=HELD, **kwargs):
    return jax.device_get(jax.jit(lambda p: ref.loss_sums(
        p, REF_CONFIG, TOKENS[:, :-1], TOKENS[:, 1:], held=held,
        keep_logits=True, keep=0, **kwargs))(params))


@pytest.fixture(scope="module")
def zaya():
    model, ex, variables, feed = build()
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    params = host(model, ex)
    return dict(model=model, ex=ex, variables=variables, feed=feed, out=out,
                params=params, ref=reference_sums(params))


def test_layers_and_weights(zaya):
    model = zaya["model"]
    assert len(model.model.layers) == 3 and len(model.moe_layers()) == 3
    # the first layer's gamma is in no graph: no state comes in
    want = len(ref.WEIGHTS) + 3 * len(ref.LAYER_WEIGHTS) - 1
    assert len(zaya["params"]) == want
    # the routers' biases are no weights: they have no gradient
    assert len(zaya["variables"]) == want - 3
    assert model.lm_head is None                       # tied


def test_logits_and_loss_match_the_reference(zaya):
    out, sums = zaya["out"], zaya["ref"]
    assert np.abs(sums["logits"]).max() > 0.3
    assert np.abs(out[0] - sums["logits"]).max() < LOGIT_TOL
    want = float(ref.loss_from_sums(sums)["loss"])
    assert int(sums["n"]) == B * S
    assert abs(float(out[1]) - want) < 1e-5 * want


def test_every_router_state_and_choice_is_the_references(zaya):
    out, sums = zaya["out"], zaya["ref"]
    states, chosen, loads = out[2:5], out[5:8], out[8:11]
    assert states[2].shape == (B, S, 16)
    np.testing.assert_allclose(states[2], sums["state"], atol=2e-5)
    # the state grows down the depth: it is a sum
    rms = [float(np.sqrt(np.mean(np.square(s)))) for s in states]
    assert rms[2] > rms[0]
    mine = np.stack([c.reshape(-1) for c in chosen])
    np.testing.assert_array_equal(mine, sums["chosen"])
    first, count = HELD
    skipped = 0
    for load, ch, state in zip(loads, sums["chosen"], states):
        theirs = np.bincount(ch, minlength=9)
        assert load.shape == (5, count)
        np.testing.assert_array_equal(load[0], theirs[first:first + count])
        np.testing.assert_array_equal(load[1], load[0])
        assert load[2, 0] == theirs[:first].sum()       # held elsewhere
        assert load[4, 0] == theirs[8]                  # chose no expert
        np.testing.assert_allclose(
            load[4, 1], np.sqrt(np.mean(np.square(state))), rtol=1e-5)
        skipped += theirs[8]
    assert skipped == int(sums["skipped"])
    assert 0.05 < skipped / (3 * B * S) < 0.5


def test_every_gradient_leaf_matches_reference(zaya):
    ex, variables = zaya["ex"], zaya["variables"]
    got = ex.run("grads", feed_dict=zaya["feed"],
                 convert_to_numpy_ret_vals=True)[1:]
    want = jax.jit(jax.grad(lambda p: ref.pretraining_loss(
        p, REF_CONFIG, TOKENS[:, :-1], TOKENS[:, 1:], held=HELD)))(
            zaya["params"])
    names = {v: k for k, v in reference_params(
        zaya["model"], {n: n for n in ex.params}).items()}
    for var, g in zip(variables, got):
        w = np.asarray(want[names[var.name]])
        assert np.abs(w).max() > 0, var.name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, var.name


def test_one_adamw_step_and_the_biases_move():
    """One step of AdamW (lr 0.01, decay 0.1, the repo's defaults otherwise)
    from the reference's gradient moves every weight as the program's step
    does, and each router's bias moves by the rate against the load over all
    nine choices."""
    model, ex, variables, feed = build(name="zaya1step")
    before = host(model, ex)
    grads = jax.jit(jax.grad(lambda p: ref.pretraining_loss(
        p, REF_CONFIG, TOKENS[:, :-1], TOKENS[:, 1:], held=HELD)))(before)
    chosen = reference_sums(before)["chosen"]
    out = ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    after = host(model, ex)
    moved = 0
    for key, w in before.items():
        if key.endswith("router.bias"):
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
        key = f"layers.{i}.router.bias"
        load = np.bincount(ch, minlength=9)
        want = before[key] + 1e-3 * np.sign(load.mean() - load)
        np.testing.assert_allclose(bias, want, atol=1e-6)
        np.testing.assert_allclose(after[key], want, atol=1e-6)


@pytest.mark.parametrize("what,kwargs,least", [
    ("bf16 operands", dict(matmul_inputs=jnp.bfloat16), "logits"),
    ("the depthwise taps swapped in time", dict(without=("taps_in_time",)),
     "qk"),
    ("no head-mixing taps", dict(without=("head_mix",)), "qk"),
    ("no q-k mean", dict(without=("qk_mean",)), "qk"),
    ("W_v2 fed the current token", dict(without=("value_shift",)),
     "attention"),
    ("no temperature", dict(without=("temperature",)), "qk"),
    ("rotary over the whole head", dict(without=("rotary_all",)),
     "attention"),
    ("no carried router state", dict(without=("eda",)), "state"),
    ("the skip choice sent to an expert", dict(without=("skip_choice",)),
     "logits"),
    ("no scale on the residual", dict(without=("residual_scale",)),
     "logits"),
])
def test_tolerance_refuses(zaya, what, kwargs, least):
    """Each omission or lower precision moves the quantity that holds it by
    far more than the tolerance."""
    base, wrong = zaya["ref"], reference_sums(zaya["params"], **kwargs)
    gap = np.abs(wrong[least] - base[least]).max()
    # a skipped token's expert output, times p and a merge scale, is small
    # beside the stream at this size: three times the tolerance, not ten
    assert gap > (3 if "skip" in what else 10) * LOGIT_TOL, (what, gap)


def test_a_recomputed_layer_keeps_the_pair_at_its_boundary(zaya):
    """``remat="layer"``: a layer is one ``ht.remat()`` group whose boundary
    is the pair ``(x, r)``; loss and gradients are the un-recomputed
    model's."""
    model, ex, variables, feed = build(name="zaya1remat", remat="layer")
    groups = {n.remat_scope for n in (model.model.states[1],
                                      model.model.layers[1].attn.out)}
    assert len(groups) == 1 and None not in groups
    mine = reference_params(model, {n: n for n in ex.params})
    # the first model's weights as they are NOW: a run of its "grads" moves
    # the routers' biases, as every training program does
    theirs = host(zaya["model"], zaya["ex"])
    for key, name in mine.items():
        ex.params[name] = jnp.asarray(theirs[key])
    got = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    want = zaya["ex"].run("grads", feed_dict=zaya["feed"],
                          convert_to_numpy_ret_vals=True)
    assert abs(float(got[0]) - float(want[0])) < 1e-6
    for var, g, w in zip(variables, got[1:], want[1:]):
        assert np.abs(g - w).max() < 1e-5 * np.abs(w).max() + 1e-9, var.name


def test_a_staged_model_is_refused():
    with pytest.raises(NotImplementedError,
                       match="the router state is not sent between stages"):
        Zaya1ForCausalLM(Zaya1Config(seq_len=S, **REF_CONFIG),
                         name="zaya1staged", pipeline_stages=2)
