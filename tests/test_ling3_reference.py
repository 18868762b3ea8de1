"""Ling-3.0 through ``Ling3ForCausalLM`` against the plain reference
(``chipbench/reference/ling3.py``) at a small size on the CPU: seeded weights
with every norm weight, ``A_log``, ``dt_bias`` and the router's bias moved off
its initial value, f32 compute, one chip's share of the experts held, whole
layers recomputed.  And the shares add up: the 64 (here 4) shares' expert
outputs, the shared expert counted once, sum to the uncut layer.

Program and reference both compute in f32 here, in different orders (the
delta rule in chunks against token by token, sorted grouped products against
every-expert-masked sums, flash-style against blocked attention), so they
differ by rounding alone.  The negative controls show how far that is from
getting the architecture wrong."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.models import Ling3Config, Ling3ForCausalLM

from chipbench.builders.ling3 import reference_params
from chipbench.reference import ling3 as ref

B, S = 2, 40
HELD = (4, 8)                # experts 4..11 of 16
LOGIT_TOL = 2e-4
REF_CONFIG = dict(
    vocab_size=256, hidden_size=64, num_hidden_layers=7,
    num_attention_heads=2, head_dim=32, layer_group_size=6,
    first_k_dense_replace=1, intermediate_size=96, num_experts_per_tok=4,
    moe_intermediate_size=32, moe_shared_expert_intermediate_size=32,
    n_group=4, topk_group=2, norm_topk_prob=True, routed_scaling_factor=2.5,
    kv_lora_rank=24, qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=24,
    rope_theta=6000000.0, use_qk_norm=True, short_conv_kernel_size=4,
    kda_lower_bound=-5, rms_norm_eps=1e-6)
TOKENS = np.random.default_rng(0).integers(0, 256, (B, S + 1))


def build(name="ling3ref", **over):
    ids = ht.placeholder_op(f"{name}_ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op(f"{name}_labels", (B, S), dtype=np.int32)
    model = Ling3ForCausalLM(Ling3Config(
        seq_len=S, num_experts=16, experts_held=HELD, remat="layer",
        **dict(REF_CONFIG, **over)), name=name)
    loss, terms = model.loss_terms(ids, labels)
    variables = graph_variables([loss], trainable_only=True)
    ex = ht.Executor(
        {"forward": [model(ids), loss] + model.moe_loads(),
         "grads": [loss] + ht.gradients(loss, variables)}, seed=3)
    r = np.random.default_rng(7)
    for key, value in list(ex.params.items()):
        if key.endswith(("_scale", "_a_log", "_dt_bias", "_bias")):
            ex.params[key] = value + jnp.asarray(
                r.normal(0, 0.2, value.shape), value.dtype)
    feed = {ids: TOKENS[:, :-1], labels: TOKENS[:, 1:]}
    return model, ex, variables, feed


@pytest.fixture(scope="module")
def ling():
    model, ex, variables, feed = build()
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    params = {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}
    return dict(model=model, ex=ex, variables=variables, feed=feed, out=out,
                params=params, ref_logits=reference_logits(params))


def reference_logits(params, config=REF_CONFIG, **kwargs):
    return np.asarray(jax.jit(lambda p: ref.forward(
        p, config, TOKENS[:, :-1], held=HELD, **kwargs)[0])(params))


def test_layer_kinds_and_weights(ling):
    assert ref.layer_kinds(REF_CONFIG) == ["kda"] * 5 + ["attention", "kda"]
    kinds = [l.kind for l in ling["model"].model.layers]
    assert kinds == ref.layer_kinds(REF_CONFIG)
    assert [l.dense for l in ling["model"].model.layers] == [True] + [False] * 6
    per = {"kda": len(ref.KDA_WEIGHTS), "attention": len(
        ref.ATTENTION_WEIGHTS)}
    want = (len(ref.WEIGHTS) + 7 * len(ref.LAYER_WEIGHTS)
            + sum(per[k] for k in kinds) + len(ref.DENSE_WEIGHTS)
            + 6 * len(ref.EXPERT_WEIGHTS))
    assert len(ling["params"]) == want
    # the router's bias is no weight: it has no gradient
    assert len(ling["variables"]) == want - 6


def test_logits_match_reference(ling):
    assert np.abs(ling["ref_logits"]).max() > 0.3
    assert np.abs(ling["out"][0] - ling["ref_logits"]).max() < LOGIT_TOL


def test_loss_matches_reference(ling):
    want = float(jax.jit(lambda p: ref.pretraining_loss(
        p, REF_CONFIG, TOKENS[:, :-1], TOKENS[:, 1:], held=HELD))(
            ling["params"]))
    assert abs(float(ling["out"][1]) - want) < 1e-5 * abs(want)


def test_load_vector_is_the_references(ling):
    chosen = np.asarray(jax.jit(lambda p: ref.loss_sums(
        p, REF_CONFIG, TOKENS[:, :-1], TOKENS[:, 1:], held=HELD)["chosen"])(
            ling["params"]))
    first, count = HELD
    assert len(ling["out"][2:]) == 6
    for load, ch in zip(ling["out"][2:], chosen):
        theirs = np.bincount(ch.reshape(-1), minlength=16)
        np.testing.assert_array_equal(load[0], theirs[first:first + count])
        np.testing.assert_array_equal(load[1], load[0])
        assert load[2, 0] == theirs.sum() - load[0].sum()
        assert theirs.sum() == B * S * 4
        # only experts of the two best of four groups
        assert all(len(set(row // 4)) <= 2 for row in ch)


def test_every_gradient_leaf_matches_reference(ling):
    ex, variables = ling["ex"], ling["variables"]
    got = ex.run("grads", feed_dict=ling["feed"],
                 convert_to_numpy_ret_vals=True)[1:]
    want = jax.jit(jax.grad(lambda p: ref.pretraining_loss(
        p, REF_CONFIG, TOKENS[:, :-1], TOKENS[:, 1:], held=HELD)))(
            ling["params"])
    names = {v: k for k, v in reference_params(
        ling["model"], {n: n for n in ex.params}).items()}
    for var, g in zip(variables, got):
        w = np.asarray(want[names[var.name]])
        assert np.abs(w).max() > 0, var.name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, var.name


@pytest.mark.parametrize("what,kwargs", [
    ("no head gate", dict(without=("head_gate",))),
    ("a scalar decay", dict(without=("vector_decay",))),
    ("ungrouped routing", dict(without=("groups",))),
    ("a bf16 state", dict(state_dtype=jnp.bfloat16)),
    ("bf16 operands", dict(matmul_inputs=jnp.bfloat16)),
])
def test_tolerance_refuses(ling, what, kwargs):
    """Each omission or lower precision the issue lists moves some logit by
    far more than the tolerance."""
    wrong = reference_logits(ling["params"], **kwargs)
    assert np.abs(wrong - ling["ref_logits"]).max() > 10 * LOGIT_TOL, what


def test_the_clamp_is_refused_not_ignored():
    with pytest.raises(NotImplementedError, match="clamp"):
        Ling3Config(num_hidden_layers=7, expert_swiglu_limit_list=[0] * 6 + [4])
    Ling3Config(num_hidden_layers=7,
                expert_swiglu_limit_list=[0] * 7 + [4] * 35)
    with pytest.raises(AssertionError, match="clamp"):
        ref.forward({}, dict(REF_CONFIG, share_expert_swiglu_limit_list=[5]),
                    TOKENS[:, :-1])


def test_the_shares_add_up():
    """One expert block of 16 experts cut into 4 shares of 4: the shares'
    routed outputs, the shared expert counted once, sum to the uncut
    reference's layer; and the program's held layer is its share."""
    from hetu_tpu.layers.moe import MoELayer
    c = dict(REF_CONFIG)
    r = np.random.default_rng(5)
    H, F, E = 64, 32, 16
    w = {"router": r.normal(0, 0.5, (H, E)), "router_bias": r.normal(
            0, 0.1, (E,)),
         "w_gate": r.normal(0, 0.1, (E, H, F)), "w_up": r.normal(
             0, 0.1, (E, H, F)), "w_down": r.normal(0, 0.1, (E, F, H)),
         "shared_gate": r.normal(0, 0.1, (H, F)), "shared_up": r.normal(
             0, 0.1, (H, F)), "shared_down": r.normal(0, 0.1, (F, H))}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    h = jnp.asarray(r.normal(0, 1, (48, H)), jnp.float32)
    mm = lambda a, b: a @ b
    with jax.default_matmul_precision("highest"):
        whole, chosen = ref.expert_block(h, w, c, mm)
        total = 0
        for s in range(4):
            held = (4 * s, 4)
            part = dict(w, **{k: w[k][4 * s:4 * s + 4]
                              for k in ("w_gate", "w_up", "w_down")})
            y, ch = ref.expert_block(h, part, c, mm, held, shared=(s == 0))
            np.testing.assert_array_equal(np.asarray(ch), np.asarray(chosen))
            total = total + y
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    # the program's layer holding share 1 against the reference's share 1
    layer = MoELayer(H, F, num_experts=E, k=4, capacity_factor=None,
                     expert_act="swiglu", held=(4, 4), shared_width=F,
                     shared_gate=False, router_score="sigmoid",
                     router_scale=2.5, router_groups=(4, 2), name="share1")
    x = ht.placeholder_op("share1_x", (1, 48, H))
    ex = ht.Executor([layer(x)], seed=0)
    part = dict(w, **{k: w[k][4:8] for k in ("w_gate", "w_up", "w_down")})
    for var, key in ((layer.gate.wg, "router"), (layer.gate.bias,
                                                  "router_bias"),
                     (layer.w1, "w_gate"), (layer.w3, "w_up"),
                     (layer.w2, "w_down"), (layer.shared[0], "shared_gate"),
                     (layer.shared[1], "shared_up"),
                     (layer.shared[2], "shared_down")):
        ex.params[var.name] = part[key]
    (got,) = ex.run(feed_dict={x: np.asarray(h)[None]},
                    convert_to_numpy_ret_vals=True)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.expert_block(h, part, c, mm, (4, 4))
    assert np.abs(got[0] - np.asarray(want)).max() < 1e-5


def fake_checkpoint(params, config=REF_CONFIG, experts=16):
    """A state_dict under the import's assumed key names (``hf_import.py
    load_hf_ling3_weights``) from the reference's weights, experts the model
    does not hold filled with noise."""
    r = np.random.default_rng(11)
    H, d = config["num_attention_heads"], config["head_dim"]
    hd = H * d
    sd = {"model.word_embeddings.weight": params["embed"],
          "model.norm.weight": params["norm"],
          "lm_head.weight": params["lm_head"].T}
    for i, kind in enumerate(ref.layer_kinds(config)):
        w = {k.split(".", 2)[2]: v for k, v in params.items()
             if k.startswith(f"layers.{i}.")}
        hf, a = f"model.layers.{i}.", f"model.layers.{i}.attention."
        sd[hf + "input_layernorm.weight"] = w["input_norm"]
        sd[hf + "post_attention_layernorm.weight"] = w["post_norm"]
        if kind == "attention":
            for theirs, ours in (("q_proj", "q"), ("kv_a_proj_with_mqa",
                                                   "kva"),
                                 ("kv_b_proj", "kvb"), ("g_proj", "gate"),
                                 ("dense", "o")):
                sd[a + theirs + ".weight"] = w[ours].T
            sd[a + "kv_a_layernorm.weight"] = w["kv_norm"]
            sd[a + "query_layernorm.weight"] = w["q_norm"]
            sd[a + "key_layernorm.weight"] = w["k_norm"]
        else:
            for j, n in enumerate("qkvfg"):
                sd[a + f"{n}_proj.weight"] = w["kda_in"][
                    :, j * hd:(j + 1) * hd].T
            for j, n in enumerate("qkv"):
                sd[a + f"{n}_conv1d.weight"] = w["conv"][
                    :, j * hd:(j + 1) * hd].T[:, None, :]
            sd[a + "b_proj.weight"] = w["kda_beta"].T
            sd[a + "A_log"], sd[a + "dt_bias"] = w["a_log"], w["dt_bias"]
            sd[a + "o_norm.weight"] = w["kda_norm"]
            sd[a + "o_proj.weight"] = w["kda_out"].T
        if "mlp_gate" in w:
            for theirs, ours in (("gate_proj", "mlp_gate"),
                                 ("up_proj", "mlp_up"),
                                 ("down_proj", "mlp_down")):
                sd[hf + f"mlp.{theirs}.weight"] = w[ours].T
            continue
        sd[hf + "mlp.gate.weight"] = w["router"].T
        sd[hf + "mlp.gate.expert_bias"] = w["router_bias"]
        for theirs, ours in (("gate_proj", "w_gate"), ("up_proj", "w_up"),
                             ("down_proj", "w_down")):
            for j in range(experts):
                held = HELD[0] <= j < HELD[0] + HELD[1]
                sd[hf + f"mlp.experts.{j}.{theirs}.weight"] = (
                    w[ours][j - HELD[0]].T if held
                    else r.normal(0, 1, w[ours][0].T.shape).astype(
                        np.float32))
            sd[hf + f"mlp.shared_experts.{theirs}.weight"] = w[
                "shared_" + theirs.split("_")[0]].T
    return sd


def test_a_checkpoint_comes_over_and_its_tower_is_refused(ling):
    """The family's key map: a state_dict laid out under the assumed names
    gives the logits of the weights it was made from; vision and
    multi-token-prediction weights are refused by name."""
    from hetu_tpu.models import load_hf_ling3_weights
    model, ex, _, feed = build(name="ling3imp")
    sd = fake_checkpoint(ling["params"])
    load_hf_ling3_weights(ex, model, sd, name="ling3imp")
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert np.abs(out[0] - ling["ref_logits"]).max() < LOGIT_TOL
    for extra in ("model.visual.patch_embed.proj.weight",
                  "model.mtp.layers.0.eh_proj.weight"):
        with pytest.raises(ValueError, match="not modelled"):
            load_hf_ling3_weights(ex, model, dict(sd, **{extra: np.zeros(2)}),
                                  name="ling3imp")
