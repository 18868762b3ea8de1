"""Laguna through ``LagunaForCausalLM`` against the plain reference
(``chipbench/reference/laguna.py``) at a small size on the CPU: seeded weights
with every norm weight moved off its initial value, f32 compute, one chip's
share of the experts held; with and without recomputation, and with a window
smaller than, equal to and larger than the sequence.  And the shares add up:
the eight (here 4) shares' expert outputs, the shared expert counted once, sum
to the uncut layer.

Program and reference both compute in f32 here, in different orders (sorted
grouped products against every-expert-masked sums, one softmax against blocked
attention), so they differ by rounding alone.  The negative controls show how
far that is from getting the architecture wrong."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.models import LagunaConfig, LagunaForCausalLM

from chipbench.builders.laguna import reference_params
from chipbench.reference import laguna as ref

B, S = 2, 48
HELD = (4, 4)                # experts 4..7 of 16
LOGIT_TOL = 2e-4
ROPE = {
    "full_attention": {
        "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
        "original_max_position_embeddings": 32, "beta_slow": 1,
        "beta_fast": 8, "attention_factor": 1.4158883083359672,
        "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}
REF_CONFIG = dict(
    vocab_size=256, hidden_size=48, intermediate_size=96, num_hidden_layers=3,
    num_attention_heads=6, num_key_value_heads=2, head_dim=16,
    rms_norm_eps=1e-6, num_experts_per_tok=4, moe_intermediate_size=24,
    shared_expert_intermediate_size=24, gating=True, sliding_window=16,
    rope_parameters=ROPE,
    layer_types=["full_attention", "sliding_attention", "full_attention"],
    mlp_layer_types=["dense", "sparse", "sparse"],
    moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[6, 8, 6])
TOKENS = np.random.default_rng(0).integers(0, 256, (B, S + 1))
#: (what is recomputed, the window): below, at and above the sequence (the
#: published period of five layers, with the window layers alone recomputed
#: too, runs through the cell's builder in ``test_laguna_cell.py``)
CASES = [(None, 16), ("layer", 16), (None, S), ("layer", 64)]


def build(name, remat=None, **over):
    ids = ht.placeholder_op(f"{name}_ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op(f"{name}_labels", (B, S), dtype=np.int32)
    model = LagunaForCausalLM(LagunaConfig(
        seq_len=S, num_experts=16, experts_held=HELD, remat=remat,
        **dict(REF_CONFIG, **over)), name=name)
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


def reference_logits(params, config, **kwargs):
    return np.asarray(jax.jit(lambda p: ref.forward(
        p, config, TOKENS[:, :-1], held=HELD, **kwargs)[0])(params))


@pytest.fixture(scope="module", params=CASES,
                ids=[f"remat_{r}-window_{w}" for r, w in CASES])
def laguna(request):
    remat, window = request.param
    config = dict(REF_CONFIG, sliding_window=window)
    model, ex, variables, feed = build(f"lagref_{remat}_{window}", remat,
                                       sliding_window=window)
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    params = {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}
    return dict(model=model, ex=ex, variables=variables, feed=feed, out=out,
                params=params, config=config,
                ref_logits=reference_logits(params, config))


def test_layer_kinds_and_weights(laguna):
    layers = laguna["model"].model.layers
    assert [l.kind for l in layers] == REF_CONFIG["layer_types"]
    assert [l.dense for l in layers] == [True, False, False]
    assert [l.attn.num_heads for l in layers] == [6, 8, 6]
    assert [l.attn.window for l in layers] == [
        None, laguna["config"]["sliding_window"], None]
    assert [l.attn.rotary_dim for l in layers] == [8, None, 8]
    want = (len(ref.WEIGHTS) + 3 * len(ref.LAYER_WEIGHTS)
            + len(ref.DENSE_WEIGHTS) + 2 * len(ref.EXPERT_WEIGHTS))
    assert len(laguna["params"]) == want == len(laguna["variables"])


def test_logits_and_loss_match_reference(laguna):
    assert np.abs(laguna["ref_logits"]).max() > 0.3
    assert np.abs(laguna["out"][0] - laguna["ref_logits"]).max() < LOGIT_TOL
    want = float(jax.jit(lambda p: ref.pretraining_loss(
        p, laguna["config"], TOKENS[:, :-1], TOKENS[:, 1:], held=HELD))(
            laguna["params"]))
    assert abs(float(laguna["out"][1]) - want) < 1e-5 * abs(want)


def test_every_gradient_leaf_matches_reference(laguna):
    ex, variables = laguna["ex"], laguna["variables"]
    got = ex.run("grads", feed_dict=laguna["feed"],
                 convert_to_numpy_ret_vals=True)[1:]
    want = jax.jit(jax.grad(lambda p: ref.pretraining_loss(
        p, laguna["config"], TOKENS[:, :-1], TOKENS[:, 1:], held=HELD)))(
            laguna["params"])
    names = {v: k for k, v in reference_params(
        laguna["model"], {n: n for n in ex.params}).items()}
    for var, g in zip(variables, got):
        w = np.asarray(want[names[var.name]])
        assert np.abs(w).max() > 0, var.name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, var.name


def test_load_vector_is_the_references(laguna):
    chosen = np.asarray(jax.jit(lambda p: ref.loss_sums(
        p, laguna["config"], TOKENS[:, :-1], TOKENS[:, 1:],
        held=HELD)["chosen"])(laguna["params"]))
    first, count = HELD
    assert len(laguna["out"][2:]) == 2
    for load, ch in zip(laguna["out"][2:], chosen):
        theirs = np.bincount(ch.reshape(-1), minlength=16)
        np.testing.assert_array_equal(load[0], theirs[first:first + count])
        np.testing.assert_array_equal(load[1], load[0])
        assert theirs.sum() == B * S * 4


@pytest.fixture(scope="module")
def base():
    """The cell's own kind of model: a window below the sequence."""
    model, ex, _, feed = build("lagref_base")
    params = {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}
    return params, reference_logits(params, REF_CONFIG)


@pytest.mark.parametrize("what", sorted(ref.CONTROLS))
def test_tolerance_refuses(base, what):
    """Each changed piece the issue lists moves some logit by far more than
    the tolerance."""
    params, logits = base
    wrong = reference_logits(params, REF_CONFIG, without=(what,))
    assert np.abs(wrong - logits).max() > 10 * LOGIT_TOL, what


def test_bf16_operands_are_refused(base):
    params, logits = base
    wrong = reference_logits(params, REF_CONFIG, matmul_inputs=jnp.bfloat16)
    assert np.abs(wrong - logits).max() > 10 * LOGIT_TOL


def test_the_shares_add_up():
    """One expert block of 16 experts cut into EIGHT shares of 2, as the
    job's eight chips cut its 256: the shares' routed outputs, the shared
    expert counted once, sum to the uncut reference's layer; and the
    program's held layer is its share."""
    from hetu_tpu.layers.moe import MoELayer
    c = dict(REF_CONFIG)
    r = np.random.default_rng(5)
    H, F, E = 64, 32, 16
    w = {"router": r.normal(0, 0.5, (H, E)),
         "w_gate": r.normal(0, 0.1, (E, H, F)), "w_up": r.normal(
             0, 0.1, (E, H, F)), "w_down": r.normal(0, 0.1, (E, F, H)),
         "shared_gate": r.normal(0, 0.1, (H, F)), "shared_up": r.normal(
             0, 0.1, (H, F)), "shared_down": r.normal(0, 0.1, (F, H))}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    h = jnp.asarray(r.normal(0, 1, (48, H)), jnp.float32)
    mm = lambda a, b: a @ b
    with jax.default_matmul_precision("highest"):
        whole, chosen, _ = ref.expert_block(h, w, c, mm)
        total = 0
        for s in range(8):
            held = (2 * s, 2)
            part = dict(w, **{k: w[k][2 * s:2 * s + 2]
                              for k in ("w_gate", "w_up", "w_down")})
            y, ch, _ = ref.expert_block(h, part, c, mm, held, shared=(s == 0))
            np.testing.assert_array_equal(np.asarray(ch), np.asarray(chosen))
            total = total + y
    assert np.abs(np.asarray(total - whole)).max() < 1e-5
    # the program's layer holding share 1 against the reference's share 1
    layer = MoELayer(H, F, num_experts=E, k=4, capacity_factor=None,
                     expert_act="swiglu", held=(4, 4), shared_width=F,
                     shared_gate=False, router_score="sigmoid",
                     router_scale=2.5, renorm_topk=True, name="lagshare1")
    x = ht.placeholder_op("lagshare1_x", (1, 48, H))
    ex = ht.Executor([layer(x)], seed=0)
    part = dict(w, **{k: w[k][4:8] for k in ("w_gate", "w_up", "w_down")})
    for var, key in ((layer.gate.wg, "router"), (layer.w1, "w_gate"),
                     (layer.w3, "w_up"), (layer.w2, "w_down"),
                     (layer.shared[0], "shared_gate"),
                     (layer.shared[1], "shared_up"),
                     (layer.shared[2], "shared_down")):
        ex.params[var.name] = part[key]
    (got,) = ex.run(feed_dict={x: np.asarray(h)[None]},
                    convert_to_numpy_ret_vals=True)
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref.expert_block(h, part, c, mm, (4, 4))
    assert np.abs(got[0] - np.asarray(want)).max() < 1e-5


def attention_layer(name, heads, **kw):
    from hetu_tpu.layers.attention import MultiHeadAttention
    x = ht.placeholder_op(f"{name}_x", (B, S, 48))
    layer = MultiHeadAttention(48, heads, sequence_length=S, causal_mask=True,
                               num_kv_heads=2, head_dim=16, bias=False,
                               name=name, **kw)
    ex = ht.Executor([layer(x, x, x)], seed=1)
    u = np.random.default_rng(3).normal(0, 1, (B, S, 48)).astype(np.float32)
    (got,) = ex.run(feed_dict={x: u}, convert_to_numpy_ret_vals=True)
    return layer, ex, u, got


@pytest.mark.parametrize("heads,kind", [(6, "full_attention"),
                                        (8, "sliding_attention")])
def test_a_gate_a_head_against_the_reference(heads, kind):
    """``MultiHeadAttention(output_gate="head")`` with groups of 3 and of 4,
    YaRN on half a head and plain rotary on a whole one, against the
    reference's attention sublayer; without its gate the reference is far
    off."""
    c = LagunaConfig(seq_len=S, **REF_CONFIG)
    window = 16 if kind == "sliding_attention" else None
    layer, ex, u, got = attention_layer(
        f"laggate{heads}", heads, output_gate="head", window=window,
        **c.rope[kind])
    assert layer.gate_proj.weight.shape == (48, heads)
    assert layer.q_proj.weight.shape == (48, heads * 16)
    w = {k: np.asarray(ex.params[v.weight.name]) for k, v in (
        ("q", layer.q_proj), ("k", layer.k_proj), ("v", layer.v_proj),
        ("o", layer.out_proj), ("gate", layer.gate_proj))}
    config = dict(REF_CONFIG, layer_types=[kind],
                  num_attention_heads_per_layer=[heads])
    mm = lambda a, b: a @ b
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.attention(jnp.asarray(u), w, config, 0, mm))
        bare = np.asarray(ref.attention(jnp.asarray(u), w, config, 0, mm,
                                        without=("head_gate",)))
    assert np.abs(got - want).max() < 1e-5
    assert np.abs(bare - want).max() > 0.05


def test_the_elementwise_gate_is_what_it_was():
    """``output_gate=True``: the query projection doubled, a head's query
    followed by its gate, the context times the sigmoid of the gate element by
    element; no gate projection of its own."""
    layer, ex, u, got = attention_layer("laggate_elem", 6, output_gate=True)
    assert layer.gate_proj is None
    assert layer.q_proj.weight.shape == (48, 2 * 6 * 16)
    wq, wk, wv, wo = (np.asarray(ex.params[p.weight.name], np.float64)
                      for p in (layer.q_proj, layer.k_proj, layer.v_proj,
                                layer.out_proj))
    qg = (u @ wq).reshape(B, S, 6, 32)
    q, gate = qg[..., :16], qg[..., 16:].reshape(B, S, 96)
    k = np.repeat((u @ wk).reshape(B, S, 2, 16), 3, axis=2)
    v = np.repeat((u @ wv).reshape(B, S, 2, 16), 3, axis=2)
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, 96)
    want = (o / (1.0 + np.exp(-gate))) @ wo
    assert np.abs(got - want).max() < 1e-5
