"""OLMoE's block through ``LlamaForCausalLM`` against the plain reference
(``chipbench/reference/olmoe.py``) at a small size on the CPU, seeded
weights, f32 compute; the dropless MoE path against a dense computation; the
new cell's rehearsal.

Tolerances.  Program and reference both compute in f32 here, in different
orders (sorted grouped products against every-expert-masked sums, fused
against plain norms), so they differ by rounding alone: logits of size ~1
agree to 2e-5, loss terms to 1e-5 relative, gradients to 1e-5 of the leaf's
largest entry.  The negative controls below show the same tolerances refuse
bf16 compute (logit gaps of 1e-2) and a renormalised top-k (gaps of 1e-1).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.layers.moe import MoELayer, record_moe_load
from hetu_tpu.models import LlamaConfig, LlamaForCausalLM, LLAMA_CONFIGS
from hetu_tpu.ops import moe as moe_ops

from chipbench.builders.llama import reference_params
from chipbench.reference import olmoe as ref

B, S = 2, 32
REF_CONFIG = dict(vocab_size=256, hidden_size=64, num_hidden_layers=2,
                  num_attention_heads=4, intermediate_size=32,
                  num_experts=16, num_experts_per_tok=4, rms_norm_eps=1e-5,
                  rope_theta=10000.0)
LBL_W, Z_W = 0.01, 0.001
LOGIT_TOL = 2e-5


def build(compute_dtype=None, **over):
    kw = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
              intermediate_size=32, seq_len=S, qk_norm=True, num_experts=16,
              moe_k=4, moe_renorm_topk=False, moe_capacity_factor=None,
              moe_aux_coeff=LBL_W, moe_z_coeff=Z_W)
    kw.update(over)
    ids = ht.placeholder_op("ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op("labels", (B, S), dtype=np.int32)
    model = LlamaForCausalLM(LlamaConfig(**kw))
    loss, terms = model.loss_terms(ids, labels)
    variables = graph_variables([loss], trainable_only=True)
    ex = ht.Executor(
        {"forward": [model(ids), loss, terms["ce"], terms["lbl"],
                     terms["z"]] + model.moe_loads(),
         "grads": [loss] + ht.gradients(loss, variables)},
        seed=3, compute_dtype=compute_dtype)
    tok = np.random.default_rng(0).integers(0, 256, (B, S + 1))
    feed = {ids: tok[:, :-1], labels: tok[:, 1:]}
    return model, ex, variables, feed, tok


@pytest.fixture(scope="module")
def olmoe():
    model, ex, variables, feed, tok = build()
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    params = reference_params(model, ex.params)
    sums = ref.loss_sums(params, REF_CONFIG, tok[:, :-1], tok[:, 1:])
    want = ref.loss_from_sums(sums, REF_CONFIG, LBL_W, Z_W)
    ref_logits = np.asarray(ref.forward(params, REF_CONFIG, tok[:, :-1])[0])
    return dict(model=model, ex=ex, variables=variables, feed=feed, tok=tok,
                out=out, sums=sums, want=want, ref_logits=ref_logits)


def test_logits_match_reference(olmoe):
    assert np.abs(olmoe["out"][0] - olmoe["ref_logits"]).max() < LOGIT_TOL


@pytest.mark.parametrize("term,index", [("loss", 1), ("ce", 2), ("lbl", 3),
                                        ("z", 4)])
def test_loss_term_matches_reference(olmoe, term, index):
    want = float(olmoe["want"][term])
    assert abs(float(olmoe["out"][index]) - want) < 1e-5 * abs(want)


def test_load_vector_is_the_references(olmoe):
    """The [2, E] vector fetched beside the loss: pairs routed per expert
    as the reference counts them, all of them kept."""
    for layer, load in enumerate(olmoe["out"][5:]):
        np.testing.assert_array_equal(load[0], olmoe["sums"]["load"][layer])
        np.testing.assert_array_equal(load[1], load[0])


def test_every_gradient_leaf_matches_reference(olmoe):
    ex, variables, tok = olmoe["ex"], olmoe["variables"], olmoe["tok"]
    got = ex.run("grads", feed_dict=olmoe["feed"],
                 convert_to_numpy_ret_vals=True)[1:]
    params = reference_params(olmoe["model"], ex.params)
    want = jax.grad(lambda p: ref.pretraining_loss(
        p, REF_CONFIG, tok[:, :-1], tok[:, 1:], LBL_W, Z_W))(params)
    by_buffer = {id(v): k for k, v in params.items()}
    assert len(variables) == len(params) == 3 + 12 * 2
    for var, g in zip(variables, got):
        w = np.asarray(want[by_buffer[id(ex.params[var.name])]])
        assert np.abs(g - w).max() < 1e-5 * np.abs(w).max() + 1e-9, var.name


@pytest.mark.parametrize("what,over,dtype", [
    ("bf16 compute", {}, jnp.bfloat16),
    ("renormalised top-k", {"moe_renorm_topk": True}, None),
    ("no QK-norm", {"qk_norm": False}, None)])
def test_tolerance_refuses(olmoe, what, over, dtype):
    """The logits tolerance is tight enough that a lower compute precision,
    a renormalised top-k or a missing QK-norm fails it (same seed, so the
    same weights)."""
    _, ex, _, feed, _ = build(compute_dtype=dtype, **over)
    logits = ex.run("forward", feed_dict=feed,
                    convert_to_numpy_ret_vals=True)[0]
    gap = np.abs(np.asarray(logits, np.float32) - olmoe["ref_logits"]).max()
    assert gap > 50 * LOGIT_TOL, (what, gap)


# -- the dropless op against a dense computation ------------------------------

def dense_moe(x, wg, w1, w3, w2, k, renorm):
    probs = jax.nn.softmax(x @ wg, -1)
    chosen = jnp.argsort(-probs, -1, stable=True)[:, :k]
    gate = jnp.take_along_axis(probs, chosen, -1)
    if renorm:
        gate = gate / gate.sum(-1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(chosen, wg.shape[1]) * gate[..., None], 1)
    return sum(weight[:, e:e + 1]
               * ((jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e])
               for e in range(wg.shape[1]))


def moe_inputs(T=64, H=32, F=48, E=8, seed=0):
    r = np.random.default_rng(seed)
    return [jnp.asarray(a, jnp.float32) for a in (
        r.normal(size=(T, H)), r.normal(size=(H, E)),
        r.normal(size=(E, H, F)) * 0.1, r.normal(size=(E, H, F)) * 0.1,
        r.normal(size=(E, F, H)) * 0.1)]


def dropless(k, renorm, impl):
    def f(x, wg, w1, w3, w2):
        idx, gate, _ = moe_ops.top_k_route(x @ wg, k, renorm=renorm)
        return moe_ops.dropless_moe(x, idx, gate, w1, w3, w2, impl=impl)[0]
    return f


@pytest.mark.parametrize("k", [1, 2, 8])
@pytest.mark.parametrize("renorm", [False, True])
def test_dropless_top_k(k, renorm):
    args = moe_inputs()
    got = jax.jit(dropless(k, renorm, None))(*args)
    np.testing.assert_allclose(got, dense_moe(*args, k, renorm), atol=2e-6)


@pytest.mark.parametrize("impl", ["ragged", "pallas"])
def test_grouped_products_forward_and_backward(impl):
    """Both forms of the grouped products (``jax.lax.ragged_dot`` and the
    ``hetu_moe_gmm_*`` kernels, interpreted here) against the dense
    computation, values and the gradient of every operand."""
    args = moe_inputs()
    want = jax.grad(lambda *a: jnp.sum(dense_moe(*a, 3, False) ** 2),
                    argnums=range(5))(*args)
    got = jax.jit(jax.grad(
        lambda *a: jnp.sum(dropless(3, False, impl)(*a) ** 2),
        argnums=range(5)))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5)


def test_router_ties_go_to_the_lower_expert():
    """Equal router logits: program and reference both take experts
    0..k-1, so a tie cannot make them disagree."""
    x, wg, w1, w3, w2 = moe_inputs()
    wg = jnp.zeros_like(wg)
    idx, gate, _ = moe_ops.top_k_route(x @ wg, 3)
    np.testing.assert_array_equal(idx, np.tile(np.arange(3), (64, 1)))
    _, _, chosen, _ = ref.router(x, wg, 3)
    np.testing.assert_array_equal(chosen, idx)
    np.testing.assert_allclose(dropless(3, False, None)(x, wg, w1, w3, w2),
                               dense_moe(x, wg, w1, w3, w2, 3, False),
                               atol=2e-6)


def test_tiled_layout_with_empty_experts():
    """Tile-aligned layout: every pair has its own row inside its expert's
    tiles, an expert without pairs still owns a tile, unused tiles fall to
    the last expert."""
    E, tile = 6, 4
    idx = jnp.asarray([[0, 0], [0, 3], [3, 0], [3, 3], [5, 0], [0, 3]],
                      jnp.int32)                        # experts 1, 2, 4 empty
    lay = jax.jit(lambda i: moe_ops.grouped_layout(i, E, tile))(idx)
    slot, pair = np.asarray(lay["slot_of_pair"]), np.asarray(
        lay["pair_of_slot"])
    te = np.asarray(lay["tile_expert"])
    assert sorted(set(slot)) == sorted(slot) and len(pair) == 12 + E * tile
    np.testing.assert_array_equal(pair[slot], np.arange(12))
    np.testing.assert_array_equal(te[slot // tile], np.asarray(idx).ravel())
    assert (pair >= 0).sum() == 12
    assert int(lay["n_used"][0]) == 2 + 1 + 1 + 2 + 1 + 1
    assert list(te[:8]) == [0, 0, 1, 2, 3, 3, 4, 5] and (te[8:] == 5).all()
    np.testing.assert_array_equal(lay["load"], [6, 0, 0, 5, 0, 1])


# -- through the graph API: dropless keeps what a capacity drops --------------

def run_layer(capacity_factor, x, router):
    H, F, E, k = 16, 32, 16, 8
    node = ht.placeholder_op("x", x.shape)
    layer = MoELayer(H, F, E, k=k, capacity_factor=capacity_factor,
                     expert_act="swiglu", renorm_topk=False,
                     track_load=True)
    out = layer(node)
    ex = ht.Executor({"f": [out, layer.load()]}, seed=5)
    ex.params[layer.gate.wg.name] = jnp.asarray(router)
    y, load = ex.run("f", feed_dict={node: x},
                     convert_to_numpy_ret_vals=True)
    p = {n: ex.params[v.name] for n, v in (("w1", layer.w1), ("w3", layer.w3),
                                           ("w2", layer.w2))}
    return y, load, p


def test_every_token_to_the_same_experts():
    """A router that sends all 64 tokens to experts 0..7: the dropless path
    computes all 512 pairs and equals the dense computation; the capacity
    path at factor 1.25 provably cannot (40 slots an expert for 64 tokens)
    and says so in its load vector."""
    r = np.random.default_rng(1)
    x = r.normal(size=(64, 16)).astype(np.float32)
    x[:, 0] = 1.0
    router = np.zeros((16, 16), np.float32)
    router[0, :8] = 20.0 - np.arange(8)       # x[:, 0] = 1 carries it
    y, load, p = run_layer(None, x, router)
    np.testing.assert_array_equal(load[0], [64] * 8 + [0] * 8)
    np.testing.assert_array_equal(load[1], load[0])
    want = dense_moe(jnp.asarray(x), jnp.asarray(router), p["w1"], p["w3"],
                     p["w2"], 8, False)
    np.testing.assert_allclose(y, want, atol=2e-6)
    _, load, _ = run_layer(1.25, x, router)
    np.testing.assert_array_equal(load[0], [64] * 8 + [0] * 8)
    np.testing.assert_array_equal(load[1], [40] * 8 + [0] * 8)


def test_load_counters():
    telemetry.enable()
    try:
        telemetry.get_registry().reset()
        record_moe_load("layer0", np.zeros((2, 4)))      # state's zeros
        record_moe_load("layer0", [[6, 2, 0, 0], [4, 2, 0, 0]])
        record_moe_load("layer0", [[2, 2, 2, 2], [2, 2, 2, 2]])
        snap = telemetry.get_registry().snapshot()

        def value(name):
            (s,) = snap[name]["samples"]
            assert s["labels"] == {"layer": "layer0"}
            return s["value"]
        assert value("hetu_moe_pairs_routed_total") == 16
        assert value("hetu_moe_pairs_dropped_total") == 2
        assert value("hetu_moe_expert_load_max_over_mean") == 1.0
    finally:
        telemetry.shutdown()


def test_published_config_entry():
    """``LLAMA_CONFIGS["olmoe-1b-7b"]`` carries config.json's widths."""
    c = LlamaConfig(**LLAMA_CONFIGS["olmoe-1b-7b"])
    assert (c.hidden_size, c.num_layers, c.num_heads, c.num_kv_heads,
            c.intermediate_size, c.vocab_size, c.num_experts, c.moe_k) == (
                2048, 16, 16, 16, 1024, 50304, 64, 8)
    assert c.qk_norm and not c.moe_renorm_topk
    assert c.moe_capacity_factor is None
    assert (c.moe_aux_coeff, c.moe_z_coeff) == (0.01, 0.001)


# -- the benchmark's side -----------------------------------------------------

def test_flops_of_the_cut_configuration():
    """357.4 M forward operations a token at depth 1 and 4,096 positions,
    experts 28% and head 58% (ISSUE 26); 61% and 8% at depth 16."""
    import json
    import os
    from chipbench import flops_moe, run
    c = run.load_json(run.ROOT, "chipbench", "configs",
                      "olmoe-1b-7b-pretrain.json")
    parts = flops_moe.olmoe_forward_flops_per_token(c, 4096)
    total = sum(parts.values())
    assert abs(total - 357.4e6) < 0.1e6
    assert round(100 * parts["experts"] / total) == 28
    assert round(100 * parts["head"] / total) == 58
    whole = flops_moe.olmoe_forward_flops_per_token(
        dict(c, num_hidden_layers=16), 4096)
    assert round(100 * whole["experts"] / sum(whole.values())) == 61
    assert round(100 * whole["head"] / sum(whole.values())) == 8
    assert flops_moe.olmoe_train_flops_per_token(c, 4096) == 3 * total
    assert json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))


def test_new_cell_rehearses(capsys):
    """The harness runs the new cell end to end at toy size on the CPU:
    builder, loop, reference, every check."""
    from chipbench import run
    rc = run.main(["--workload", "olmoe-1b-7b.b2-s4096", "--seed",
                   str(2 ** 31 + 11), "--seconds", "2", "--trace", "0"],
                  rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "WRONG" not in out and "routing_mismatch" in out


def test_traffic_files_keep_the_generators_promise():
    from chipbench import selfcheck
    selfcheck.check_traffic()
