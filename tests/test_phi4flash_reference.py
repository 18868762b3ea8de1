"""A SambaY decoder through ``Phi4FlashForCausalLM`` and ``ht.Executor`` against
the plain reference (``chipbench/reference/phi4flash.py``) at a small size on
the CPU: the model's layers 14-19 (every kind) on seeded weights with every
norm weight, bias and ``D`` moved off its initial value, f32 compute, with and
without whole layers recomputed: the loss, the logits and the gradient of
every parameter (the lambdas, ``A_log``, ``D``, the ``dt`` bias; layer 17's
``W_qkv`` through BOTH of its readers; layer 16's ``in_proj`` through the GMU).
And what ties the cut to the model: the published parameter count, the
vocabulary slice, the configuration file against the catalog's row.

Program and reference both compute in f32 here, in different orders (a
chunked scan against the recurrence, one product over grouped heads against
blocked attention a pair), so they differ by rounding alone."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.models import (PHI4FLASH_CONFIGS, Phi4FlashConfig,
                             Phi4FlashForCausalLM)

from chipbench import run
from chipbench.builders.phi4flash import reference_params
from chipbench.reference import phi4flash as ref

B, S, V = 2, 48, 256
KEYS = dict(vocab_size=V, hidden_size=64, intermediate_size=96,
            num_hidden_layers=6, num_attention_heads=4,
            num_key_value_heads=2, sliding_window=8, first_layer_index=14)
#: the reference reads the configuration FILE's layout
C = dict(KEYS, layer_norm_eps=1e-5, mb_per_layer=2,
         deployment={"num_hidden_layers": 32},
         assumed={"mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
                  "mamba_dt_rank": 4})
RNG = np.random.default_rng(0)
TOKENS = RNG.integers(0, V, (B, S + 1)).astype(np.int32)
IDS, LABELS = TOKENS[:, :-1], TOKENS[:, 1:]


def build(name, remat=None, **over):
    ids = ht.placeholder_op(f"{name}_ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op(f"{name}_labels", (B, S), dtype=np.int32)
    model = Phi4FlashForCausalLM(Phi4FlashConfig(
        seq_len=S, published_layers=32, remat=remat,
        **dict(KEYS, **over)), name=name)
    logits = model(ids)
    loss, _ = model.loss_terms(ids, labels, logits=logits)
    variables = graph_variables([loss], trainable_only=True)
    ex = ht.Executor({"forward": [logits, loss],
                      "grads": [loss] + ht.gradients(loss, variables)},
                     seed=3)
    r = np.random.default_rng(7)
    for key, value in list(ex.params.items()):
        if key.endswith(("_scale", "_bias", "_d")) and "dt_bias" not in key:
            ex.params[key] = value + jnp.asarray(
                r.normal(0, 0.2, value.shape), value.dtype)
    return model, ex, variables, {ids: IDS, labels: LABELS}


@pytest.fixture(scope="module", params=[None, "layer"],
                ids=["remat_None", "remat_layer"])
def built(request):
    # ONE name: the variables' draws are by name, so both hold the same
    # weights
    model, ex, variables, feed = build("phi4ref", request.param)
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    params = {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}
    sums = jax.jit(lambda p: ref.loss_sums(p, C, IDS, LABELS,
                                           keep_logits=True))(params)
    yield dict(model=model, ex=ex, variables=variables, feed=feed, out=out,
               params=params, sums=jax.device_get(sums))
    ex.close()


def test_layers_and_weights(built):
    model = built["model"]
    kinds = [layer.kind for layer in model.model.layers]
    assert kinds == ["mamba", "window", "mamba", "full", "gmu", "cross"]
    assert kinds == [ref.kind_of(C, 14 + i) for i in range(6)]
    assert [layer.index for layer in model.model.layers] == list(range(14, 20))
    first, second = model.model.layers_of("mamba")
    assert second.mixer.hand_out_scan and not first.mixer.hand_out_scan
    assert model.model.shared["memory"] is second.mixer.memory
    window, full, cross = model.model.layers_of("window", "full", "cross")
    assert (window.mixer.window, full.mixer.window, cross.mixer.cross) == (
        8, None, True)
    assert model.model.shared["keys"] is full.mixer.keys
    for layer in (window, full, cross):
        assert layer.mixer.lambda_init == pytest.approx(
            0.8 - 0.6 * np.exp(-0.3 * layer.index))
    assert len(built["params"]) == len(built["variables"])
    assert model.lm_head is None            # the head is the embedding


def test_logits_and_loss_match_reference(built):
    want = built["sums"]["logits"]
    assert want.shape == (B * S, V) and np.abs(want).max() > 0.1
    assert np.abs(built["out"][0] - want).max() < 2e-5
    loss = float(ref.loss_from_sums(built["sums"])["loss"])
    assert abs(float(built["out"][1]) - loss) < 1e-5 * loss


def test_every_gradient_leaf_matches_reference(built):
    ex, variables = built["ex"], built["variables"]
    got = ex.run("grads", feed_dict=built["feed"],
                 convert_to_numpy_ret_vals=True)[1:]
    want = jax.jit(jax.grad(lambda p: ref.training_loss(
        p, C, IDS, LABELS)))(built["params"])
    names = {v: k for k, v in reference_params(
        built["model"], {n: n for n in ex.params}).items()}
    seen = set()
    for var, g in zip(variables, got):
        w = np.asarray(want[names[var.name]])
        assert np.abs(w).max() > 0, var.name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, var.name
        seen.add(names[var.name].split(".")[-1])
    assert {"lq1", "lk2", "subln", "a_log", "d", "dt_bias", "dt_proj",
            "x_proj", "conv", "qkv", "qkv_bias", "o_bias", "in_proj",
            "embed", "norm_bias"} <= seen


_CUTS = {}


def test_a_handed_out_value_carries_every_readers_gradient(built):
    """Layer 17's ``W_qkv`` through its own attention AND layer 19's; layer
    16's ``in_proj`` through its own gate AND layer 18's unit: with a reader
    cut off the reference's gradient is another, and the program's is the
    whole one (``test_every_gradient_leaf_matches_reference``)."""
    readers = ((5, "layers.3.qkv"), (4, "layers.2.in_proj"))

    def grads(p):
        def loss(leaves, reader=None):
            q = dict(p, **leaves)
            for k in [k for k in p if reader is not None and k.startswith(
                    f"layers.{reader}.") and k.endswith(("out_proj", ".o"))]:
                q[k] = p[k] * 0.0
            return ref.training_loss(q, C, IDS, LABELS)
        leaves = {leaf: p[leaf] for _, leaf in readers}
        return [jax.grad(loss)(leaves)] + [
            jax.grad(loss)(leaves, reader) for reader, _ in readers]
    if not _CUTS:           # the reference's alone: once for both programs
        _CUTS["grads"] = jax.device_get(jax.jit(grads)(built["params"]))
    whole, *cuts = _CUTS["grads"]
    for cut, (_, leaf) in zip(cuts, readers):
        w = np.asarray(whole[leaf])
        assert np.abs(cut[leaf] - w).max() > 1e-3 * np.abs(w).max(), leaf


def test_recomputed_layers_change_no_bit_of_the_loss():
    losses = {}
    for remat in (None, "layer"):
        _, ex, variables, feed = build("phi4bits", remat)
        out = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
        losses[remat] = (out[0], out[1:])
        ex.close()
    assert losses[None][0] == losses["layer"][0]
    for a, b, var in zip(losses[None][1], losses["layer"][1], variables):
        assert np.abs(a - b).max() <= 1e-6 * np.abs(a).max(), var.name


def test_two_groups_that_read_one_kept_value():
    """``graph/trace.py``: a value that leaves a recomputed group for two
    LATER groups is an output of its group and an argument of theirs, and its
    cotangents from both are summed before its group's backward pass runs."""
    x = ht.placeholder_op("kept_x", (4, 8))
    w = [ht.Variable(f"kept_w{i}", shape=(8, 8),
                     initializer=ht.init.normal(0.0, 0.5)) for i in range(3)]

    def graph(recompute):
        from contextlib import nullcontext
        scope = ht.remat if recompute else nullcontext
        with scope():
            kept = ht.tanh_op(ht.matmul_op(x, w[0]))
            a = ht.matmul_op(kept, w[0])
        with scope():
            b = ht.tanh_op(ht.matmul_op(a, w[1])) * kept
        with scope():
            c = ht.matmul_op(b, w[2]) + kept
        return ht.reduce_sum_op(c * c, axes=[0, 1]), kept
    grads = {}
    for recompute in (False, True):
        loss, kept = graph(recompute)
        if recompute:
            assert kept.remat_scope is not None
        ex = ht.Executor({"g": [loss] + ht.gradients(loss, w)}, seed=1)
        grads[recompute] = ex.run(
            "g", feed_dict={x: np.arange(32, dtype=np.float32).reshape(4, 8)
                            / 32}, convert_to_numpy_ret_vals=True)
    for a, b in zip(grads[False], grads[True]):
        assert np.abs(a).max() > 0 and np.allclose(a, b, rtol=1e-6, atol=0)


def test_the_sliced_models_logits_are_the_whole_models_at_the_slices_rows():
    def logits(name, vocab, rows=None):
        model, ex, _, feed = build(name, vocab_size=vocab)
        if rows is not None:
            table = model.model.embed.weight.name
            ex.params[table] = rows(ex.params[table])
        params = dict(ex.params)
        out = ex.run("forward", feed_dict=feed,
                     convert_to_numpy_ret_vals=True)[0]
        ex.close()
        return out, params, model
    whole, params, model = logits("phi4slice", 4 * V)
    table = model.model.embed.weight.name
    sliced, _, _ = logits("phi4slice", V,
                          rows=lambda _: params[table][:V])
    assert sliced.shape == (B * S, V) and whole.shape == (B * S, 4 * V)
    assert np.abs(sliced - whole[:, :V]).max() < 1e-6


def variables_of(config):
    ids = ht.placeholder_op("phi4count_ids", (1, 128), dtype=np.int32)
    model = Phi4FlashForCausalLM(config, name="phi4count")
    return graph_variables([model(ids)], trainable_only=True)


def test_the_published_sizes_count_what_the_paper_says():
    """9 Mamba + 9 attention + 7 GMU + 7 cross layers + the tied embedding:
    3,852.6 M, the published "3.8B"; the cell's six layers and an eighth of
    the vocabulary: 697,094,272."""
    assert PHI4FLASH_CONFIGS["phi-4-mini-flash-reasoning"] == {}
    count = lambda vs: sum(int(np.prod(v.shape)) for v in vs)
    whole = Phi4FlashConfig(seq_len=128)
    kinds = whole.kinds
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu",
                                     "cross")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "mamba" and kinds[17] == "full"
    assert round(count(variables_of(whole)) / 1e6, 1) == 3852.6
    cut = Phi4FlashConfig(seq_len=128, num_hidden_layers=6,
                          first_layer_index=14, published_layers=32,
                          vocab_size=25008)
    by_kind = {}
    variables = variables_of(cut)
    for v in variables:
        by_kind[v.name.split("_")[1]] = by_kind.get(
            v.name.split("_")[1], 0) + int(np.prod(v.shape))
    assert count(variables) == 697_094_272
    assert by_kind["layer14"] == by_kind["layer16"] == 119_895_040
    assert by_kind["layer15"] == by_kind["layer17"] == 98_322_304
    assert by_kind["layer18"] == 104_867_840
    assert by_kind["layer19"] == 91_766_144
    with pytest.raises(AssertionError, match="reads layer 16"):
        Phi4FlashConfig(num_hidden_layers=2, first_layer_index=18,
                        published_layers=32)


def test_the_configuration_file_is_the_catalogs_row():
    _, cell, config, mix = run.load_cell("phi-4-mini-flash.b1-s16384")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Phi-4-mini-flash-reasoning")
    assert config["source"] == row["source_url"]
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value
            assert config["deployment"][key] == value
        else:
            assert config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["first_layer_index"]) == (6, 25008, 14)
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert config["assumed"]["mamba_dt_rank"] == -(-config["hidden_size"]
                                                   // 16)
    assert (cell["chips"], mix["batch"], mix["seq"]) == (1, 1, 16384)
    assert config["deployment"]["parameters_m"] == 697.1
