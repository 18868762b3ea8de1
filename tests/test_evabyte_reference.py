"""An EvaByte decoder through ``EvaByteForCausalLM`` and ``ht.Executor`` against
the plain reference (``chipbench/reference/evabyte.py``) at the toy preset of
the configuration file (hidden 64, 2 heads of 32, window 32, chunk 4, 2
layers, 64-128 positions, f32, seeded weights) on the CPU, with and without
whole layers recomputed: the loss, each head's cross-entropy, the logits, one
layer's own attention output and the gradient of every parameter (``phi``,
``mu``, ``W_k``, ``W_v``: what only the summaries' backward path reaches).
Each control of the reference must lie far from the program.  And what ties
the cut to the model: the configuration file against the catalog's row.

Program and reference both compute in f32 here, in different orders, so they
differ by rounding alone."""

import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.models import EvaByteConfig, EvaByteForCausalLM

from chipbench import run
from chipbench.builders.evabyte import (HF_KEYS, reference_params,
                                        remote_gap, seed_parts)
from chipbench.builders.granite_hybrid import logits_gap
from chipbench.reference import evabyte as ref

CELL = "evabyte-6.5b.b1-s8192"
B = 2


def toy_config():
    _, _, config, _ = run.load_cell(CELL)
    return run.merge(config, config["toy"])


def build(name, seq, remat=None):
    c = toy_config()
    P = c["num_pred_heads"]
    ids = ht.placeholder_op(f"{name}_ids", (B, seq), dtype=np.int32)
    labels = ht.placeholder_op(f"{name}_labels", (B, seq, P), dtype=np.int32)
    model = EvaByteForCausalLM(EvaByteConfig(
        seq_len=seq, remat=remat, **{k: c[k] for k in HF_KEYS}), name=name)
    logits = model(ids)
    loss, terms = model.loss_terms(ids, labels, logits=logits)
    variables = graph_variables([loss], trainable_only=True)
    attn = model.model.layers[-1].attn
    ex = ht.Executor({"forward": [logits, loss, terms["ce_heads"],
                                  attn.context, *attn.summaries],
                      "grads": [loss] + ht.gradients(loss, variables)},
                     seed=3)
    seed_parts(ex, model, 7)
    tok = np.random.default_rng(seq).integers(
        0, c["vocab_size"], (B, seq + P)).astype(np.int32)
    lab = np.stack([tok[:, 1 + i:1 + i + seq] for i in range(P)], -1)
    lab[0, -3:, 5] = -1         # some positions of one head unlabelled
    return c, model, ex, variables, {ids: tok[:, :seq], labels: lab}


@pytest.fixture(scope="module", params=[(64, None), (128, "layer")],
                ids=["s64_remat_None", "s128_remat_layer"])
def built(request):
    seq, remat = request.param
    # ONE name: the variables' draws are by name
    c, model, ex, variables, feed = build("evaref", seq, remat)
    ids, labels = feed.values()
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    params = {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}
    with jax.default_matmul_precision("highest"):
        sums = jax.device_get(jax.jit(lambda p: ref.loss_sums(
            p, c, ids, labels, keep_logits=True,
            keep_layer=c["num_hidden_layers"] - 1))(params))
    yield dict(c=c, model=model, ex=ex, variables=variables, feed=feed,
               out=out, params=params, sums=sums, seq=seq)
    ex.close()


def test_weights_are_the_references(built):
    assert len(built["params"]) == len(built["variables"])
    assert set(built["params"]) == set(ref.WEIGHTS) | {
        f"layers.{i}.{n}" for i in range(2) for n in ref.LAYER_WEIGHTS}
    attn = built["model"].model.layers[0].attn
    assert (attn.window, attn.chunk) == (32, 4)
    assert built["params"]["layers.0.phi"].shape == (2, 32)
    assert built["params"]["lm_head"].shape == (64, 8 * 320)


def test_logits_loss_and_every_heads_ce_match_reference(built):
    logits, loss, heads = built["out"][:3]
    want = built["sums"]["logits"]
    assert want.shape == (B * built["seq"] * 8, 320)
    assert logits.dtype == np.float32 and np.abs(want).max() > 0.05
    assert logits_gap(logits, want) < 2e-5
    terms = {k: float(v) for k, v in ref.loss_from_sums(
        built["sums"]).items()}
    assert abs(float(loss) - terms["loss"]) < 1e-5
    for i in range(8):
        assert abs(float(heads[i]) - terms[f"ce_head{i}"]) < 1e-5, i
    assert built["sums"]["n"][5] == B * built["seq"] - 3


def test_a_layers_attention_output_and_summaries_match_reference(built):
    context, ks, vs = built["out"][3:]
    kept, window = built["sums"], built["c"]["window_size"]
    assert logits_gap(context, kept["eva"]) < 2e-5
    # the layer summarises every window but the last, which no query reads
    read = (built["seq"] - 1) // window * window // built["c"]["chunk_size"]
    assert ks.shape[1] == read < kept["summaries"].shape[1]
    assert logits_gap(np.concatenate([ks, vs], -1),
                      kept["summaries"][:, :read]) < 2e-5
    assert remote_gap(context, kept["eva"], kept["local"], window) < 1e-4
    # a program that read no summary would read 1 there
    assert remote_gap(kept["local"], kept["eva"], kept["local"],
                      window) == pytest.approx(1.0)


def test_every_gradient_leaf_matches_reference(built):
    ex, variables, c = built["ex"], built["variables"], built["c"]
    ids, labels = built["feed"].values()
    got = ex.run("grads", feed_dict=built["feed"],
                 convert_to_numpy_ret_vals=True)[1:]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda p: ref.loss(
            p, c, ids, labels)))(built["params"])
    names = {v: k for k, v in reference_params(
        built["model"], {n: n for n in ex.params}).items()}
    seen = set()
    for var, g in zip(variables, got):
        w = np.asarray(want[names[var.name]])
        assert np.abs(w).max() > 0, var.name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, var.name
        seen.add(names[var.name].split(".")[-1])
    assert {"phi", "mu", "k", "v", "q", "o", "embed", "lm_head", "norm",
            "input_norm", "mlp_down"} <= seen


@pytest.mark.parametrize("control", [*ref.CONTROLS, "bf16", "fp8_e4m3"])
def test_a_control_lies_far_from_the_program(built, control):
    """The remote term left out, a sliding window, a window one key off,
    ``mu`` or ``phi`` ignored, a second rotation, the summaries' sums in bf16,
    products at a lower precision: each moves the probed layer's output by far
    more than the program lies from the reference (under 2e-5)."""
    c = built["c"]
    ids, labels = built["feed"].values()
    how = ({"matmul_inputs": {"bf16": jnp.bfloat16,
                              "fp8_e4m3": jnp.float8_e4m3fn}[control]}
           if control in ("bf16", "fp8_e4m3") else {"without": (control,)})
    with jax.default_matmul_precision("highest"):
        kept = jax.device_get(jax.jit(lambda p: ref.loss_sums(
            p, c, ids, labels, keep_logits=True,
            keep_layer=c["num_hidden_layers"] - 1, **how))(built["params"]))
    context = built["out"][3]
    floor = 2e-4 if control == "summaries_bf16" else 1e-3
    assert logits_gap(context, kept["eva"]) > floor
    assert logits_gap(built["out"][0], kept["logits"]) > floor / 2


def test_the_configuration_file_is_the_catalogs_row():
    """Every published key at its published value but ``num_hidden_layers``,
    which ``reduced`` lists with its reason; ``assumed`` has an entry for
    each point the published keys do not fix."""
    _, _, config, _ = run.load_cell(CELL)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "EvaByte")
    assert config["source"] == row["source_url"]
    assert set(HF_KEYS) == set(row["config"])
    differ = [k for k, v in row["config"].items() if config[k] != v]
    assert differ == config["reduced"] == ["num_hidden_layers"]
    assert (row["config"]["num_hidden_layers"],
            config["num_hidden_layers"]) == (32, 4)
    assert "32 -> 4" in config["reduced_why"]["num_hidden_layers"]
    assert {"summary_weights", "summary_key", "summary_positions", "windows",
            "own_window_chunks", "heads", "rotary", "initial_values",
            "job"} <= set(config["assumed"])
    assert config["deployment"]["chips_sharing_a_layer"] == 1
    assert config["builder"] == "evabyte"
