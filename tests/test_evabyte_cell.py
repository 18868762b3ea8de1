"""The benchmark's side of the EvaByte cell on the CPU: its entries in
``BENCHMARK.json``, the cut against the whole model (the four layers'
variables are the 32-layer model's, name for name and shape for shape; 821.4 M
and 6,488 M parameters), the builder at toy size against the plain reference
with and without whole layers recomputed, the cell's rehearsal through the
harness, its controls (each fails its term), the toy's train step lowered for
a TPU at a head of 128 (which kernels a step calls, and how often), and its
readers (``chipbench/tests/test_evabyte_readers.py``, collected here).

**A new cell's test calls ``declared(bench, CELL, own=...)`` of
``tests/cells.py`` inside its ``table_part(bench)`` and states nothing else
about ``BENCHMARK.json``**: no count of entries, configurations or cells, no
index into them, no list of the quantities the cell reports (that list is
``chipbench/testdata/per_layer/<cell>.json``).
``tests/test_cells_declared.py`` runs ``table_part`` on a folded table and on
one that holds more."""

import json

import numpy as np
import pytest

import cells
from chipbench import run
from chipbench.tests.test_evabyte_readers import *  # noqa: F401,F403

CELL = "evabyte-6.5b.b1-s8192"
#: the family's own mechanism: EVA's kernel pair, read as a window's
OWN = ("window_attn_roofline", "window_attn_block_device_ms_per_step")
TERMS = {"ce", "logits_gap", "eva_gap", "eva_remote_gap", "summary_gap",
         "nodes"}


def table_part(bench):
    mine = cells.declared(bench, CELL, own=OWN)
    # no layer runs the flash kernel
    assert "flash_roofline" not in mine


def test_benchmark_entries():
    bench, cell, config, mix = run.load_cell(CELL)
    table_part(bench)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "evabyte-6.5b-pretrain", "b1-s8192-evabyte", 1)
    assert config["reduced"] == ["num_hidden_layers"]
    assert set(mix["reference_tolerance"]) == TERMS
    for key, value in {"batch": 1, "seq": 8192, "ring": 8, "warm_steps": 3,
                       "strategy": None, "mask_fraction": 1.0,
                       "kind": "train_loop"}.items():
        assert mix[key] == value, key
    assert config["job"]["remat"] == "layer"


def variables_of(layers, seq=8192):
    """``{name: shape}`` of the model's variables at the published widths,
    nothing allocated."""
    import hetu_tpu as ht
    from hetu_tpu.graph.node import graph_variables
    from hetu_tpu.models import EvaByteConfig, EvaByteForCausalLM
    from chipbench.builders.evabyte import HF_KEYS
    _, _, config, _ = run.load_cell(CELL)
    keys = {k: config[k] for k in HF_KEYS}
    keys["num_hidden_layers"] = layers
    model = EvaByteForCausalLM(EvaByteConfig(seq_len=seq, **keys))
    ids = ht.placeholder_op(f"ids{layers}", (1, seq), dtype=np.int32)
    labels = ht.placeholder_op(f"labels{layers}", (1, seq, 8), dtype=np.int32)
    loss = model.loss(ids, labels)
    return {v.name: tuple(v.shape)
            for v in graph_variables([loss], trainable_only=True)}


def test_the_cut_is_the_model():
    """821,366,784 parameters held here (9.18 GiB at 12 bytes), 6,488 M in
    the whole model; every variable of the cut is the whole model's, under
    the same name and of the same shape."""
    _, _, config, _ = run.load_cell(CELL)
    cut, whole = variables_of(4), variables_of(32)
    count = lambda vs: sum(int(np.prod(s)) for s in vs.values())
    assert count(cut) == 821366784
    assert round(count(cut) / 1e6, 1) == config["deployment"]["parameters_m"]
    assert round(count(cut) * 12 / 2 ** 30, 2) == config["deployment"][
        "resident_gib"]
    assert round(count(whole) / 1e6) == 6488 == round(
        config["deployment"]["whole_model_parameters_m"])
    assert set(cut) <= set(whole)
    assert all(whole[name] == shape for name, shape in cut.items())
    a_layer = {n: s for n, s in cut.items() if "layer0_" in n}
    assert count(a_layer) == 202391552
    assert len(cut) == 3 + 4 * len(a_layer)


def toy(say=lambda msg: None, over=None, **job):
    from chipbench.builders import evabyte as builder
    _, _, config, mix = run.load_cell(CELL)
    config = run.merge(run.merge(config, config["toy"]), {"job": job})
    config = run.merge(config, over or {})
    mix = run.merge(mix, mix["toy"])
    return builder.build(config, mix, 2 ** 31 + 3, say), mix


@pytest.mark.parametrize("remat", ["layer", None])
def test_the_cells_builder_at_toy_size(remat, live_registry):
    prog, mix = toy(remat=remat)
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        ids, labels = (feed[prog.nodes[k]] for k in ("ids", "labels"))
        assert ids.shape == (1, 64) and labels.shape == (1, 64, 8)
        for i in range(8):      # head i's labels are the ids shifted by 1 + i
            assert (ids[:, 1 + i:] == labels[:, :63 - i, i]).all()
        assert ids.max() < 320 and labels.min() >= 0
        want = prog.reference_loss(feed, 1)
        assert set(prog.kept) == {"logits", "eva", "local", "summaries"}
        assert prog.kept["logits"].shape == (64 * 8, 320)
        assert prog.kept["summaries"].shape == (1, 16, 2 * 64)
        got = prog.eval_loss(feed)
        for term, tol in mix["reference_tolerance"].items():
            assert abs(got[term] - want[term]) < tol, (term, got, want)
        for i in range(8):
            assert abs(got[f"ce_head{i}"] - want[f"ce_head{i}"]) < 1e-5
        first = prog.step(feed)
        assert abs(first - want["loss"]) < mix["first_loss_tolerance"]
        second = prog.step(feed)
        assert np.isfinite(second) and second < first
        shapes = prog.expected_kernel_shapes()
        assert shapes["attention_passes"] == 2 == shapes["eva_layers"]
        assert shapes["attention_layers"] == (4 if remat else 2)
        assert shapes["flash_dims"] == (1, 2, 64, 32)
        assert prog.tokens_per_step == 64 and prog.seq == 64
        taken, fallbacks = prog.kernel_choices()
        assert not fallbacks
        assert prog.uniform_loss() == pytest.approx(np.log(320))
    finally:
        prog.close()


def test_cell_rehearses(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                   "--seconds", "2", "--trace", "0"], rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "WRONG" not in out
    for term in TERMS:
        assert f"the program's {term} " in out, term
    assert "hetu_eva_pairs_total" in out


def test_every_control_is_refused_and_the_program_is_not(capsys):
    from chipbench.reference import evabyte as ref
    from chipbench.reference import evabyte_controls as controls
    # fp8 e5m2 and the plain bf16 reading are the chip's to make
    names = ["fp8_e4m3", *ref.CONTROLS]
    rc = controls.main(["--seed", "5", "--rehearsal"] + [
        arg for name in names for arg in ("--control", name)])
    out = capsys.readouterr().out
    assert rc == 0, out
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    by = {ln["control"]: ln for ln in lines}
    assert set(by) == {"program", *names}
    assert by["program"]["correct"]
    # each fails its own term
    for name, term in (("remote", "eva_remote_gap"), ("sliding", "eva_gap"),
                       ("window_edge", "eva_gap"), ("mu", "summary_gap"),
                       ("phi", "summary_gap"),
                       ("second_rotation", "eva_remote_gap"),
                       ("summaries_bf16", "summary_gap"),
                       ("fp8_e4m3", "logits_gap")):
        assert term in by[name]["refused_by"], (name, by[name])
    # a program without summaries reads a remote part of its own size off
    assert by["remote"]["gaps"]["eva_remote_gap"] >= 1.0


def test_a_recomputed_toy_step_calls_each_kernel_where_it_is_due(
        monkeypatch, live_registry):
    """Lowered for a TPU (nothing compiled or run) at one head of 128, a
    window of 128 and 256 positions: each layer runs ``hetu_eva_fwd`` ONCE with
    whole layers recomputed (the group keeps the kernel's context and
    log-sum-exp) and ``hetu_eva_bwd`` once, the rotary pair as every in-place
    layer; no ``hetu_flash_*`` or ``hetu_swa_*`` call and no ``jax.numpy``
    attention in the step."""
    from conftest import kernel_calls, lowered_for_tpu
    from hetu_tpu.ops.pallas import dispatch
    chosen_before = dispatch.choices()

    def build():
        return toy(over={"hidden_size": 128, "num_attention_heads": 1,
                         "num_key_value_heads": 1, "window_size": 128,
                         "chunk_size": 8},
                   remat="layer", compute_dtype="bfloat16")[0]
    monkeypatch.setattr(run, "load_cell", lambda name, _l=run.load_cell: (
        *_l(name)[:3], run.merge(_l(name)[3], {"toy": {"seq": 256}})))
    text = lowered_for_tpu(monkeypatch, build)
    assert kernel_calls(text, "hetu_eva_fwd") == 2
    assert kernel_calls(text, "hetu_eva_bwd") == 2
    assert kernel_calls(text, "hetu_flash_") == 0
    assert kernel_calls(text, "hetu_swa_") == 0
    assert kernel_calls(text, "hetu_rope_fwd") >= 2
    choices = {k: n - chosen_before.get(k, 0)
               for k, n in dispatch.choices().items()}
    assert not [k for k, n in choices.items() if n and k[1] == "jnp"
                and k[0] in ("eva", "flash_attention", "rotary")], choices
    assert choices[("eva", "pallas", "")] >= 2
