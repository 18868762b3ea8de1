"""The benchmark's side of the SambaY cell on the CPU: its entries in
``BENCHMARK.json``, the operations the readers credit, the builder at toy size
against the plain reference with and without whole layers recomputed, the
cell's rehearsal through the harness, its controls (each fails its term), the
toy's train step lowered for a TPU at a pair's width (which kernels a step
calls, and how often), and its readers
(``chipbench/tests/test_phi4flash_readers.py``, collected here)."""

import json

import numpy as np
import pytest

import cells
from chipbench import flops_phi4flash as fp, run
from chipbench.tests.test_phi4flash_readers import *  # noqa: F401,F403

CELL = "phi-4-mini-flash.b1-s16384"
#: the family's own mechanism: the Mamba-1 scan's kernel pair
OWN = ("selective_scan_roofline",)
TERMS = {"ce", "logits_gap", "scan_gap", "scan_probe_gap", "window_gap",
         "window_edge", "attention_gap", "gmu_gap", "cross_gap", "nodes"}


def table_part(bench):
    mine = cells.declared(bench, CELL, own=OWN)
    scan = mine["selective_scan_roofline"]
    assert (scan["unit"], scan["better"], scan["source"], scan["layer"],
            scan["moves"]) == ("%", "higher", "device_trace", "kernels",
                               "train_tokens_per_s")


def test_benchmark_entries():
    bench, cell, config, mix = run.load_cell(CELL)
    table_part(bench)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi-4-mini-flash-reasoning-train", "b1-s16384-phi4flash", 1)
    assert set(mix["reference_tolerance"]) == TERMS
    for key, value in {"batch": 1, "seq": 16384, "ring": 8, "warm_steps": 3,
                       "strategy": None, "mask_fraction": 1.0}.items():
        assert mix[key] == value, key
    assert config["job"]["remat"] == "layer"


def test_flops_of_the_cut_configuration():
    """1,654 M forward operations a token: six MLPs 57%, the two layers over
    all earlier keys 15%, the Mamba projections 10%, the head 8%, the window's
    band half a percent, the scan under a tenth."""
    _, _, c, _ = run.load_cell(CELL)
    assert fp.kinds(c) == ["mamba", "window", "mamba", "full", "gmu",
                           "cross"]
    parts = fp.forward_flops_per_token(c, 16384)
    total = sum(parts.values())

    def share(name):
        return round(100 * parts[name] / total, 1)
    assert (share("mlp"), share("full_attention"), share("head")) == (
        57.1, 15.2, 7.7)
    assert share("mamba_projections") == 9.9 and share("scan") == 0.1
    assert share("window_attention") == 0.5
    assert round(parts["full_attention"] / 2e6) == 126


def toy(say=lambda msg: None, over=None, **job):
    from chipbench.builders import phi4flash as builder
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
        assert ids.shape == labels.shape == (1, 64)
        assert (ids[:, 1:] == labels[:, :-1]).all() and ids.max() < 2048
        want = prog.reference_loss(feed, 1)
        assert set(prog.kept) == {"logits", "memory", "window", "edges",
                                  "full", "gmu", "cross"}
        assert prog.kept["logits"].shape == (64, 2048)
        assert prog.kept["edges"].shape == (2, 1, 64, 64)
        got = prog.eval_loss(feed)
        for term, tol in mix["reference_tolerance"].items():
            assert abs(got[term] - want[term]) < tol, (term, got, want)
        first = prog.step(feed)
        assert abs(first - want["loss"]) < mix["first_loss_tolerance"]
        second = prog.step(feed)
        assert np.isfinite(second) and second < first
        shapes = prog.expected_kernel_shapes()
        assert shapes["attention_passes"] == 2 and shapes["window_layers"] == 1
        assert shapes["attention_layers"] == (4 if remat else 2)
        assert shapes["flash_dims"] == shapes["window_dims"] == (1, 4, 64, 32)
        assert shapes["ce_rows"] == 64 and shapes["key_heads"] == 1
        assert prog.tokens_per_step == 64 and prog.seq == 64
        taken, fallbacks = prog.kernel_choices()
        assert not fallbacks
        lambdas = prog.model.record_lambdas(prog.ex.params)
        assert sorted(lambdas) == [15, 17, 19]
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
    assert "layers reading a kept value: scan 2, kv 2" in out
    assert "hetu_diff_attn_lambda at the end of the run" in out


def test_every_control_is_refused_and_the_program_is_not(capsys):
    from chipbench.reference import phi4flash as ref
    from chipbench.reference import phi4flash_controls as controls
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
    for name, term in (("bf16_state", "scan_probe_gap"),
                       ("subtract", "attention_gap"),
                       ("lambda_index", "attention_gap"),
                       ("sub_norm", "cross_gap"),
                       ("memory_after_gate", "gmu_gap"),
                       ("memory_skip", "scan_gap"), ("own_kv", "cross_gap"),
                       ("window_511", "window_edge"),
                       ("window_513", "window_edge"),
                       ("fp8_e4m3", "logits_gap")):
        assert term in by[name]["refused_by"], (name, by[name])
    # a state in bf16 moves nothing a short sequence shows but the probe
    assert by["bf16_state"]["refused_by"] == ["scan_probe_gap"]
    # the window's size and the cross layer's own keys leave the memory alone
    assert by["own_kv"]["gaps"]["scan_gap"] == 0
    assert by["memory_skip"]["gaps"]["window_gap"] == 0


def test_a_recomputed_toy_step_calls_each_kernel_where_it_is_due(
        monkeypatch, live_registry):
    """Lowered for a TPU (nothing compiled or run) at heads of 64, so that a
    pair is one lane tile: the full and the cross layer run ``hetu_flash_*``
    once each with whole layers recomputed (the group keeps the kernel's
    context and log-sum-exp), the window layer ``hetu_swa_*``, each Mamba
    layer ``hetu_s6_bwd`` once and ``hetu_s6_fwd`` twice (the scan's output
    is not among what a group keeps: PERF.md section 7), the convolution's
    pair likewise; no ``jax.numpy`` attention or scan in the step."""
    from conftest import kernel_calls, lowered_for_tpu
    from hetu_tpu.ops.pallas import dispatch
    chosen_before = dispatch.choices()

    def build():
        return toy(over={"hidden_size": 256, "intermediate_size": 256,
                         "assumed": {"mamba_dt_rank": 16}},
                   remat="layer", compute_dtype="bfloat16")[0]
    # 256 positions: the kernels' least
    monkeypatch.setattr(run, "load_cell", lambda name, _l=run.load_cell: (
        *_l(name)[:3], run.merge(_l(name)[3], {"toy": {"seq": 256}})))
    text = lowered_for_tpu(monkeypatch, build)
    assert kernel_calls(text, "hetu_flash_fwd") == 2
    assert kernel_calls(text, "hetu_flash_bwd") == 2
    assert kernel_calls(text, "hetu_swa_fwd") == 1
    assert kernel_calls(text, "hetu_swa_bwd") == 1
    assert kernel_calls(text, "hetu_s6_fwd") == 4
    assert kernel_calls(text, "hetu_s6_bwd") == 2
    assert kernel_calls(text, "hetu_conv_bwd") == 2
    choices = {k: n - chosen_before.get(k, 0)
               for k, n in dispatch.choices().items()}
    assert not [k for k, n in choices.items() if n and k[1] == "jnp"
                and k[0] in ("flash_attention", "selective_scan",
                             "causal_conv")], choices
    assert choices[("selective_scan", "pallas", "")] >= 2
    assert choices[("flash_attention", "pallas", "")] == 3
