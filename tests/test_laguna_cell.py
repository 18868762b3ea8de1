"""The benchmark's side of the Laguna cell on the CPU: the configuration file
against the catalog row, the operations the ``mfu`` reader credits, the
published parameter count from the uncut keys, the builder at toy size (layer
0 and one period) against the plain reference under each kind of
recomputation, the cell's rehearsal through the harness, its controls, and
its readers (``chipbench/tests/test_laguna_readers.py``, collected here)."""

import json
import os

import numpy as np
import pytest

import cells
from chipbench import flops_laguna as fl, run
from chipbench.tests.test_laguna_readers import *  # noqa: F401,F403

CELL = "laguna-xs.2.b1-s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 5, "num_experts": 32, "vocab_size": 12544,
           "layer_types": ["full_attention"] + ["sliding_attention"] * 3
           + ["full_attention"],
           "mlp_layer_types": ["dense"] + ["sparse"] * 4,
           "num_attention_heads_per_layer": [48, 64, 64, 64, 48]}
#: the family's own mechanism: the window layers' kernel pair
OWN = ("window_attn_roofline", "window_attn_block_device_ms_per_step")


def published():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(ln) for ln in open(CATALOG) if ln.strip()]
    return next(r for r in rows if r["name"] == "Laguna-XS.2")


def test_configuration_file_holds_the_published_keys():
    row = published()
    _, entry, config, _ = run.load_cell(CELL)
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key, value in row["config"].items():
        assert config[key] == REDUCED.get(key, value), key
    dep = config["deployment"]
    for key in REDUCED:
        assert dep[key] == row["config"][key], key
        assert key in config["reduced_why"], key
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert config[key] == row["config"][key][:5], key
    assert dep["chips_sharing_a_layer"] * config["num_experts"] == 256
    assert dep["vocabulary_divided"] * config["vocab_size"] == 100352
    assert dep["pipeline_stages"] * dep["layers_a_stage"] == 40
    assert dep["experts_held"] == [0, 32] and entry["chips"] == 1
    # every width as published
    assert (config["hidden_size"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["sliding_window"], config["num_experts_per_tok"],
            config["num_key_value_heads"]) == (2048, 128, 8192, 512, 512, 8,
                                               8)


def table_part(bench):
    cells.declared(bench, CELL, own=OWN)


def test_benchmark_entries():
    bench, cell, config, mix = run.load_cell(CELL)
    table_part(bench)
    assert cell["config"] == "laguna-xs.2-pretrain"
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key in ("window", "no_qk_norm", "gate_a_head", "rotary", "router",
                "shared_expert", "loss", "job", "remat"):
        assert key in config["assumed"], key
    assert set(config["not_modelled"]) == {"kv_cache", "long_context"}
    assert set(mix["reference_tolerance"]) == {
        "ce", "logits_gap", "window_gap", "window_edge", "full_gap",
        "routed_gap", "routing_share", "dropped", "full_nodes",
        "window_nodes"}
    assert mix["trace_seconds"] == 4 and mix["warm_steps"] == 3


def test_flops_and_parameters_of_the_cut_configuration():
    """About 802 M forward operations a token at this cut (ISSUE 51): the
    three window layers 40% (their kernels' band 6%), the two full layers 54%
    (attention's products 25%, the dense MLP 13%), the head 6%, the held
    experts and the shared one 7%; 19.7 T a step."""
    _, _, c, _ = run.load_cell(CELL)
    parts = fl.forward_flops_per_token(c, 8192, 8 * 32 / 256)
    total = sum(parts.values())
    assert abs(total - 802e6) < 5e6
    assert abs(3 * total * 8192 - 19.7e12) < 0.1e12

    def share(*names):
        return round(100 * sum(parts[n] for n in names) / total)
    assert share("window_attention") == 6 and share("full_attention") == 25
    assert share("dense_mlp") == 13 and share("head") == 6
    assert share("router", "shared_expert", "held_experts") == 7
    # by layer: a window layer its projections, band and expert block
    experts = sum(parts[n] for n in ("router", "shared_expert",
                                     "held_experts"))
    window = (parts["window_projections"] + parts["window_attention"]
              + 3 * experts / 4)
    full = (parts["full_projections"] + parts["full_attention"]
            + parts["dense_mlp"] + experts / 4)
    assert round(100 * window / total) == 40
    assert round(100 * full / total) == 54
    # all earlier keys where the band is 49 M a token: 403 M, 44% more a step
    every = 3 * 4.0 * 64 * 128 * fl.window_pairs(8192, 8192) / 8192
    assert round(parts["window_attention"] / 1e6) == 49
    assert round(every / 1e6) == 403
    assert round(100 * (every - parts["window_attention"]) / total) == 44


def parameters(c, experts, vocab, layers):
    """Parameters of the first ``layers`` layers from the keys alone."""
    h, d, kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    f = c["moe_intermediate_size"]
    total = 2 * vocab * h + h
    for i in range(layers):
        heads = c["num_attention_heads_per_layer"][i]
        total += 2 * h * heads * d + 2 * h * kv * d + h * heads + 2 * h
        if c["mlp_layer_types"][i] == "dense":
            total += 3 * h * c["intermediate_size"]
        else:
            total += (3 * h * f * experts + h * 256
                      + 3 * h * c["shared_expert_intermediate_size"])
    return total


def test_parameters_of_the_cut_and_of_the_published_model():
    _, _, c, _ = run.load_cell(CELL)
    dep = c["deployment"]
    cut = parameters(c, 32, 12544, 5)
    assert cut == 691_623_936
    assert dep["parameters_m"] == round(cut / 1e6, 1) == 691.6
    assert dep["resident_gib"] == round(cut * 12 / 2 ** 30, 2) == 7.73
    whole = parameters(dict(c, **{k: dep[k] for k in (
        "mlp_layer_types", "num_attention_heads_per_layer")}), 256, 100352,
        40)
    gates = sum(2048 * h for h in dep["num_attention_heads_per_layer"])
    # the published "33.4B": 33.44 G with a gate a head; an elementwise
    # gate (2048 x heads x 128) would read 34.1
    assert round(whole / 1e9, 2) == 33.44
    assert round((whole + 127 * gates) / 1e9, 1) == 34.1


def toy(say=lambda msg: None, **job):
    from chipbench.builders import laguna as builder
    _, _, config, mix = run.load_cell(CELL)
    config = run.merge(run.merge(config, config["toy"]), {"job": job})
    mix = run.merge(mix, mix["toy"])
    return builder.build(config, mix, 2 ** 31 + 3, say), mix


@pytest.mark.parametrize("remat", [None, "window", "layer"])
def test_the_cells_builder_at_toy_size(remat):
    prog, mix = toy(remat=remat)
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        want = prog.reference_loss(feed, 1)
        got = prog.eval_loss(feed)
        for term, tol in mix["reference_tolerance"].items():
            if not term.endswith("_nodes"):     # counted with telemetry on
                assert abs(got[term] - want[term]) < tol, (term, got, want)
        first = prog.step(feed)
        assert abs(first - want["loss"]) < mix["first_loss_tolerance"]
        second = prog.step(feed)
        assert np.isfinite(second) and second != first
        shapes = prog.expected_kernel_shapes()
        assert shapes["attention_passes"] == 2
        assert shapes["window_layers"] == 3
        assert shapes["attention_layers"] == (4 if remat == "layer" else 2)
        assert shapes["flash_dims"] == (1, 6, 64, 16)
        assert shapes["window_dims"] == (1, 8, 64, 16)
        assert prog.n_layers == 4 and prog.probed == (1, 4)
        assert abs(prog.params_m * 1e6 - parameters(
            prog.config, 4, 2048, 5) + 4 * 64 * (256 - 16)) < 1
    finally:
        prog.close()


def test_the_lowered_train_step_runs_both_kernel_pairs(monkeypatch):
    """At heads of the kernels' width: the window layers through
    ``hetu_swa_*`` three times a step, the full layers through
    ``hetu_flash_*`` twice."""
    from conftest import kernel_calls, lowered_for_tpu

    def build():
        from chipbench.builders import laguna as builder
        _, _, config, mix = run.load_cell(CELL)
        config = run.merge(config, config["toy"])
        config = run.merge(config, {"head_dim": 128, "sliding_window": 128})
        mix = run.merge(run.merge(mix, mix["toy"]), {"seq": 256})
        return builder.build(config, mix, 3, lambda msg: None)
    text = lowered_for_tpu(monkeypatch, build)
    assert kernel_calls(text, "hetu_swa_fwd") == 3
    assert kernel_calls(text, "hetu_swa_bwd") == 3
    assert kernel_calls(text, "hetu_flash_fwd") == 2
    assert kernel_calls(text, "hetu_flash_bwd") == 2


def test_cell_rehearses(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                   "--seconds", "2", "--trace", "0"], rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "WRONG" not in out and "routing_share" in out
    assert "window_gap" in out and "full_gap" in out
    assert "full_nodes 2.0" in out and "window_nodes 3.0" in out
    assert "not finite: 0\n" in out


def test_the_controls_rehearse(capsys):
    """Every control is refused by the toy's limits and the program passes
    them (``chipbench/reference/laguna_controls.py``)."""
    from chipbench.reference import laguna_controls
    rc = laguna_controls.main(
        ["--seed", "5", "--rehearsal"] + [
            arg for name in ("window_511", "window_513", "attention_factor",
                             "scaling_factor", "norm_topk", "all_experts",
                             "fp8_e4m3") for arg in ("--control", name)])
    out = capsys.readouterr().out
    assert rc == 0, out
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    assert [ln["control"] for ln in lines][-1] == "program"
    assert lines[-1]["correct"] and not any(ln["correct"]
                                            for ln in lines[:-1])
