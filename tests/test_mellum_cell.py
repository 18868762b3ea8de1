"""The benchmark's side of the Mellum cell on the CPU: the configuration file
against the catalog row, the operations the ``mfu`` reader credits, the
published parameter count and the chip's share from the keys, the builder at
toy size under its strategy on four devices against the plain reference of the
UNCUT model, the lowered step's kernels and collectives, the cell's rehearsal
through the harness, its controls, and its readers
(``chipbench/tests/test_mellum_readers.py``, collected here)."""

import json
import os
import re

import numpy as np
import pytest

import cells
from chipbench import flops_mellum as fl, run
from chipbench.tests.test_mellum_readers import *  # noqa: F401,F403

CELL = "mellum2-12b-a2.5b.ep4-b4-s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 4}
#: the family's own mechanism: the experts' exchange, read in the MoE block
OWN = ("moe_block_device_ms_per_step", "moe_experts_roofline")


def published():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(ln) for ln in open(CATALOG) if ln.strip()]
    return next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")


def test_configuration_file_holds_the_published_keys():
    row = published()
    _, entry, config, _ = run.load_cell(CELL)
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == sorted(REDUCED)
    lists = ("layer_types", "mlp_layer_types")
    for key, value in row["config"].items():
        want = value[:4] if key in lists else REDUCED.get(key, value)
        assert config[key] == want, key
    dep = config["deployment"]
    for key in lists + ("num_hidden_layers",):
        assert dep[key] == row["config"][key], key
    assert "num_hidden_layers" in config["reduced_why"]
    assert dep["chips_sharing_a_layer"] * dep["experts_a_chip"] == 64
    assert dep["chips_sharing_a_layer"] * dep["vocabulary_rows_a_chip"] == (
        config["vocab_size"])
    assert dep["pipeline_stages"] * dep["layers_a_stage"] == 28
    assert entry["chips"] == dep["chips_sharing_a_layer"] == 4
    # every width as published, and nothing but the depth cut
    assert (config["hidden_size"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["sliding_window"], config["num_experts"],
            config["num_experts_per_tok"], config["num_key_value_heads"],
            config["vocab_size"]) == (2304, 128, 7168, 896, 1024, 64, 8, 4,
                                      98304)


def table_part(bench):
    cells.declared(bench, CELL, own=OWN)


def test_benchmark_entries():
    bench, cell, config, mix = run.load_cell(CELL)
    table_part(bench)
    assert cell["config"] == "mellum2-12b-a2.5b-pretrain"
    for key in ("no_qk_norm", "window", "rotary", "router", "dense_mlp",
                "loss", "job", "remat"):
        assert key in config["assumed"], key
    assert set(config["not_modelled"]) == {"mtp_head", "kv_cache",
                                           "long_context", "pipeline"}
    assert set(mix["reference_tolerance"]) == {
        "ce", "logits_gap", "window_gap", "window_edge", "full_gap",
        "routed_gap", "routing_share", "dropped", "full_nodes",
        "window_nodes"}
    assert mix["trace_seconds"] == 4 and mix["warm_steps"] == 3
    assert mix["strategy"] == {"name": "ExpertParallel",
                               "kwargs": {"ndev": 4}}
    # the compared sequences lie on two chips
    assert len({b * 4 // mix["batch"]
                for b in mix["compared_sequences"]}) >= 2


def test_flops_of_the_cut_configuration():
    """1,135 M forward operations a token (ISSUE 72): experts 35%, attention
    (projections and pairs) 25%, the head 40% where the whole model has 9%;
    9.3 T forward a chip and step."""
    _, _, c, _ = run.load_cell(CELL)
    parts = fl.forward_flops_per_token(c, 8192)
    total = sum(parts.values())
    assert abs(total - 1135e6) < 2e6
    assert abs(total * 8192 - 9.3e12) < 0.05e12

    def share(of, *names):
        return round(100 * sum(of[n] for n in names) / sum(of.values()))
    assert share(parts, "experts", "router") == 35
    assert share(parts, "projections", "full_attention",
                 "window_attention") == 25
    assert share(parts, "head") == 40
    whole = fl.forward_flops_per_token(dict(
        c, num_hidden_layers=28, layer_types=c["deployment"]["layer_types"]),
        8192)
    assert share(whole, "head") == 9
    # one collective brings a chip the other three chips' tokens
    assert fl.exchange_call(8192, 2304, 8, 4)["scatter"] == 113_246_208


def test_parameters_of_the_chips_share_and_of_the_published_model():
    _, _, c, _ = run.load_cell(CELL)
    dep = c["deployment"]
    h, d = c["hidden_size"], c["head_dim"]
    attention = h * d * 2 * (c["num_attention_heads"]
                             + c["num_key_value_heads"])
    expert = 3 * h * c["moe_intermediate_size"]
    whole_layer = attention + h * 64 + 64 * expert + 2 * h
    a_chip = (4 * (attention + h * 64 + 2 * h + dep["experts_a_chip"] * expert)
              + 2 * dep["vocabulary_rows_a_chip"] * h + h)
    assert dep["parameters_m_a_chip"] == round(a_chip / 1e6, 1) == 595.2
    assert dep["resident_gb_a_chip"] == round(a_chip * 12 / 1e9, 2) == 7.14
    published_g = (28 * whole_layer + 2 * c["vocab_size"] * h + h) / 1e9
    assert round(published_g, 2) == 12.15


def test_the_cells_builder_at_toy_size(traced):
    """The program the readers' tests compiled (whole layers recomputed, as
    in the cell) under its strategy on four devices: what it states of its
    kernels and where its state lies.  Its comparison with the reference of
    the uncut model runs through the harness (``test_cell_rehearses``)."""
    prog = traced[0]["program"]
    shapes = prog.expected_kernel_shapes()
    assert shapes["attention_passes"] == 1
    assert shapes["window_layers"] == 3 and shapes["attention_layers"] == 2
    # one chip's shard: one of the four sequences
    assert shapes["flash_dims"] == shapes["window_dims"] == (1, 8, 64, 16)
    assert shapes["ce_rows"] == 64 and shapes["moe_pairs"] == 64 * 4
    assert prog.n_layers == 4 and prog.probed == (0, 3)
    assert prog.ranks == 4 and len(prog.devices) == 4
    # every expert stack, the embedding and the head are divided
    params = prog.ex.params
    moe = prog.model.moe_layers()[0]
    assert params[moe.w1.name].addressable_shards[0].data.shape[0] == 4
    assert params[prog.model.lm_head.weight.name].addressable_shards[
        0].data.shape == (64, 512)
    assert params[prog.model.model.embed.weight.name].addressable_shards[
        0].data.shape == (512, 64)
    assert moe.last_op.exchange["scatter"] == 3 * 64 * 64 * 4
    assert moe.load_var.shape == (4, 16)


def test_the_lowered_train_step_runs_the_kernels_per_shard(monkeypatch):
    """Lowered for a TPU under the strategy, at heads and widths the kernels
    take: the window layers through ``hetu_swa_*`` three times a step, the
    full layer through ``hetu_flash_*`` once, the rotary pair, the grouped
    products, the row sums and the selection as Mosaic calls inside
    ``shard_map``, and each expert layer's exchange as an all-gather of the
    tokens and a reduce-scatter of the sums, forward and backward."""
    from conftest import kernel_calls, lowered_for_tpu

    def build():
        from chipbench.builders import mellum as builder
        _, _, config, mix = run.load_cell(CELL)
        config = run.merge(config, config["toy"])
        config = run.merge(config, {
            "head_dim": 128, "sliding_window": 128, "hidden_size": 128,
            "moe_intermediate_size": 128, "num_attention_heads": 2,
            "num_key_value_heads": 1, "job": {"remat": None}})
        mix = run.merge(run.merge(mix, mix["toy"]), {"seq": 256})
        return builder.build(config, mix, 3, lambda msg: None)
    text = lowered_for_tpu(monkeypatch, build)
    assert kernel_calls(text, "hetu_swa_fwd") == 3
    assert kernel_calls(text, "hetu_swa_bwd") == 3
    assert kernel_calls(text, "hetu_flash_fwd") == 1
    assert kernel_calls(text, "hetu_flash_bwd") == 1
    for kernel in ("hetu_rope_fwd", "hetu_rope_bwd", "hetu_moe_gmm_fwd",
                   "hetu_moe_gmm_dx", "hetu_moe_gmm_dw", "hetu_moe_rows_sum",
                   "hetu_moe_select", "hetu_softmax_ce_fwd"):
        assert f'kernel_name = "{kernel}"' in text, kernel
    # a layer scatters its sums forward and, backward, the gradients of the
    # gathered tokens and weights; it gathers at least the tokens forward and
    # the sums' gradient backward
    assert text.count("reduce_scatter") == 3 * 4
    assert text.count("all_gather") >= 2 * 4


def test_cell_rehearses(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                   "--seconds", "2", "--trace", "0"], rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "WRONG" not in out and "routing_share" in out
    assert "window_gap" in out and "full_gap" in out
    assert "full_nodes 1.0" in out and "window_nodes 3.0" in out
    assert "not finite: 0\n" in out and "expert axis of 4" in out
    # whole layers are recomputed: every counted step and layer gathered
    # three times and scattered twice
    from hetu_tpu.ops.moe import exchange_bytes, exchange_bytes_a_step
    a_step = exchange_bytes_a_step(exchange_bytes(64, 64, 4, 4, 4), 2)
    gather, scatter = map(int, re.search(
        r"gather (\d+), scatter (\d+)", out).groups())
    assert gather and gather % a_step["gather"] == 0
    assert gather // a_step["gather"] == scatter // a_step["scatter"]
    assert scatter % a_step["scatter"] == 0


def test_the_controls_rehearse(capsys):
    """What an exchange gets wrong is refused by the toy's limits and the
    program passes them (``chipbench/reference/mellum_controls.py``; the
    other controls run on the chip and in ``test_mellum_reference.py``)."""
    from chipbench.reference import mellum_controls
    rc = mellum_controls.main(
        ["--seed", "5", "--rehearsal"] + [
            arg for name in ("rank_offset", "returned_order", "part_left_out")
            for arg in ("--control", name)])
    out = capsys.readouterr().out
    assert rc == 0, out
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    assert [ln["control"] for ln in lines][-1] == "program"
    assert lines[-1]["correct"] and not any(ln["correct"]
                                            for ln in lines[:-1])
