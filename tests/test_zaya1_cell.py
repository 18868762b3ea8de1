"""The benchmark's side of the ZAYA1 cell on the CPU: the configuration file
against the catalog row (nothing but ``reduced`` differs; ``assumed`` names
the sibling rows), the operations and the mixing's bytes the readers credit,
the parameter count of the built program, the builder at toy size against the
plain reference, the cell's rehearsal through the harness, its controls, and
its readers (``chipbench/tests/test_zaya1_readers.py``, collected here)."""

import json
import os

import numpy as np
import pytest

import cells
from chipbench import flops_zaya1 as fz, run
from chipbench.tests.test_zaya1_readers import *  # noqa: F401,F403

CELL = "zaya1-8b.b1-s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 5, "layer_types": ["hybrid"] * 5,
           "num_experts": 8, "vocab_size": 32784}
SIBLINGS = ("ZAYA1-base", "ZAYA1-VL-8B")
#: the family's own mechanism: compressed convolutional attention, and the
#: router's skip
OWN = ("cca_block_device_ms_per_step", "cca_mix_roofline",
       "moe_skipped_share")


def rows():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    found = [json.loads(ln) for ln in open(CATALOG) if ln.strip()]
    return {r["name"]: r for r in found}


def test_configuration_file_holds_the_published_keys():
    row = rows()["ZAYA1-8B"]
    _, entry, config, _ = run.load_cell(CELL)
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key, value in row["config"].items():
        assert config[key] == REDUCED.get(key, value), key
    dep = config["deployment"]
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert dep[key] == row["config"][key], key
    assert set(config["reduced_why"]) == set(REDUCED)
    assert dep["chips_sharing_a_layer"] * config["num_experts"] == 16
    assert dep["vocabulary_divided"] * config["vocab_size"] == 262272
    assert dep["pipeline_stages"] * dep["layers_a_stage"] == 40
    assert dep["layers_a_stage"] == config["num_hidden_layers"]
    assert dep["experts_held"] == [0, 8] and entry["chips"] == 1
    # the floors: four layers of a period of one, 8 experts, vocabulary / 8
    assert config["num_hidden_layers"] >= 4
    assert config["num_experts"] >= 8 and dep["vocabulary_divided"] <= 8
    # every width, both tap counts and the head counts as published
    assert (config["hidden_size"], config["moe_intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["cca_time0"], config["cca_time1"],
            config["router_hidden_size"], config["num_experts_per_tok"],
            config["partial_rotary_factor"]) == (2048, 2048, 8, 2, 128, 2, 2,
                                                 256, 1, 0.5)


def test_the_sibling_rows_carry_what_this_row_dropped():
    """``zaya_use_eda``, ``zaya_use_mod`` and ``scale_residual_merge`` are in
    neither this row's ``config``: the file takes them from the two sibling
    rows, whose values agree, and says so under ``assumed``."""
    catalog = rows()
    _, _, config, _ = run.load_cell(CELL)
    taken = config["sibling_rows"]
    for key in ("zaya_use_eda", "zaya_use_mod", "scale_residual_merge"):
        assert key not in catalog["ZAYA1-8B"]["config"], key
        for name in SIBLINGS:
            assert catalog[name]["config"][key] is taken[key] is True
            assert name in config["assumed"][key], (key, name)
    assert catalog["ZAYA1-VL-8B"]["config"]["zaya_mlp_expansion"] == taken[
        "zaya_mlp_expansion"] == config["router_hidden_size"]
    for name in SIBLINGS:
        assert catalog[name]["config"]["cca"] is taken["cca"] is True


def table_part(bench):
    mine = cells.declared(bench, CELL, own=OWN)
    # no dense FFN: the row hetu_mlp would read nothing in this cell
    assert not [n for n in mine if n.startswith("mlp_block")]


def test_benchmark_entries():
    bench, cell, config, mix = run.load_cell(CELL)
    table_part(bench)
    assert cell["config"] == "zaya1-8b-pretrain"
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key in ("zaya_use_eda", "zaya_use_mod", "scale_residual_merge",
                "router_mlp", "router_bias", "value_halves", "temperature",
                "rotary", "qk_mean", "convolutions", "initial_values"):
        assert key in config["assumed"], key
    assert set(config["not_modelled"]) == {
        "serving", "hybrid_sliding", "balancing_controller", "pipeline"}
    assert set(mix["reference_tolerance"]) == {
        "ce", "logits_gap", "attention_gap", "cca_qk_gap", "cca_norm_gap",
        "router_state_gap", "routing_mismatch", "skipped", "skipped_output",
        "dropped"}
    for key in ("batch", "seq", "mask_fraction", "ring", "warm_steps",
                "strategy", "trace_seconds"):
        assert mix[key] == {"batch": 1, "seq": 8192, "mask_fraction": 1.0,
                            "ring": 8, "warm_steps": 3, "strategy": None,
                            "trace_seconds": 4}[key], key


def test_flops_and_bytes_of_the_cut_configuration():
    """About 340 M forward operations a token at this cut where 8 of 17
    choices are held: the tied head 40%, attention's products 25%, the five
    latent products 15%, the experts 17%, the router 2%, the head-mixing taps
    1%; and the mixing's least bytes: 6,912 values a token and sublayer."""
    _, _, c, _ = run.load_cell(CELL)
    parts = fz.forward_flops_per_token(c, 8192, 8 / 17)
    total = sum(parts.values())
    assert abs(total - 340e6) < 1e6

    def share(name):
        return round(100 * parts[name] / total)
    assert share("head") == 40 and share("causal_attention") == 25
    assert share("cca_projections") == 15 and share("held_experts") == 17
    assert share("router") == 2 and share("cca_head_mixing") == 1
    ops, nbytes = fz.cca_sublayer(c, 8192)
    assert nbytes == 6912 * 8192 * 2
    assert ops == 3 * 8192 * parts["cca_head_mixing"] / 5


def test_the_built_programs_parameter_count_is_the_deployments():
    """The graph at the published widths (no executor: nothing is
    allocated): its variables hold ``deployment.parameters_m``, which is
    ISSUE 58's table less the first layer's gamma (256: no state comes in)
    plus the five load vectors (200)."""
    import hetu_tpu as ht
    from hetu_tpu.graph.node import graph_variables
    from hetu_tpu.models import Zaya1Config, Zaya1ForCausalLM
    from chipbench.builders.zaya1 import HF_KEYS
    _, _, config, mix = run.load_cell(CELL)
    dep = config["deployment"]
    c = Zaya1Config(seq_len=mix["seq"], num_experts=dep["num_experts"],
                    experts_held=tuple(dep["experts_held"]),
                    **{k: config[k] for k in HF_KEYS})
    ids = ht.placeholder_op("zcount_ids", (1, mix["seq"]), dtype=np.int32)
    labels = ht.placeholder_op("zcount_labels", (1, mix["seq"]),
                               dtype=np.int32)
    model = Zaya1ForCausalLM(c, name="zaya1count")
    loss = model.loss(ids, labels)
    total = sum(int(np.prod(v.shape)) for v in graph_variables(
        [loss] + model.moe_loads(), trainable_only=False))
    assert total == 5 * 106920467 + 67143680 - 256 + 200 == 601745959
    assert round(total / 1e6, 1) == dep["parameters_m"]
    assert round(total * 12 / 2 ** 30, 2) == dep["resident_gib"]


def toy(say=lambda msg: None, **job):
    from chipbench.builders import zaya1 as builder
    _, _, config, mix = run.load_cell(CELL)
    config = run.merge(run.merge(config, config["toy"]), {"job": job})
    mix = run.merge(mix, mix["toy"])
    return builder.build(config, mix, 2 ** 31 + 3, say), mix


@pytest.mark.parametrize("remat", ["layer", False])
def test_the_cells_builder_at_toy_size(remat):
    prog, mix = toy(remat=remat)
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        want = prog.reference_loss(feed, 1)
        got = prog.eval_loss(feed)
        for term, tol in mix["reference_tolerance"].items():
            assert abs(got[term] - want[term]) < tol, (term, got, want)
        assert 0 < want["skipped"] < 0.6
        first = prog.step(feed)
        assert abs(first - want["loss"]) < mix["first_loss_tolerance"]
        second = prog.step(feed)
        assert np.isfinite(second) and second != first
        # the one value a step hands out: the loss, then every load and bias
        # as the step left them
        stats = prog.ex.run("train", feed_dict=feed,
                            convert_to_numpy_ret_vals=True)[0]
        state = [np.asarray(prog.ex.params[name], np.float32).ravel()
                 for name, _ in prog.stat_vars]
        assert len(state) == 6 and state[0].sum() > 0
        assert np.isfinite(stats[0]) and stats[0] < second
        np.testing.assert_array_equal(stats[1:], np.concatenate(state))
        shapes = prog.expected_kernel_shapes()
        assert shapes["attention_passes"] == 3
        assert shapes["attention_layers"] == (6 if remat else 3)
        assert shapes["flash_dims"] == (1, 4, 128, 16)
        assert shapes["kv_heads"] == 2 and shapes["cca_sublayers"] == 3
        assert prog.n_layers == 3 and prog.PROBED == 0
        taken, fallbacks = prog.kernel_choices()
        assert not fallbacks
    finally:
        prog.close()


def test_the_depthwise_taps_reason_is_explained_and_no_other():
    from chipbench.builders.zaya1 import EXPLAINED
    assert EXPLAINED == {("causal_conv", "jnp", "act:none")}


def test_cell_rehearses(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                   "--seconds", "2", "--trace", "0"], rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "WRONG" not in out and "routing_mismatch" in out
    assert "cca_qk_gap" in out and "router_state_gap" in out
    assert "not finite: 0\n" in out


def test_every_control_is_refused_and_the_program_is_not(capsys):
    from chipbench.reference import zaya1_controls as controls
    rc = controls.main(["--seed", "5", "--rehearsal"])
    out = capsys.readouterr().out
    assert rc == 0, out
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    by = {ln["control"]: ln for ln in lines}
    assert set(by) == {"bf16", "fp8_e4m3", "fp8_e5m2", "taps_in_time",
                       "head_mix", "qk_mean", "value_shift", "temperature",
                       "rotary_all", "eda", "skip_choice", "residual_scale",
                       "program"}
    assert by["program"]["correct"]
    for name in ("taps_in_time", "head_mix", "qk_mean", "temperature"):
        assert "cca_qk_gap" in by[name]["refused_by"], name
    assert "cca_norm_gap" in by["temperature"]["refused_by"]
    for name in ("value_shift", "rotary_all"):
        assert "attention_gap" in by[name]["refused_by"], name
        assert "cca_qk_gap" not in by[name]["refused_by"], name
    assert "router_state_gap" in by["eda"]["refused_by"]
    assert "attention_gap" not in by["eda"]["refused_by"]
    assert "skipped_output" in by["skip_choice"]["refused_by"]
    assert by["skip_choice"]["gaps"]["skipped_output"] > 0.1
    assert "logits_gap" in by["residual_scale"]["refused_by"]
    for name in ("fp8_e4m3", "fp8_e5m2"):
        assert "logits_gap" in by[name]["refused_by"]
