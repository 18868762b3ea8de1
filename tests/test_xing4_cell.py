"""The benchmark's side of the Xing4.0 cell on the CPU: the configuration file
against the catalog row (nothing but ``reduced`` differs), the operations and
the hyper-connections' bytes the readers credit, the parameter count of the
built program, the builder at toy size against the plain reference, the cell's
rehearsal through the harness, its controls, and its readers
(``chipbench/tests/test_xing4_readers.py``, collected here)."""

import json
import os

import numpy as np
import pytest

import cells
from chipbench import flops_xing4 as fl, run
from chipbench.tests.test_xing4_readers import *  # noqa: F401,F403

CELL = "xing4.0-29b-a4b.b1-s4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 8, "vocab_size": 16384}
#: the family's own mechanism: hyper-connections and the MTP depth
OWN = ("hc_block_device_ms_per_step", "hc_mix_roofline",
       "mtp_block_device_ms_per_step")


def published():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(ln) for ln in open(CATALOG) if ln.strip()]
    return next(r for r in rows if r["name"] == "Xing4.0-29B-A4B")


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
    assert dep["chips_sharing_a_layer"] * config["n_routed_experts"] == 64
    assert dep["vocabulary_divided"] * config["vocab_size"] == 131072
    assert dep["pipeline_stages"] * dep["layers_a_stage"] == 40
    assert dep["experts_held"] == [0, 8] and entry["chips"] == 1
    # the floors: one dense + four expert layers, 8 experts, vocabulary / 8
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8 and dep["vocabulary_divided"] <= 8
    # every width as published
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["q_lora_rank"],
            config["kv_lora_rank"], config["qk_nope_head_dim"],
            config["qk_rope_head_dim"], config["v_head_dim"],
            config["num_experts_per_tok"], config["hc_mult"],
            config["hc_sinkhorn_iters"]) == (3584, 9216, 1024, 768, 512, 128,
                                             64, 128, 4, 4, 20)


def table_part(bench):
    cells.declared(bench, CELL, own=OWN)


def test_benchmark_entries():
    bench, cell, config, mix = run.load_cell(CELL)
    table_part(bench)
    assert cell["config"] == "xing4.0-29b-a4b-pretrain"
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key in ("streams", "hc_norm", "hc_maps", "sinkhorn_order",
                "hc_initial_values", "mla", "yarn", "router",
                "shared_expert", "mtp", "dense_layers"):
        assert key in config["assumed"], key
    assert set(config["not_modelled"]) == {"serving", "ep_size"}
    assert set(mix["reference_tolerance"]) == {
        "ce", "mtp", "logits_gap", "attention_gap", "hc_res_gap",
        "hc_sums_gap", "dropped", "routing_mismatch"}
    for key in ("batch", "seq", "mask_fraction", "ring", "warm_steps",
                "strategy", "trace_seconds"):
        assert mix[key] == {"batch": 1, "seq": 4096, "mask_fraction": 1.0,
                            "ring": 8, "warm_steps": 3, "strategy": None,
                            "trace_seconds": 4}[key], key


def test_flops_and_bytes_of_the_cut_configuration():
    """About 1,255 M forward operations a token at this cut: MLA's
    projections 27%, attention's products 20%, the two head passes 19%, the
    dense MLP 16%, five expert blocks 13%, the MTP combine 4%, the
    hyper-connections under 1%; and their bytes: 33 stream-widths a token and
    sublayer application."""
    _, _, c, _ = run.load_cell(CELL)
    assert fl.layer_counts(c) == (6, 1, 5, 12)
    parts = fl.forward_flops_per_token(c, 4096, 4 * 8 / 64)
    total = sum(parts.values())
    assert abs(total - 1255e6) < 2e6

    def share(*names):
        return round(100 * sum(parts[n] for n in names) / total)
    assert share("attention_projections") == 27
    assert share("causal_attention") == 20
    assert share("head") == 19 and share("dense_mlp") == 16
    assert share("router", "shared_expert", "held_experts") == 13
    assert share("mtp_combine") == 4
    assert parts["hyper_connections"] / total < 0.01
    ops, nbytes = fl.hc_sublayer(c, 4096)
    assert nbytes == (7 * 4 + 5) * 3584 * 4096 * 2
    assert ops == 3 * 4096 * parts["hyper_connections"] / 12
    fwd, _ = fl.flash_pass("forward", 32, 4096, 192, 128)
    assert fwd == 2.0 * 32 * 4096 ** 2 * (192 + 128)


def test_the_built_programs_parameter_count_is_the_deployments():
    """The graph at the published widths (no executor: nothing is
    allocated): its variables hold ``deployment.parameters_m``."""
    import hetu_tpu as ht
    from hetu_tpu.graph.node import graph_variables
    from hetu_tpu.models import Xing4Config, Xing4ForCausalLM
    from chipbench.builders.xing4 import HF_KEYS
    _, _, config, mix = run.load_cell(CELL)
    dep = config["deployment"]
    c = Xing4Config(seq_len=mix["seq"],
                    n_routed_experts=dep["n_routed_experts"],
                    experts_held=tuple(dep["experts_held"]),
                    **{k: config[k] for k in HF_KEYS})
    ids = ht.placeholder_op("count_ids", (1, mix["seq"]), dtype=np.int32)
    labels = ht.placeholder_op("count_labels", (1, mix["seq"]),
                               dtype=np.int32)
    loss = Xing4ForCausalLM(c, name="xing4count").loss(ids, labels)
    total = sum(int(np.prod(v.shape))
                for v in graph_variables([loss], trainable_only=False))
    assert total == 913473828
    assert round(total / 1e6, 1) == dep["parameters_m"]
    assert round(total * 12 / 2 ** 30, 2) == dep["resident_gib"]


def toy(say=lambda msg: None, **job):
    from chipbench.builders import xing4 as builder
    _, _, config, mix = run.load_cell(CELL)
    config = run.merge(run.merge(config, config["toy"]), {"job": job})
    mix = run.merge(mix, mix["toy"])
    return builder.build(config, mix, 2 ** 31 + 3, say), mix


@pytest.mark.parametrize("remat", ["layer", None])
def test_the_cells_builder_at_toy_size(remat):
    prog, mix = toy(remat=remat)
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        want = prog.reference_loss(feed, 1)
        got = prog.eval_loss(feed)
        for term, tol in mix["reference_tolerance"].items():
            assert abs(got[term] - want[term]) < tol, (term, got, want)
        first = prog.step(feed)
        assert abs(first - want["loss"]) < mix["first_loss_tolerance"]
        second = prog.step(feed)
        assert np.isfinite(second) and second != first
        shapes = prog.expected_kernel_shapes()
        assert shapes["attention_passes"] == 3
        assert shapes["attention_layers"] == (6 if remat == "layer" else 3)
        assert shapes["flash_dims"] == (1, 2, 64, 32)
        assert shapes["score_dim"] == 48 and shapes["hc_sublayers"] == 6
        assert prog.n_layers == 2 and prog.probed_layer == 1
    finally:
        prog.close()


def test_cell_rehearses(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                   "--seconds", "2", "--trace", "0"], rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "WRONG" not in out and "routing_mismatch" in out
    assert "hc_res_gap" in out and "mtp" in out
    assert "not finite: 0\n" in out


def test_every_control_is_refused_and_the_program_is_not(capsys):
    from chipbench.reference import xing4_controls as controls
    rc = controls.main(["--seed", "5", "--rehearsal"])
    out = capsys.readouterr().out
    assert rc == 0, out
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    by = {ln["control"]: ln for ln in lines}
    assert set(by) == {"bf16", "fp8_e4m3", "fp8_e5m2", "sinkhorn_2", "clamp",
                       "mscale", "mtp_shift", "program"}
    assert by["program"]["correct"]
    assert "hc_sums_gap" in by["sinkhorn_2"]["refused_by"]
    assert "hc_res_gap" in by["clamp"]["refused_by"]
    assert "attention_gap" in by["mscale"]["refused_by"]
    assert by["mtp_shift"]["refused_by"] == ["mtp", "first_loss"]
    for name in ("fp8_e4m3", "fp8_e5m2"):
        assert "logits_gap" in by[name]["refused_by"]
