"""The benchmark's side of the SDAR cell on the CPU: the configuration file
against the catalog row (nothing but ``reduced`` differs; ``assumed`` holds
what the row's ``not_given`` names), the operations the readers credit, the
parameter count of the built program, the builder at toy size against the
plain reference, the cell's rehearsal through the harness, its controls, and
its readers (``chipbench/tests/test_sdar_readers.py``, collected here)."""

import json
import os

import numpy as np
import pytest

import cells
from chipbench import flops_sdar as fs, run
from chipbench.tests.test_sdar_readers import *  # noqa: F401,F403

CELL = "sdar-30b-a3b.b1-s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 6, "num_experts": 16, "vocab_size": 18992}
#: the family's own mechanism: the masked share of a block-diffusion batch
OWN = ("diffusion_masked_share",)


def row():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    found = [json.loads(ln) for ln in open(CATALOG) if ln.strip()]
    return next(r for r in found if r["name"] == "SDAR-30B-A3B-Chat")


def test_configuration_file_holds_the_published_keys():
    published = row()
    _, entry, config, _ = run.load_cell(CELL)
    assert config["source"] == published["source_url"]
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key, value in published["config"].items():
        assert config[key] == REDUCED.get(key, value), key
    dep = config["deployment"]
    for key in REDUCED:
        assert dep[key] == published["config"][key], key
    assert set(config["reduced_why"]) == set(REDUCED)
    assert dep["expert_parallel"] * config["num_experts"] == 128
    assert dep["vocab_parallel"] * config["vocab_size"] == 151936
    assert dep["pipeline_stages"] * config["num_hidden_layers"] == 48
    assert "93.1%" in dep["eight_layers_measured"]
    assert dep["experts_held"] == [0, 16] and entry["chips"] == 1
    first, end = dep["vocab_rows"]
    assert (first, end) == (dep["vocab_rank"] * 18992, 151936)
    # the slice holds the mask token's row
    assert first <= config["assumed"]["mask_token_id"] == 151669 < end
    # the floors: four layers of a period of one, 8 experts, vocabulary / 8
    assert config["num_hidden_layers"] >= 4
    assert config["num_experts"] >= 8 and dep["vocab_parallel"] <= 8
    # every width and head count as published
    assert (config["hidden_size"], config["moe_intermediate_size"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], config["num_experts_per_tok"]) == (
        2048, 768, 32, 4, 128, 8)
    # what the row does not give stands under assumed, each with its sentence
    assert set(published["not_given"]) == {"block length", "noise schedule"}
    for key in ("block_length", "block_length_why", "noise_schedule",
                "noise_eps", "logit_shift", "mask_token_id", "qk_norm",
                "balance_term", "seq", "two_copy_pass", "optimizer",
                "remat"):
        assert key in config["assumed"], key
    assert set(config["not_modelled"]) == {
        "generation", "packing", "exchange", "keys_unused"}


def table_part(bench):
    mine = cells.declared(bench, CELL, own=OWN)
    new = mine["diffusion_masked_share"]
    assert (new["unit"], new["better"], new["source"], new["layer"],
            new["moves"]) == ("%", "higher", "program_counter", "model step",
                              "train_tokens_per_s")
    # no dense FFN: the row hetu_mlp would read nothing in this cell
    assert not [n for n in mine if n.startswith("mlp_block")]


def test_benchmark_entries():
    bench, cell, config, mix = run.load_cell(CELL)
    table_part(bench)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "sdar-30b-a3b-chat-train", "b1-s8192-sdar", 1)
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert set(mix["reference_tolerance"]) == {
        "ce", "ce_masked", "logits_gap", "attention_gap", "routing_mismatch",
        "dropped"}
    for key, value in {"batch": 1, "seq": 8192, "block": 4, "ring": 8,
                       "warm_steps": 3, "strategy": None,
                       "trace_seconds": 4}.items():
        assert mix[key] == value, key


def test_flops_of_the_cut_configuration():
    """1,456 M forward operations a data token at this cut with 1 of 8 pairs
    on held experts: the mask's pairs 55%, the projections 31%, the held
    experts 8%, the head 5%, the router under 1%; a layer's forward attention
    1.10e12 over a pass, a quarter of the dense square's and a sliver."""
    _, _, c, _ = run.load_cell(CELL)
    parts = fs.forward_flops_per_token(c, 8192, 1.0)
    total = sum(parts.values())
    assert abs(total - 1456.0e6) < 1e6

    def share(name):
        return round(100 * parts[name] / total)
    assert share("masked_attention") == 55
    assert share("attention_projections") == 31
    assert share("held_experts") == 8 and share("head") == 5
    assert share("router") == 0
    assert fs.visible_pairs(8192, 4) == 8192 ** 2 + 4 * 8192
    assert fs.visible_pairs(8192, 4) / (2 * 8192) ** 2 < 0.2502
    assert round(8192 * parts["masked_attention"] / 6 / 1e12, 2) == 1.10


def test_the_built_programs_parameter_count_is_the_issues():
    """The graph at the published widths (no executor: nothing is allocated):
    645,623,296 parameters (ISSUE 62's table: 6 x 94,638,336 a layer with 16
    experts held + 77,793,280 of embedding, head and final norm; at the eight
    layers the issue tried first 834,899,968) plus the six load vectors (6 x
    4 x 16)."""
    import hetu_tpu as ht
    from hetu_tpu.graph.node import graph_variables
    from hetu_tpu.models import SdarMoeConfig, SdarMoeForCausalLM
    from chipbench.builders.sdar import HF_KEYS
    _, _, config, mix = run.load_cell(CELL)
    dep = config["deployment"]
    L = mix["seq"]
    c = SdarMoeConfig(seq_len=L, num_experts=dep["num_experts"],
                      experts_held=tuple(dep["experts_held"]),
                      block_length=config["assumed"]["block_length"],
                      **{k: config[k] for k in HF_KEYS})
    ids = ht.placeholder_op("scount_ids", (1, 2 * L), dtype=np.int32)
    labels = ht.placeholder_op("scount_labels", (1, L), dtype=np.int32)
    weights = ht.placeholder_op("scount_weights", (1, L), dtype=np.float32)
    model = SdarMoeForCausalLM(c, name="sdarcount")
    loss = model.loss(ids, labels, weights)
    total = sum(int(np.prod(v.shape)) for v in graph_variables(
        [loss] + model.moe_loads(), trainable_only=False))
    layer = 18874624 + 266240 + 16 * 4718592
    assert layer == 94638336
    assert total == 6 * layer + 77793280 + 6 * 4 * 16 == 645623296 + 384
    assert 8 * layer + 77793280 == 834899968
    assert round(645623296 * 12 / 2 ** 30, 2) == 7.22


def toy(say=lambda msg: None, **job):
    from chipbench.builders import sdar as builder
    _, _, config, mix = run.load_cell(CELL)
    config = run.merge(run.merge(config, config["toy"]), {"job": job})
    mix = run.merge(mix, mix["toy"])
    return builder.build(config, mix, 2 ** 31 + 3, say), mix


@pytest.mark.parametrize("remat", ["layer", False])
def test_the_cells_builder_at_toy_size(remat):
    prog, mix = toy(remat=remat)
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        ids, labels, weights = (feed[prog.nodes[k]]
                                for k in ("ids", "labels", "weights"))
        assert ids.shape == (1, 128) and labels.shape == weights.shape == (
            1, 64)
        # never the mask token in the clean half, never as a label
        assert (ids[:, :64] != prog.mask_id).all()
        assert (labels != prog.mask_id).all() and (labels >= 0).any()
        want = prog.reference_loss(feed, 1)
        got = prog.eval_loss(feed)
        for term, tol in mix["reference_tolerance"].items():
            assert abs(got[term] - want[term]) < tol, (term, got, want)
        first = prog.step(feed)
        assert abs(first - want["loss"]) < mix["first_loss_tolerance"]
        second = prog.step(feed)
        assert np.isfinite(second) and second != first
        # the one value a step hands out: the loss, then every layer's load
        stats = prog.ex.run("train", feed_dict=feed,
                            convert_to_numpy_ret_vals=True)[0]
        assert stats.shape == (1 + 2 * 4 * 8,) and stats[1:].sum() > 0
        assert np.isfinite(stats[0]) and stats[0] < second
        shapes = prog.expected_kernel_shapes()
        assert shapes["attention_passes"] == 2
        assert shapes["attention_layers"] == (4 if remat else 2)
        assert shapes["flash_dims"] == (1, 4, 128, 16)
        assert shapes["ce_rows"] == 64 and shapes["moe_pairs"] == 128 * 4
        assert prog.tokens_per_step == 64 and prog.seq == 128
        taken, fallbacks = prog.kernel_choices()
        assert not fallbacks
    finally:
        prog.close()


def test_cell_rehearses(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                   "--seconds", "2", "--trace", "0"], rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "WRONG" not in out and "routing_mismatch" in out
    assert "ce_masked" in out and "attention_gap" in out
    assert "not finite: 0\n" in out


def test_every_control_is_refused_and_the_program_is_not(capsys):
    from chipbench.reference import sdar_controls as controls
    rc = controls.main(["--seed", "5", "--rehearsal"])
    out = capsys.readouterr().out
    assert rc == 0, out
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    by = {ln["control"]: ln for ln in lines}
    assert set(by) == {"bf16", "fp8_e4m3", "fp8_e5m2", "causal",
                       "own_clean_block", "noised_causal", "positions",
                       "qk_norm", "weights", "program"}
    assert by["program"]["correct"]
    for name in ("causal", "own_clean_block", "noised_causal", "positions",
                 "qk_norm"):
        assert "attention_gap" in by[name]["refused_by"], name
        assert "logits_gap" in by[name]["refused_by"], name
    # the weights change the loss and nothing else
    assert by["weights"]["refused_by"] == ["ce", "first_loss"]
    assert by["weights"]["gaps"]["logits_gap"] == 0


def test_a_recomputed_toy_step_runs_the_bd_kernels_once_a_layer(
        monkeypatch, live_registry):
    """Lowered for a TPU (nothing compiled or run) at heads of the kernels'
    width: one ``hetu_flash_fwd_bd`` and one ``hetu_flash_bwd_bd`` a layer
    with whole layers recomputed (the group keeps the kernel's context and
    log-sum-exp: ``hetu_remat_kept_total{kernel="bd"}``), no plain flash
    kernel and no ``jax.numpy`` attention in the step, and the tiles walked
    are the tiles that hold a visible pair."""
    from conftest import kernel_calls, lowered_for_tpu
    from hetu_tpu.ops.pallas import dispatch

    def counted(name):
        return {tuple(sorted(lab.items())): n
                for lab, n in dispatch.counted(name)}
    before = counted("hetu_remat_kept_total")
    chosen_before = dispatch.choices()

    def build():
        from chipbench.builders import sdar as builder
        _, _, config, mix = run.load_cell(CELL)
        config = run.merge(run.merge(config, config["toy"]),
                           {"head_dim": 128})
        mix = run.merge(run.merge(mix, mix["toy"]), {"seq": 256})
        return builder.build(config, mix, 3, lambda msg: None)
    text = lowered_for_tpu(monkeypatch, build)
    assert kernel_calls(text, "hetu_flash_fwd_bd") == 2
    assert kernel_calls(text, "hetu_flash_bwd_bd") == 2
    # no flash kernel of the plain name: every call of the step is a _bd one
    assert kernel_calls(text, "hetu_flash_fwd") == 0
    assert kernel_calls(text, "hetu_flash_bwd") == 0
    after = counted("hetu_remat_kept_total")
    key = (("kernel", "bd"),)
    assert after[key] - before.get(key, 0) == 2
    # the registry is the process's: what this step's trace added to it
    choices = {k: n - chosen_before.get(k, 0)
               for k, n in dispatch.choices().items()}
    assert not [k for k, n in choices.items()
                if n and k[0] == "flash_attention" and k[1] == "jnp"]
    assert choices[("flash_attention", "pallas", "")] == 2
    tiles = {(lab["pass"], lab["tiles"]): n
             for lab, n in dispatch.counted("hetu_flash_tiles")
             if lab["mask"] == "block_diffusion"}
    assert tiles[("forward", "walked")] == tiles[("forward", "visible")] == 3
    assert tiles[("backward", "walked")] == tiles[("backward", "visible")]
