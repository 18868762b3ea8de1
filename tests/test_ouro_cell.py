"""The benchmark's side of the Ouro cell on the CPU: the configuration file
against the catalog row and its parameter count by shape arithmetic, the
operations the ``mfu`` reader credits, the ``BENCHMARK.json`` entries the cell
joins and declares, the builder at toy size through the benchmark's own loop
against the plain reference, the cell's rehearsal through the harness, and
the traffic file's limits."""

import json
import os

import numpy as np
import pytest

import cells
from chipbench import flops_ouro as fo, run

CELL = "ouro-2.6b.b1-s8192"
CONFIG = "ouro-2.6b-pretrain"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 8, "layer_types": ["full_attention"] * 8}
#: the family's own mechanism: the exit gate and the loop's recomputation
OWN = ("exit_block_device_ms_per_step", "loop_recompute_device_share")


def published():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(ln) for ln in open(CATALOG) if ln.strip()]
    return next(r for r in rows if r["name"] == "Ouro-2.6B")


def test_configuration_file_holds_the_published_keys():
    """Every key of the catalog row unchanged but the two in ``reduced``,
    whose published values stand in the ``deployment`` group beside the cut:
    one pipeline stage of eight layers; every width, the vocabulary and
    ``total_ut_steps`` as published."""
    row = published()
    _, entry, config, _ = run.load_cell(CELL)
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == sorted(REDUCED)
    assert sorted(config["reduced_why"]) == sorted(REDUCED)
    for key, value in row["config"].items():
        assert config[key] == REDUCED.get(key, value), key
    dep = config["deployment"]
    for key in REDUCED:
        assert dep[key] == row["config"][key], key
    assert dep["pipeline_stages"] * dep["layers_a_stage"] == 48
    assert dep["layers_a_stage"] == config["num_hidden_layers"]
    assert dep["chips_sharing_a_layer"] == 1
    assert (config["total_ut_steps"], config["vocab_size"],
            config["hidden_size"], config["intermediate_size"],
            config["head_dim"]) == (4, 49152, 2048, 5632, 128)
    assert config["builder"] == "ouro" and entry["chips"] == 1
    for key in ("attention_bias", "sandwich_norms", "loop", "exit_gate",
                "loss", "job", "sequence_length", "remat", "initialisation"):
        assert key in config["assumed"], key
    assert set(config["not_modelled"]) == {"early_exit", "kv_cache_a_pass"}
    assert config["job"]["remat"] is True
    assert config["job"]["exit_entropy_coeff"] == 0.05
    toy = config["toy"]
    assert toy["num_hidden_layers"] == 2 and "total_ut_steps" not in toy


def table_part(bench):
    mine = cells.declared(bench, CELL, own=OWN)
    assert all(mine[name]["moves"] == "train_tokens_per_s" for name in OWN)
    assert (mine["loop_recompute_device_share"]["unit"],
            mine["exit_block_device_ms_per_step"]["unit"]) == ("%", "ms")


def test_benchmark_entries():
    """The cell on one chip; the exit gate's block and the loop's recomputed
    share are entries that move the training throughput."""
    bench, cell, config, _ = run.load_cell(CELL)
    table_part(bench)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "b1-s8192-ouro", 1)
    assert sorted(config["reduced"]) == sorted(REDUCED)


def test_parameter_count_at_the_published_widths():
    """612.4 M by shape arithmetic from the configuration file's keys (no
    arrays): a layer 51.39 M, embedding and untied head 201.3 M, the final
    norm and the gate; 6.84 GiB at the 12 bytes a parameter the optimiser
    keeps resident."""
    _, _, c, _ = run.load_cell(CELL)
    h, i, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    layer = 4 * h * h + 3 * h * i + 4 * h
    total = c["num_hidden_layers"] * layer + 2 * v * h + h + (h + 1)
    assert (layer, 2 * v * h, total) == (51_388_416, 201_326_592,
                                         612_438_017)
    assert round(total / 1e6, 1) == 612.4 == c["deployment"]["parameters_m"]
    assert round(12 * total / 2 ** 30, 2) == c["deployment"]["resident_gib"]
    whole = 48 * layer + 2 * v * h + h + (h + 1)
    assert round(whole / 1e9, 2) == 2.67


def test_flops_of_the_cut_configuration():
    """About 5,168 M forward operations a token at this cut: the 32 layer
    applications 84% (attention's two products 21%), the four head passes
    16% (3% in the whole model: 192 applications)."""
    _, _, c, _ = run.load_cell(CELL)
    parts = fo.forward_flops_per_token(c, 8192)
    total = sum(parts.values())
    assert abs(total - 5168e6) < 1e6

    def share(p, *names):
        return round(100 * sum(p[n] for n in names) / sum(p.values()))
    assert share(parts, "attention_projections", "causal_attention",
                 "mlp") == 84
    assert share(parts, "causal_attention") == 21
    assert share(parts, "head") == 16
    assert parts["causal_attention"] == 32 * 4.0 * 4096 * 2048
    assert parts["exit_gate"] == 4 * 2.0 * 2048
    whole = fo.forward_flops_per_token(dict(c, num_hidden_layers=48), 8192)
    assert share(whole, "head") == 3


def toy(say=lambda msg: None):
    from chipbench.builders import ouro as builder
    _, _, config, mix = run.load_cell(CELL)
    config, mix = run.merge(config, config["toy"]), run.merge(mix, mix["toy"])
    return builder.build(config, mix, 2 ** 31 + 3, say), mix


def test_the_toy_runs_through_the_benchmarks_loop():
    """The benchmark's loop (prepare, a window, finish) over the builder's
    program at toy widths, two layers walked four times: every check that
    decides ``correct`` holds, the steps do not retrace, the builder tells
    the trace checks sixteen flash forward calls a step, the counter said
    two applications a pass and the gauge holds shares that add up to 1."""
    from hetu_tpu import telemetry
    from chipbench import loops
    from chipbench.builders.common import counter
    telemetry.enable()
    said = []
    prog, mix = toy(said.append)
    try:
        loop = loops.TrainLoop(prog, mix, 2 ** 31 + 3, loops.Spans(),
                               said.append)
        loop.prepare()
        rec = loop.window(1.0, loops.Tracer())
        checks = loop.finish()
        assert checks and all(ok for ok, _ in checks), checks
        assert {what.split()[2] for _, what in checks[:4]} == {
            "ce", "entropy", "exit_gap", "logits_gap"}
        assert rec["attempted"] >= 1 and rec["failed"] == 0
        losses = loop.warm_losses + rec["losses"]
        assert len(losses) >= 2 and losses[-1] < losses[0]
        shapes = prog.expected_kernel_shapes()
        assert shapes["attention_layers"] == 2 * 4 * 2
        assert shapes["flash_dims"] == (1, 4, 64, 16) and shapes["causal"]
        assert prog.KERNELS == ("hetu_softmax_ce_fwd", "hetu_softmax_ce_bwd")
        assert any("[2, 2, 2, 2] layer applications" in m for m in said)
        shares = [counter("hetu_loop_exit_share", **{"pass": str(t)})
                  for t in range(4)]
        assert abs(sum(shares) - 1.0) < 1e-3 and min(shares) > 0
        assert prog.steps_off == 0
    finally:
        prog.close()
        telemetry.shutdown()


def test_a_step_whose_shares_do_not_add_up_is_a_failed_step():
    prog, _ = toy()
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        assert np.isfinite(prog.step(feed))
        run_ = prog.ex.run
        prog.ex.run = lambda *a, **k: [np.float32(1.0), None,
                                       np.array([0.5, 0.2, 0.2, 0.2])]
        assert np.isnan(prog.step(feed)) and prog.steps_off == 1
        prog.ex.run = run_
    finally:
        prog.close()


def test_cell_rehearses(capsys):
    """The harness runs the cell end to end at toy size on the CPU: builder,
    loop, reference, every check."""
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                   "--seconds", "2", "--trace", "0"], rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "WRONG" not in out and "exit_gap" in out
    assert "walked 4 times on one set of weights" in out
    assert "hetu_loop_exit_share" in out


@pytest.fixture(scope="module")
def control_lines():
    """``python3 -m chipbench.reference.ouro_controls --rehearsal``, once:
    its exit code and its JSON lines by control."""
    import contextlib
    import io
    from chipbench.reference import ouro_controls
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = ouro_controls.main(["--seed", str(2 ** 31 + 5), "--rehearsal"])
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()
             if ln.startswith("{")]
    return rc, {ln["control"]: ln for ln in lines}


@pytest.mark.parametrize("control, refused_by", [
    ("fp8_e4m3", "logits_gap"), ("fp8_e5m2", "logits_gap"),
    ("a_pass", "exit_gap"), ("post_norms", "logits_gap"),
    ("fed_norm", "logits_gap"), ("last_takes_rest", "ce"),
    ("entropy", "first_loss"), ("program", None)])
def test_controls_go_through_the_harness_comparison(control_lines, control,
                                                    refused_by):
    """Each control of ``reference/ouro_controls.py`` stands in the
    program's place before ``TrainLoop.finish`` and comes out ``correct:
    false``, refused by the term named at least; the program itself comes out
    ``correct: true``; the entropy term is seen by the first loss alone."""
    rc, lines = control_lines
    line = lines[control]
    assert rc == 0 and set(lines) == {"bf16", "fp8_e4m3", "fp8_e5m2",
                                      "a_pass", "post_norms", "fed_norm",
                                      "last_takes_rest", "entropy",
                                      "program"}
    if refused_by is None:
        assert line["correct"] and not line["refused_by"]
    else:
        assert not line["correct"] and refused_by in line["refused_by"]
    if control == "entropy":
        assert line["refused_by"] == ["first_loss"]


def test_a_limit_that_refuses_nothing_fails_the_controls():
    """``verdict`` is ``TrainLoop.finish``'s: a reading inside every limit
    is correct, one term outside its limit refuses it by that term."""
    from chipbench.reference.ouro_controls import verdict
    _, _, _, mix = run.load_cell(CELL)
    want = {"loss": 11.0, "ce": 11.05, "entropy": 1.0, "exit_gap": 0.0,
            "logits_gap": 0.0}
    inside = dict(want, ce=11.0505, exit_gap=0.04, logits_gap=0.07)
    assert verdict(mix, want, inside) == (True, [])
    for term, limit in mix["reference_tolerance"].items():
        off = dict(inside, **{term: want[term] + 1.01 * limit})
        assert verdict(mix, want, off) == (False, [term])
    off = dict(inside, loss=want["loss"] + 1.01 * mix["first_loss_tolerance"])
    assert verdict(mix, want, off) == (False, ["first_loss"])


def test_the_tolerances_have_their_readings():
    """Each limit of the traffic file is written with the program's reading,
    a lower precision's and each left-out piece's beside it."""
    _, _, _, mix = run.load_cell(CELL)
    assert sorted(mix["reference_tolerance"]) == [
        "ce", "entropy", "exit_gap", "logits_gap"]
    why = mix["reference_tolerance_why"]
    for word in ("fp8", "PR 47", "a pass", "norms behind", "fed back",
                 "remaining mass", "entropy term"):
        assert word in why, word
    assert "PR 47" in mix["first_loss_tolerance_why"]
    assert (mix["batch"], mix["seq"], mix["ring"], mix["warm_steps"],
            mix["mask_fraction"], mix["strategy"], mix["trace_seconds"]) == (
                1, 8192, 8, 3, 1.0, None, 8)
