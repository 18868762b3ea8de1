"""The benchmark's side of the Nemotron-H cell on the CPU: the builder at a
toy size with the cell's HYBRID pattern against the plain reference, the
configuration file against the published keys, the operations the ``mfu``
reader credits, the cell's rehearsal through the harness, and the things the
configuration states that the loss terms alone do not hold: dropless routing
all through the window, the f32 state-space state, and the rule that moves
the router's bias."""

import numpy as np
import pytest

import cells
from chipbench import flops_nemotronh as fn, run

CELL = "nemotron-3-nano-30b-a3b.b1-s8192"
PATTERN = "MEMEM*EME"
#: the family's own mechanism: the state-space mixers and their scan
OWN = ("ssd_scan_roofline", "ssm_block_device_ms_per_step")

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), every key of it
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1, "n_groups": 8,
    "n_routed_experts": 128, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}


def table_part(bench):
    cells.declared(bench, CELL, own=OWN)


def test_configuration_file_holds_the_published_keys():
    """Every published key unchanged but the four in ``reduced``, whose
    published values stand in the ``deployment`` group beside the cut."""
    bench, cell, config, _ = run.load_cell(CELL)
    reduced = {"num_hidden_layers": 9, "hybrid_override_pattern": PATTERN,
               "n_routed_experts": 8, "vocab_size": 16384}
    assert sorted(config["reduced"]) == sorted(reduced)
    assert sorted(config["reduced_why"]) == sorted(reduced)
    for key, value in PUBLISHED.items():
        assert config[key] == reduced.get(key, value), key
    dep = config["deployment"]
    for key in reduced:
        assert dep[key] == PUBLISHED[key], key
    assert PUBLISHED["hybrid_override_pattern"].startswith(PATTERN)
    assert dep["chips_sharing_a_layer"] * config["n_routed_experts"] == 128
    assert dep["experts_held"] == [0, 8]
    assert dep["vocabulary_divided"] * config["vocab_size"] == 131072
    assert (dep["pipeline_stages"] - 1) * dep["blocks_a_stage"] < 52 <= (
        dep["pipeline_stages"] * dep["blocks_a_stage"])
    assert dep["blocks_a_stage"] == config["num_hidden_layers"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-nano-30b-a3b-pretrain", "b1-s8192-nemotron", 1)
    table_part(bench)


def test_flops_of_the_cut_configuration():
    """About 717 M forward operations a token at this cut: the Mamba-2
    mixers 45% (projections 43, the scan 2), the expert layers 27% (shared
    expert 22, held pairs 4), attention 16%, the head 12% (ISSUE 33)."""
    _, _, c, _ = run.load_cell(CELL)
    parts = fn.forward_flops_per_token(c, 8192, 6 * 8 / 128)
    total = sum(parts.values())
    assert abs(total - 716.8e6) < 0.5e6

    def share(*names):
        return round(100 * sum(parts[n] for n in names) / total)
    assert share("mamba_projections", "ssm_scan") == 45
    assert share("mamba_projections") == 43 and share("ssm_scan") == 2
    assert share("router", "shared_expert", "held_experts") == 27
    assert share("shared_expert") == 22 and share("held_experts") == 4
    assert share("attention_projections", "causal_attention") == 16
    assert share("head") == 12
    assert parts["ssm_scan"] == 4 * (6 * 64 * 64 * 128 + 2 * 4096)
    ops, nbytes = fn.ssd_step(c, 8192)
    assert ops == 3 * 64 * 64 * fn.ssd_chunk(128, 64, 128, 8)
    assert nbytes == 3 * (8192 * (2 * 4096 + 2 * 1024) * 2 + 8192 * 64 * 4
                          + 64 * 64 * 64 * 128 * 4)


def hybrid_toy(say=lambda msg: None, **widths):
    """The cell's program at toy widths with the cell's own pattern (four
    Mamba-2 mixers, four expert blocks, one attention block; the
    configuration's own ``toy`` is a hybrid of one block of each kind, see
    its ``why_pattern``)."""
    from chipbench.builders import nemotron_h as builder
    _, _, config, mix = run.load_cell(CELL)
    config = run.merge(config, config["toy"])
    config.update(num_hidden_layers=9, hybrid_override_pattern=PATTERN,
                  **widths)
    mix = run.merge(mix, mix["toy"])
    return builder.build(config, mix, 2 ** 31 + 3, say), mix


def test_the_lowered_train_step_holds_the_convolutions_kernels(monkeypatch):
    """Four Mamba-2 mixers, each recomputed in the backward pass: the
    convolution reads ``xBC`` in place out of ``[z | xBC | dt]`` (at toy
    widths with a state of 32: lanes 128 to 384 of 392, with a bias) in
    ``hetu_conv_fwd`` eight times and ``hetu_conv_bwd`` four, as in the
    cell's step (PERF.md section 3)."""
    from conftest import conv_calls, lowered_for_tpu
    text = lowered_for_tpu(
        monkeypatch, lambda: hybrid_toy(ssm_state_size=32)[0])
    assert conv_calls(text) == (8, 4)
    assert "x392x" in text and "x256x" in text


def test_the_lowered_train_step_holds_the_gated_norms_kernels(monkeypatch):
    """Four Mamba-2 mixers, each recomputed in the backward pass, with heads
    of 32 channels (256 channels in two groups of 128 lanes): the gate and the
    grouped norm are ``hetu_gated_norm_fwd`` eight times and
    ``hetu_gated_norm_bwd`` four, ``z`` read out of ``[z | xBC | dt]`` (648
    lanes) where it lies, and under ``hetu_ssm_out`` no f32 array by groups
    ``[.., 2, 128]`` is formed, forward or backward (PR 44; the ``jax.numpy``
    form makes several)."""
    from conftest import arrays_under, gated_norm_calls, lowered_for_tpu
    text = lowered_for_tpu(
        monkeypatch, lambda: hybrid_toy(ssm_state_size=32,
                                        mamba_head_dim=32)[0],
        debug_info=True)
    assert gated_norm_calls(text) == (8, 4)
    assert "x648x" in text
    seen, views = arrays_under(text, "hetu_ssm_out", (2, 128))
    assert seen > 40 and not views, views[:3]


def test_the_cells_builder_at_a_hybrid_toy_size():
    """The benchmark's builder on the cell's configuration and traffic at
    toy widths and the cell's pattern: the program's loss terms against the
    plain reference's through the builder's own entry points, training steps
    without a retrace, and what the builder tells the trace checks."""
    from hetu_tpu import telemetry
    telemetry.enable()
    prog, mix = hybrid_toy()
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        want = prog.reference_loss(feed, 1)
        got = prog.eval_loss(feed)
        assert want["lbl"] > 3.5 and got["lbl"] > 3.5     # four blocks' ~1
        for term, tol in mix["reference_tolerance"].items():
            assert abs(got[term] - want[term]) < tol, (term, got, want)
        assert abs(got["loss"] - want["loss"]) < mix["first_loss_tolerance"]
        first = prog.step(feed)
        assert abs(first - want["loss"]) < mix["first_loss_tolerance"]
        traced = prog.retraces()
        assert all(np.isfinite(prog.step(feed)) for _ in range(3))
        assert prog.retraces() == traced and prog.steps_dropping == 0
        assert 0 < prog.bias_peak <= 4 * 0.001 + 1e-9
        shapes = prog.expected_kernel_shapes()
        assert shapes["attention_layers"] == 1
        assert shapes["flash_dims"] == (1, 4, 64, 32)
        assert prog.n_layers == 4
        snap = telemetry.get_registry().snapshot()
        gauge = snap["hetu_moe_router_bias_max_abs"]["samples"]
        assert {s["labels"]["layer"] for s in gauge} == {
            f"layer{i}" for i in range(4)}
        assert all(0 < s["value"] <= 0.004 + 1e-9 for s in gauge)
    finally:
        prog.close()
        telemetry.shutdown()


def test_cell_rehearses(capsys):
    """The harness runs the cell end to end at toy size on the CPU: builder,
    loop, reference, every check."""
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                   "--seconds", "2", "--trace", "0"], rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "WRONG" not in out and "routing_mismatch" in out
    assert "ssd_state_gap" in out and "pairs on held experts" in out
    assert "not finite: 0\n" in out


def test_pairs_over_the_row_bound_are_computed_and_counted(monkeypatch):
    """With rows for 8 pairs a pass every expert block overflows; further
    passes compute the rest: nothing is dropped, the loss is the unbounded
    program's, and the builder counts the step and its pairs."""
    from hetu_tpu.ops import moe as moe_ops
    prog, mix = hybrid_toy()
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        want = prog.step(feed)
        assert prog.steps_over == 0 and np.isfinite(want)
    finally:
        prog.close()
    monkeypatch.setattr(moe_ops, "held_rows", lambda pairs, E, count: 8)
    prog, mix = hybrid_toy()
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        prog.reference_loss(feed, 1)
        got = prog.eval_loss(feed)
        assert got["dropped"] == 0.0
        assert got["routing_mismatch"] <= mix["reference_tolerance"][
            "routing_mismatch"]
        np.testing.assert_allclose(prog.step(feed), want, rtol=1e-6)
        assert prog.steps_dropping == 0 and prog.steps_over == 1
        assert prog.held_peak > 8 and prog.pairs_over > 8
    finally:
        prog.close()


def test_a_step_that_drops_a_pair_is_a_failed_step(monkeypatch):
    """Dropless routing is the configuration's: a program that stops after
    the first pass over its rows leaves pairs out, the first batch's
    ``dropped`` is over its limit and a training step reports a loss that is
    not finite, which the harness's loop counts as a failed step and an
    incorrect run."""
    from hetu_tpu.ops import moe as moe_ops
    monkeypatch.setattr(moe_ops, "held_rows", lambda pairs, E, count: 8)
    monkeypatch.setattr(
        moe_ops, "_every_window",
        lambda one_pass, step, *args, later: one_pass(*args, 0))
    prog, mix = hybrid_toy()
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        prog.reference_loss(feed, 1)
        got = prog.eval_loss(feed)
        assert got["dropped"] > 0.5 > mix["reference_tolerance"]["dropped"]
        assert np.isnan(prog.step(feed)) and prog.steps_dropping == 1
        assert prog.held_peak > 8
    finally:
        prog.close()


def test_a_bf16_state_fails_the_scan_probe(monkeypatch):
    """The f32 state-space state is the configuration's.  The probe reads
    the function the blocks' ``hetu_ssm_scan`` nodes call: the sound scan is
    within the toy limit by a wide margin, the same scan with its state
    carried in bf16 is far over it, and the blocks do go through the
    function that was swapped (four Mamba-2 mixers traced it)."""
    import jax.numpy as jnp
    from hetu_tpu.ops import ssd
    calls = []

    def bf16_state(x, dt, A, B, C, chunk=None):
        calls.append(x.shape)
        return ssd.recurrent_ssd(x, dt, A, B, C, state_dtype=jnp.bfloat16)
    prog, mix = hybrid_toy()
    limit = mix["reference_tolerance"]["ssd_state_gap"]
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        prog.reference_loss(feed, 1)
        assert prog.eval_loss(feed)["ssd_state_gap"] < limit / 10
    finally:
        prog.close()
    monkeypatch.setattr(ssd, "chunk_ssd", bf16_state)
    prog, _ = hybrid_toy()
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        prog.reference_loss(feed, 1)
        assert prog.eval_loss(feed)["ssd_state_gap"] > 10 * limit
        assert len(calls) == 4 + 1 and calls[-1][2] == 4   # the probe's heads
    finally:
        prog.close()
