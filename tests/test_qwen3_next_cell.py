"""The benchmark's side of the Qwen3-Next cell on the CPU: the builder at a
hybrid toy size against the plain reference, the configuration file against
the published keys, the operations the ``mfu`` reader credits, the cell's
rehearsal through the harness, and the two things the configuration states
that the loss terms alone do not hold: dropless routing all through the
window, and the f32 DeltaNet state."""

import numpy as np
import pytest

import cells
from chipbench import flops_qwen3next as fq, run

CELL = "qwen3-next-80b-a3b.b1-s8192"
#: the family's own mechanism: the gated delta rule's mixers and their scan
OWN = ("gdn_scan_roofline", "gdn_block_device_ms_per_step")

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: Qwen3-Next-80B-A3B-Instruct), every number of it
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def table_part(bench):
    cells.declared(bench, CELL, own=OWN)
    # the contract's rule: four-chip cells are at most a quarter of the cells
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)


def test_configuration_file_holds_the_published_keys():
    """Every published key unchanged but the three in ``reduced``, whose
    published values stand in the ``deployment`` group beside the cut."""
    bench, cell, config, _ = run.load_cell(CELL)
    reduced = {"num_hidden_layers": 4, "num_experts": 32,
               "vocab_size": 18992}
    assert sorted(config["reduced"]) == sorted(reduced)
    for key, value in PUBLISHED.items():
        assert config[key] == reduced.get(key, value), key
    dep = config["deployment"]
    for key in reduced:
        assert dep[key] == PUBLISHED[key], key
    assert dep["chips_sharing_a_layer"] * config["num_experts"] == 512
    assert dep["experts_held"] == [0, 32]
    assert dep["vocabulary_divided"] * config["vocab_size"] == 151936
    assert dep["pipeline_stages"] * config["num_hidden_layers"] == 48
    assert cell["config"] == "qwen3-next-80b-a3b-pretrain"
    table_part(bench)


def test_flops_of_the_cut_configuration():
    """About 460 M forward operations a token at this cut: the DeltaNet
    mixers 46%, the attention layer 26%, the head 17%, all MoE parts 11%
    (ISSUE 31); in the whole model the MoE block is far more."""
    _, _, c, _ = run.load_cell(CELL)
    parts = fq.forward_flops_per_token(c, 8192, 10 * 32 / 512)
    total = sum(parts.values())
    assert abs(total - 460.4e6) < 0.5e6

    def share(*names):
        return round(100 * sum(parts[n] for n in names) / total)
    assert share("deltanet_projections", "delta_rule") == 46
    assert share("attention_projections", "causal_attention") == 26
    assert share("head") == 17
    assert share("router", "shared_expert", "held_experts") == 11
    assert parts["delta_rule"] == 3 * 6 * 32 * 128 * 128
    ops, nbytes = fq.delta_rule_step(c, 8192, 64)
    assert ops == 3 * 128 * 32 * fq.delta_rule_chunk(64, 128, 128)
    assert nbytes > 0 and fq.layer_kinds(c).count("full_attention") == 1


def hybrid_toy(say=lambda msg: None, **widths):
    """The cell's program at toy widths with the published layer pattern
    (three DeltaNet layers, one attention layer; the configuration's own
    ``toy`` is a hybrid of one layer of each kind, see its ``why_pattern``)."""
    from chipbench.builders import qwen3_next as builder
    _, _, config, mix = run.load_cell(CELL)
    config = run.merge(config, config["toy"])
    config.update(num_hidden_layers=4, full_attention_interval=4, **widths)
    mix = run.merge(mix, mix["toy"])
    return builder.build(config, mix, 2 ** 31 + 3, say), mix


def test_the_lowered_train_step_holds_the_convolutions_kernels(monkeypatch):
    """Three DeltaNet layers, each recomputed in the backward pass: the
    convolution over ``q | k | v`` (2 x 32 + 64 = 128 lanes at toy widths, no
    bias, no window) is ``hetu_conv_fwd`` six times and ``hetu_conv_bwd``
    three, as in the cell's step (PERF.md section 3)."""
    from conftest import conv_calls, lowered_for_tpu
    text = lowered_for_tpu(monkeypatch, lambda: hybrid_toy()[0])
    assert conv_calls(text) == (6, 3)


def test_the_lowered_train_step_holds_the_gated_norms_kernels(monkeypatch):
    """Three DeltaNet layers, each recomputed in the backward pass, at the
    cell's own head sizes (128 and 128, two value heads a key head): the norm
    over a value head and its gate are ``hetu_gated_norm_fwd`` six times and
    ``hetu_gated_norm_bwd`` three, ``z`` read out of ``qkvz`` (2 x 768 lanes)
    where it lies, and under ``hetu_gdn_out`` no f32 array by heads ``[..,
    4, 128]`` is formed, forward or backward (PR 44; the ``jax.numpy`` form
    makes several)."""
    from conftest import arrays_under, gated_norm_calls, lowered_for_tpu
    text = lowered_for_tpu(
        monkeypatch, lambda: hybrid_toy(linear_key_head_dim=128,
                                        linear_value_head_dim=128)[0],
        debug_info=True)
    assert gated_norm_calls(text) == (6, 3)
    assert "x1536x" in text
    seen, views = arrays_under(text, "hetu_gdn_out", (4, 128))
    assert seen > 30 and not views, views[:3]


def test_the_cells_builder_at_a_hybrid_toy_size():
    """The benchmark's builder on the cell's configuration and traffic at
    toy widths and the published layer pattern: the program's loss terms
    against the plain reference's through the builder's own entry points,
    one training step, and what the builder tells the trace checks."""
    prog, mix = hybrid_toy()
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        want = prog.reference_loss(feed, 1)
        got = prog.eval_loss(feed)
        for term, tol in mix["reference_tolerance"].items():
            assert abs(got[term] - want[term]) < tol, (term, got, want)
        assert abs(got["loss"] - want["loss"]) < mix["first_loss_tolerance"]
        first = prog.step(feed)
        assert abs(first - want["loss"]) < mix["first_loss_tolerance"]
        shapes = prog.expected_kernel_shapes()
        assert shapes["attention_layers"] == 1
        assert shapes["flash_dims"] == (1, 4, 64, 32)
        assert prog.n_layers == 4
    finally:
        prog.close()


def test_cell_rehearses(capsys):
    """The harness runs the cell end to end at toy size on the CPU: builder,
    loop, reference, every check."""
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                   "--seconds", "2", "--trace", "0"], rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "WRONG" not in out and "routing_mismatch" in out
    assert "delta_rule_gap" in out and "pairs on held experts" in out
    assert "not finite: 0\n" in out


def test_a_window_that_drops_a_pair_is_not_correct(monkeypatch, capsys):
    """Dropless routing is the configuration's: with a row bound the batch
    overflows, a step of the window reports a loss that is not finite, and
    the rehearsal (the harness's own loop and checks) ends not correct, by
    the window's checks as well as by the first batch's ``dropped``."""
    from hetu_tpu.ops import moe as moe_ops
    monkeypatch.setattr(moe_ops, "held_rows", lambda pairs, E, count: 8)
    # and a program that stops after the first pass over its rows
    monkeypatch.setattr(
        moe_ops, "_every_window",
        lambda one_pass, step, *args, later: one_pass(*args, 0))
    prog, _ = hybrid_toy()
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        assert np.isnan(prog.step(feed)) and prog.steps_dropping == 1
    finally:
        prog.close()
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 13),
                   "--seconds", "1", "--trace", "0"], rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 1 and "rehearsal complete (correct=False)" in out
    assert "WRONG every loss is finite" in out
    assert "WRONG the program's dropped" in out
    assert "not finite: 0\n" not in out


def test_a_window_over_the_row_bound_drops_nothing(monkeypatch, capsys):
    """With rows for 8 pairs a pass the batch overflows in every expert
    layer; the further passes compute the rest, so a step's loss is the
    unbounded program's and the rehearsal ends correct."""
    from hetu_tpu.ops import moe as moe_ops
    prog, _ = hybrid_toy()
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        want = prog.step(feed)
    finally:
        prog.close()
    monkeypatch.setattr(moe_ops, "held_rows", lambda pairs, E, count: 8)
    prog, _ = hybrid_toy()
    try:
        got = prog.step(prog.make_batches(2 ** 31 + 3, 1)[0])
        assert np.isfinite(got) and prog.steps_dropping == 0
        assert prog.held_peak > 8
        np.testing.assert_allclose(got, want, rtol=1e-6)
    finally:
        prog.close()
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 13),
                   "--seconds", "1", "--trace", "0"], rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 0 and "WRONG" not in out, out
    assert "not finite: 0\n" in out


def test_a_bf16_state_fails_the_delta_rule_probe(monkeypatch):
    """The f32 DeltaNet state is the configuration's.  The probe reads the
    function the layers' ``hetu_gdn_scan`` nodes call: the sound rule is
    within the toy limit by a wide margin, the same rule with its state
    carried in bf16 is far over it, and the layers do go through the function
    that was swapped (three DeltaNet layers traced it)."""
    import jax.numpy as jnp
    from hetu_tpu.ops import gated_delta
    calls = []

    def bf16_state(q, k, v, g, beta):
        calls.append(q.shape)
        return gated_delta.recurrent_gated_delta_rule(
            q, k, v, g, beta, state_dtype=jnp.bfloat16)
    prog, mix = hybrid_toy()
    limit = mix["reference_tolerance"]["delta_rule_gap"]
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        prog.reference_loss(feed, 1)
        assert prog.eval_loss(feed)["delta_rule_gap"] < limit / 10
    finally:
        prog.close()
    monkeypatch.setattr(gated_delta, "chunk_gated_delta_rule", bf16_state)
    prog, _ = hybrid_toy()
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        prog.reference_loss(feed, 1)
        assert prog.eval_loss(feed)["delta_rule_gap"] > 10 * limit
        assert len(calls) == 3 + 1 and calls[-1][2] == 4   # the probe's heads
    finally:
        prog.close()
