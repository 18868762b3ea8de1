"""The benchmark's side of the Granite 4.0-H cell on the CPU: the
configuration file against the published keys and its parameter count by
shape arithmetic, the operations the ``mfu`` and scan readers credit, the
builder at a toy size with the cell's HYBRID period through the benchmark's
own loop against the plain reference, the cell's rehearsal through the
harness, and the f32 state-space state, which the loss alone does not hold."""

import numpy as np

import cells
from chipbench import flops_granitehybrid as fg, flops_nemotronh as fn, run

CELL = "granite-4.0-h-micro.b1-s8192"
#: the family's own mechanism: the state-space mixers and their scan
OWN = ("ssd_scan_roofline", "ssm_block_device_ms_per_step")
KINDS = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4

#: the catalog row's ``config`` (model-configs guide, architectures.jsonl,
#: granite-4.0-h-micro), every key of it
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": KINDS * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}


def table_part(bench):
    mine = cells.declared(bench, CELL, own=OWN)
    assert all(mine[name]["moves"] == "train_tokens_per_s" for name in OWN)


def test_configuration_file_holds_the_published_keys():
    """Every published key unchanged but the three in ``reduced``, whose
    published values stand in the ``deployment`` group beside the cut: one
    whole period of the layer pattern, an eighth of the vocabulary."""
    bench, cell, config, _ = run.load_cell(CELL)
    reduced = {"num_hidden_layers": 10, "layer_types": KINDS,
               "vocab_size": 12544}
    assert sorted(config["reduced"]) == sorted(reduced)
    assert sorted(config["reduced_why"]) == sorted(reduced)
    for key, value in PUBLISHED.items():
        assert config[key] == reduced.get(key, value), key
    dep = config["deployment"]
    for key in reduced:
        assert dep[key] == PUBLISHED[key], key
    assert PUBLISHED["layer_types"][:10] == KINDS
    assert [i for i, k in enumerate(PUBLISHED["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert dep["pipeline_stages"] * dep["layers_a_stage"] == 40
    assert dep["layers_a_stage"] == config["num_hidden_layers"]
    assert dep["vocabulary_divided"] * config["vocab_size"] == 100352
    assert dep["chips_sharing_a_layer"] == 1
    assert config["job"]["remat"] == "mamba"
    assert config["job"]["scan_chunk"] == 128 != config["mamba_chunk_size"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "granite-4.0-h-micro-pretrain", "b1-s8192-granite", 1)
    table_part(bench)


def test_parameter_count_at_the_published_widths():
    """772.2 M by shape arithmetic from the configuration file's keys (no
    arrays): a Mamba layer 76.18 M, the attention layer 60.82 M, the period
    746.5 M, the tied slice 25.7 M; 8.6 GiB at the 12 bytes a parameter the
    optimiser keeps resident."""
    _, _, c, _ = run.load_cell(CELL)
    h, i = c["hidden_size"], c["shared_intermediate_size"]
    heads, p = c["mamba_n_heads"], c["mamba_d_head"]
    d, gn = heads * p, c["mamba_n_groups"] * c["mamba_d_state"]
    assert d == c["mamba_expand"] * h
    conv = d + 2 * gn
    mamba = (h * (d + conv + heads) + d * h + c["mamba_d_conv"] * conv
             + conv + 3 * heads + d)
    hd = h // c["num_attention_heads"]
    attention = h * (h + 2 * c["num_key_value_heads"] * hd) + h * h
    mlp = h * 2 * i + i * h
    kinds = c["layer_types"]
    total = (kinds.count("mamba") * mamba + kinds.count("attention")
             * attention + len(kinds) * (mlp + 2 * h) + h
             + c["vocab_size"] * h)
    assert (mamba, mlp, attention) == (25_847_232, 50_331_648, 10_485_760)
    assert round((mamba + mlp + 2 * h) / 1e6, 2) == 76.18
    assert round((attention + mlp + 2 * h) / 1e6, 2) == 60.82
    assert round(total / 1e6, 1) == 772.2 == c["deployment"]["parameters_m"]
    assert round(12 * total / 2 ** 30, 1) == c["deployment"]["resident_gib"]


def test_flops_of_the_cut_configuration():
    """About 1,606 M forward operations a token at this cut: the MLPs 63%,
    the mixers' projections 29%, attention 3.4%, the head 3.2%, the scan's
    recurrence 1.8% (ISSUE 37); the chunked scan credits ``C B^T`` once for
    all 64 heads and reads ``B`` and ``C`` once."""
    _, _, c, _ = run.load_cell(CELL)
    parts = fg.forward_flops_per_token(c, 8192)
    total = sum(parts.values())
    assert abs(total - 1606.0e6) < 0.5e6

    def share(*names):
        return round(100 * sum(parts[n] for n in names) / total, 1)
    assert share("mlp") == 62.7 and share("mamba_projections") == 29.0
    assert share("attention_projections", "causal_attention") == 3.4
    assert share("head") == 3.2 and share("ssm_scan") == 1.8
    assert parts["ssm_scan"] == 9 * (6 * 64 * 64 * 128 + 2 * 4096)
    ops, nbytes = fg.ssd_step(c, 8192, 128)
    assert ops == 3 * 64 * 64 * fn.ssd_chunk(128, 64, 128, 64)
    assert nbytes == 3 * (8192 * (2 * 4096 + 2 * 128) * 2 + 8192 * 64 * 4
                          + 64 * 64 * 64 * 128 * 4)
    # one group for all heads: less to credit than at eight heads a group
    assert fn.ssd_chunk(128, 64, 128, 64) < fn.ssd_chunk(128, 64, 128, 8)


def hybrid_toy(say=lambda msg: None, seq=192, **widths):
    """The cell's program at toy widths with the cell's own period (nine
    Mamba-2 layers, one attention layer; the configuration's own ``toy`` is
    a hybrid of one layer of each kind, see its ``why_pattern``) over a whole
    chunk of 128 positions and half of a second."""
    from chipbench.builders import granite_hybrid as builder
    _, _, config, mix = run.load_cell(CELL)
    config = run.merge(config, config["toy"])
    config.update(num_hidden_layers=10, layer_types=KINDS, **widths)
    mix = run.merge(mix, mix["toy"])
    mix["seq"] = seq
    config["max_position_embeddings"] = max(
        seq, config["max_position_embeddings"])
    return builder.build(config, mix, 2 ** 31 + 3, say), mix


def test_the_lowered_train_step_holds_the_convolutions_kernels(monkeypatch):
    """Nine Mamba-2 layers, each mixer recomputed in the backward pass: the
    convolution reads ``xBC`` in place out of ``[z | xBC | dt]`` (at toy
    widths with a state of 64: lanes 128 to 384 of 392, with a bias) in
    ``hetu_conv_fwd`` eighteen times and ``hetu_conv_bwd`` nine, as in the
    cell's step (PERF.md section 3)."""
    from conftest import conv_calls, lowered_for_tpu
    text = lowered_for_tpu(
        monkeypatch, lambda: hybrid_toy(mamba_d_state=64)[0])
    assert conv_calls(text) == (18, 9)
    assert "x392x" in text and "x256x" in text


def test_the_lowered_train_step_holds_the_gated_norms_kernels(monkeypatch):
    """Nine Mamba-2 layers, each mixer recomputed in the backward pass, ONE
    group over all 128 channels: the gate and the norm are
    ``hetu_gated_norm_fwd`` eighteen times and ``hetu_gated_norm_bwd`` nine,
    ``z`` read out of ``[z | xBC | dt]`` (392 lanes) where it lies, and under
    ``hetu_ssm_out`` no f32 array by groups ``[.., 1, 128]`` is formed,
    forward or backward (PR 44; the ``jax.numpy`` form makes several)."""
    from conftest import arrays_under, gated_norm_calls, lowered_for_tpu
    text = lowered_for_tpu(
        monkeypatch, lambda: hybrid_toy(mamba_d_state=64)[0], debug_info=True)
    assert gated_norm_calls(text) == (18, 9)
    assert "x392x" in text
    seen, views = arrays_under(text, "hetu_ssm_out", (1, 128))
    assert seen > 90 and not views, views[:3]


def test_the_hybrid_runs_through_the_benchmarks_loop():
    """The benchmark's loop (prepare, a window, finish) over the builder's
    program at toy widths and the cell's period: every check that decides
    ``correct`` holds, the steps do not retrace, and the builder tells the
    trace checks one attention layer and the scan's kernels."""
    from hetu_tpu import telemetry
    from chipbench import loops
    telemetry.enable()
    said = []
    prog, mix = hybrid_toy(said.append)
    try:
        loop = loops.TrainLoop(prog, mix, 2 ** 31 + 3, loops.Spans(),
                               said.append)
        loop.prepare()
        rec = loop.window(1.0, loops.Tracer())
        checks = loop.finish()
        assert checks and all(ok for ok, _ in checks), checks
        assert {what.split()[2] for _, what in checks[:3]} == {
            "ce", "logits_gap", "ssd_state_gap"}
        assert rec["attempted"] >= 1 and rec["failed"] == 0
        # the warm steps count: a loaded host may fit one step in the window
        losses = loop.warm_losses + rec["losses"]
        assert len(losses) >= 2 and losses[-1] < losses[0]
        shapes = prog.expected_kernel_shapes()
        assert shapes["attention_layers"] == 1
        assert shapes["flash_dims"] == (1, 4, 192, 16)
        assert prog.KERNELS[2:] == ("hetu_ssd_fwd", "hetu_ssd_bwd")
        assert any("one group of 8 heads" in m for m in said)
        assert any("state-space scan calls traced" in m for m in said)
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
    assert "WRONG" not in out and "ssd_state_gap" in out
    assert "tied head" in out and "residuals x 0.22" in out


def test_a_bf16_state_fails_the_scan_probe(monkeypatch):
    """The f32 state-space state is the configuration's.  The probe reads
    the function the layers' ``hetu_ssm_scan`` nodes call, over one whole
    group of heads: the sound scan is within the toy limit by a wide margin,
    the same scan with its state carried in bf16 is far over it, and the
    layers do go through the function that was swapped (nine mixers traced
    it)."""
    import jax.numpy as jnp
    from hetu_tpu.ops import ssd
    calls = []

    def bf16_state(x, dt, A, B, C, chunk=None):
        calls.append(x.shape)
        return ssd.recurrent_ssd(x, dt, A, B, C, state_dtype=jnp.bfloat16)
    prog, mix = hybrid_toy(seq=2048)
    limit = mix["reference_tolerance"]["ssd_state_gap"]
    try:
        assert prog.ssd_state_gap() < limit / 10
    finally:
        prog.close()
    monkeypatch.setattr(ssd, "chunk_ssd", bf16_state)
    prog, _ = hybrid_toy(seq=2048)
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        prog.reference_loss(feed, 1)
        assert prog.eval_loss(feed)["ssd_state_gap"] > 10 * limit
        assert len(calls) == 9 + 1 and calls[-1][2] == 8   # a whole group
    finally:
        prog.close()


def test_the_tolerances_have_their_two_readings():
    """Each limit of the traffic file lies between the program's largest gap
    and the lower precision's least, both written beside it."""
    _, _, _, mix = run.load_cell(CELL)
    assert sorted(mix["reference_tolerance"]) == ["ce", "logits_gap",
                                                  "ssd_state_gap"]
    for text in (mix["reference_tolerance_why"],
                 mix["first_loss_tolerance_why"]):
        assert "fp8" in text and "PR 37" in text
    assert (mix["batch"], mix["seq"], mix["ring"], mix["warm_steps"],
            mix["mask_fraction"], mix["strategy"], mix["trace_seconds"]) == (
                1, 8192, 8, 3, 1.0, None, 4)
