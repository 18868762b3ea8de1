"""The SambaY cell's readers off the chip, as ``test_sdar_readers.py`` holds the
SDAR cell's: the cell's program is built at toy widths by its builder (six
layers, every kind), its train step compiled, and a device trace synthesised
from the compiled step's own ENTRY instructions
(``test_laguna_readers.synth``), with the flash and the window kernels' events
written in (the CPU's step has none).  What the readers say is compared with
sums taken by hand.  Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import importlib

import pytest

import hetu_tpu as ht
from chipbench import flops, flops_phi4flash as fp, loops, peaks, run
from chipbench import selfcheck
from chipbench.metrics import _blocks
from chipbench.tests.test_laguna_readers import STEPS, synth

CELL = "phi-4-mini-flash.b1-s16384"
KIND = "TPU v5 lite"
#: a step's kernel events: the full and the cross layer's flash passes (the
#: forward's first result is the toy's ``[B, S, 4 x 32]`` f32) and the window
#: layer's
FLASH = (("jvp_hetu_flash_fwd__f32_1_64_128_f32_4_1_1_64", 4e5),
         ("transpose_jvp_hetu_flash_bwd___f32_1_64_128", 9e5))
WINDOW = (("jvp_hetu_swa_fwd__f32_1_64_128", 1e5),
          ("transpose_jvp_hetu_swa_bwd___f32_1_64_128", 2e5))


@pytest.fixture(scope="module")
def traced():
    _, _, config, mix = run.load_cell(CELL)
    config = run.merge(config, config["toy"])
    mix = run.merge(mix, mix["toy"])
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    prog = builder.build(config, mix, 2 ** 31 + 7, lambda msg: None)
    hlo = prog.ex.subexecutor["train"].lower_compiled().as_text()
    insts = _blocks.entry_instructions(hlo, ht.scopes())
    (fwd, t_fwd), (bwd, t_bwd) = FLASH
    reduced, want = synth(insts, [(fwd, t_fwd)] * 2 + [(bwd, t_bwd)] * 2
                          + [WINDOW[0], WINDOW[1]])
    said = []
    ctx = selfcheck.trace_ctx(reduced, prog, KIND, said.append)
    ends = [10.0 + 0.5 * (i + 1) for i in range(8)]
    ctx.update(config=config, mix=mix, cell={"chips": 1}, registry={},
               rec={"t0": 10.0, "step_ends": ends,
                    "tokens_per_step": prog.tokens_per_step})
    yield ctx, want, said
    prog.close()


def test_rows_add_up_and_the_block_sums_read_this_cell(traced):
    ctx, want, _ = traced
    table = _blocks.compute(dict(ctx))
    assert sum(table.values()) == pytest.approx(sum(want.values()), rel=1e-9)
    for row in ("hetu_attn", "hetu_window_attn", "hetu_ssm_proj",
                "hetu_ssm_scan", "hetu_ssm_out", "hetu_gmu", "hetu_mlp",
                "hetu_head", "hetu_norm"):
        assert table[row] == pytest.approx(want[row], rel=1e-9) and table[row]
    assert run.reader("attn_block_device_ms_per_step.zaya1")(
        ctx) == pytest.approx(want["hetu_attn"], rel=1e-9)
    assert run.reader("mlp_block_device_ms_per_step.laguna")(
        ctx) == pytest.approx(want["hetu_mlp"], rel=1e-9)


def test_the_family_reads_its_mixers_and_the_gmu_by_scope(traced):
    ctx, want, said = traced
    assert run.reader_path("ssm_block_device_ms_per_step", "phi4flash"
                           ).endswith("ssm_block_device_ms_per_step"
                                      ".phi4flash.py")
    del said[:]
    got = run.reader("ssm_block_device_ms_per_step")(ctx)
    scopes = ("hetu_ssm_proj", "hetu_ssm_conv", "hetu_ssm_scan",
              "hetu_ssm_out", "hetu_gmu")
    assert got == pytest.approx(sum(want.get(s, 0.0) for s in scopes),
                                rel=1e-9)
    assert any("hetu_gmu" in s for s in said), said


def test_selective_scan_roofline_is_two_layers_least_over_the_scope(traced):
    ctx, want, _ = traced
    c, prog = ctx["config"], ctx["program"]
    ops, nbytes = fp.selective_scan_step(c, prog.tokens_per_step)
    inner, n = 2 * c["hidden_size"], c["assumed"]["mamba_d_state"]
    assert ops == 29.0 * prog.tokens_per_step * inner * n
    assert nbytes == prog.tokens_per_step * (inner * 22 + 6 * n * 4)
    t_min, _ = flops.roofline_seconds(ops, nbytes, peaks.peaks_for(KIND))
    assert run.reader("selective_scan_roofline")(ctx) == pytest.approx(
        100.0 * 2 * t_min / (want["hetu_ssm_scan"] * 1e-3), rel=1e-9)
    # a program without the scope (the parent's, another family's)
    other = dict(ctx, config=dict(c, builder="bert"))
    assert run.reader("selective_scan_roofline")(other) is None


def test_the_scan_cannot_pass_100_at_the_cells_shape():
    """At the cell's shape the least time is the bytes': 1.85 GB a layer and
    step, 2.26 ms at the chip's 819 GB/s; the operations' 38.9 G would take
    0.2 ms at the bf16 peak, which the vector unit never reaches."""
    _, _, config, _ = run.load_cell(CELL)
    ops, nbytes = fp.selective_scan_step(config, 16384)
    t, limit = flops.roofline_seconds(ops, nbytes, peaks.peaks_for(KIND))
    assert limit == "hbm" and round(ops / 1e9, 1) == 38.9
    assert round(nbytes / 1e9, 2) == 1.85 and round(t * 1e3, 2) == 2.26


def test_the_differential_rooflines_credit_a_pairs_products(traced):
    ctx, _, said = traced
    prog, c = ctx["program"], ctx["config"]
    shapes = prog.expected_kernel_shapes()
    assert shapes["flash_dims"] == (1, 4, 64, 32) and prog.seq == 64
    assert (shapes["attention_passes"], shapes["attention_layers"],
            shapes["window_layers"], shapes["window"]) == (2, 4, 1, 16)
    assert fp.pair_ops("forward", 64) == 768 and fp.pair_ops(
        "backward", 64) == 1792
    pk = peaks.peaks_for(KIND)
    for quantity, events, layers, window in (
            ("flash_roofline", FLASH, 2, None),
            ("window_attn_roofline", WINDOW, 1, 16)):
        assert run.reader_path(quantity, "phi4flash").endswith(
            f"{quantity}.phi4flash.py")
        least = 0.0
        for name in ("forward", "backward"):
            ops, nbytes = fp.differential_pass(name, 1, 64, c, window)
            pairs = (64 * 65 / 2 if window is None
                     else 16 * 64 - 16 * 15 / 2)
            assert ops == 2 * pairs * fp.pair_ops(name, 16)
            least += flops.roofline_seconds(ops, nbytes, pk)[0] * (
                layers * STEPS)
        measured = STEPS * layers * (events[0][1] + events[1][1]) * 1e-9
        assert run.reader(quantity)(ctx) == pytest.approx(
            100.0 * least / measured, rel=1e-9)
    checks = loops.TrainLoop(prog, None, 0, None, None).trace_checks(
        ctx["trace"]["reduced"])
    for ok, what in checks[1:]:
        assert ok, what
    assert "forward calls a required pass: 1.0" in checks[3][1]


def test_mfu_is_the_parts_times_three(traced):
    ctx, _, _ = traced
    c, prog = ctx["config"], ctx["program"]
    parts = fp.forward_flops_per_token(c, 64)
    rate = prog.tokens_per_step * 8 / 4.0
    assert run.reader("mfu")(ctx) == pytest.approx(
        100.0 * 3 * sum(parts.values()) * rate / 197e12, rel=1e-9)
    assert run.reader("mfu")(dict(ctx, peaks=None)) is None
    h = c["hidden_size"]
    assert parts["head"] == 2.0 * h * c["vocab_size"]
    assert parts["mlp"] == 6 * 6.0 * h * c["intermediate_size"]
    assert parts["gmu"] == 4.0 * h * 2 * h
    assert parts["full_attention"] == 2 * 2 * fp.pair_ops(
        "forward", 16) * (65 / 2)


def test_the_cells_operations_a_token():
    _, _, config, _ = run.load_cell(CELL)
    parts = fp.forward_flops_per_token(config, 16384)
    assert round(sum(parts.values()) / 1e6, 1) == 1654.2
    assert round(parts["full_attention"] / 1e6, 1) == 251.7
    assert round(parts["mlp"] / 1e6, 1) == 943.7


def test_none_without_a_trace(traced):
    ctx, _, _ = traced
    bare = dict(ctx, trace=None)
    bare.pop("blocks", None)
    for name in ("flash_roofline", "window_attn_roofline",
                 "selective_scan_roofline", "ssm_block_device_ms_per_step",
                 "attn_block_device_ms_per_step.zaya1"):
        assert run.reader(name)(dict(bare)) is None, name
