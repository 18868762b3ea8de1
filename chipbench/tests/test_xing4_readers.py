"""The Xing4.0 cell's readers off the chip, as ``test_laguna_readers.py`` holds
the Laguna cell's: the cell's program is built at toy widths by its builder
(one dense layer, one expert layer, the MTP depth's), its train step compiled,
and a device trace synthesised from the compiled step's own ENTRY
instructions (``test_laguna_readers.synth``), with the flash kernels' events
written in (the CPU's step has none).  What the readers say is compared with sums taken by hand.  Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import importlib

import pytest

import hetu_tpu as ht
from chipbench import flops, flops_xing4 as fl, loops, peaks, run, selfcheck
from chipbench.metrics import _blocks
from chipbench.tests.test_laguna_readers import STEPS, synth

XING4_CELL = "xing4.0-29b-a4b.b1-s4096"
XING4_KIND = "TPU v5 lite"
#: a step's kernel events a decoder layer
XING4_FLASH = (("hetu_flash_fwd.1", 4e5), ("hetu_flash_bwd.1", 9e5))


def xing4_kernel_events(layers, passes):
    (fwd, t_fwd), (bwd, t_bwd) = XING4_FLASH
    return [(fwd, t_fwd)] * (layers * passes) + [(bwd, t_bwd)] * layers


@pytest.fixture(scope="module", params=[None, "layer"])
def xing4_traced(request):
    _, _, config, mix = run.load_cell(XING4_CELL)
    config = run.merge(run.merge(config, config["toy"]),
                       {"job": {"remat": request.param}})
    mix = run.merge(mix, mix["toy"])
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    prog = builder.build(config, mix, 2 ** 31 + 7, lambda msg: None)
    hlo = prog.ex.subexecutor["train"].lower_compiled().as_text()
    insts = _blocks.entry_instructions(hlo, ht.scopes())
    passes = 2 if request.param == "layer" else 1
    reduced, want = synth(insts, xing4_kernel_events(3, passes))
    said = []
    ctx = selfcheck.trace_ctx(reduced, prog, XING4_KIND, said.append)
    ends = [10.0 + 0.5 * (i + 1) for i in range(8)]
    ctx.update(config=config, mix=mix, cell={"chips": 1}, registry={},
               rec={"t0": 10.0, "step_ends": ends,
                    "tokens_per_step": prog.tokens_per_step})
    yield ctx, want, said, passes
    prog.close()


def test_xing4_rows_add_up_with_the_two_new_scopes(xing4_traced):
    ctx, want, _, _ = xing4_traced
    table = _blocks.compute(dict(ctx))
    assert {"hetu_hc", "hetu_mtp"} <= set(ht.scopes())
    assert set(table) == set(ht.scopes()) | set(_blocks.OTHER_ROWS)
    assert sum(table.values()) == pytest.approx(sum(want.values()), rel=1e-9)
    for row in ("hetu_hc", "hetu_mtp", "hetu_attn", "hetu_mlp", "hetu_head"):
        assert table[row] == pytest.approx(want[row], rel=1e-9) and table[row]
    for name, row in (("hc_block_device_ms_per_step", "hetu_hc"),
                      ("mtp_block_device_ms_per_step", "hetu_mtp"),
                      ("attn_block_device_ms_per_step.xing4", "hetu_attn"),
                      ("mlp_block_device_ms_per_step.xing4", "hetu_mlp")):
        assert run.reader(name)(ctx) == pytest.approx(want[row], rel=1e-9)
    rest = want.get("unscoped", 0.0) + want["no_op_name"]
    assert run.reader("step_unscoped_device_share.xing4")(
        ctx) == pytest.approx(100.0 * rest / sum(want.values()), rel=1e-9)


def test_hc_mix_roofline_is_the_least_bytes_over_the_blocks_time(
        xing4_traced):
    ctx, want, said, _ = xing4_traced
    prog, c = ctx["program"], ctx["config"]
    assert prog.expected_kernel_shapes()["hc_sublayers"] == 6
    ops, nbytes = fl.hc_sublayer(c, prog.tokens_per_step)
    assert nbytes == 33 * c["hidden_size"] * prog.tokens_per_step * 2
    t_min, limit = flops.roofline_seconds(ops, nbytes,
                                          peaks.peaks_for(XING4_KIND))
    del said[:]
    got = run.reader("hc_mix_roofline")(ctx)
    assert got == pytest.approx(100.0 * 6 * t_min * 1e3 / want["hetu_hc"],
                                rel=1e-9)
    assert any("6 sublayer applications a step" in s for s in said)


def test_hc_mix_roofline_cannot_pass_100():
    """A block that moves exactly the least bytes at the chip's bandwidth
    reads 100%; the bytes are the least, so nothing reads more."""
    _, _, c, mix = run.load_cell(XING4_CELL)
    pk = peaks.peaks_for(XING4_KIND)
    ops, nbytes = fl.hc_sublayer(c, mix["seq"])
    t_min, limit = flops.roofline_seconds(ops, nbytes, pk)
    assert limit == "hbm" and t_min == nbytes / pk["hbm_bytes_per_s"]
    # 12 sublayers a step at the published sizes: about 14 ms
    assert 12 * t_min * 1e3 == pytest.approx(14.2, abs=0.1)


def test_xing4_none_without_a_trace_or_the_scopes(xing4_traced):
    ctx, _, _, _ = xing4_traced
    bare = dict(ctx, trace=None)
    bare.pop("blocks", None)
    for name in ("hc_mix_roofline", "hc_block_device_ms_per_step",
                 "mtp_block_device_ms_per_step", "moe_experts_roofline",
                 "flash_roofline"):
        assert run.reader(name)(dict(bare)) is None, name
    # a program whose table has no such row (another family's, the parent's)
    other = dict(ctx, blocks={"hetu_attn": 1.0, "unscoped": 0.0})
    assert run.reader("hc_mix_roofline")(other) is None
    assert run.reader("hc_block_device_ms_per_step")(other) is None
    assert run.reader("mtp_block_device_ms_per_step")(other) is None


def test_xing4_flash_roofline_requires_a_pass_a_decoder_layer(xing4_traced):
    ctx, _, _, passes = xing4_traced
    prog = ctx["program"]
    want = prog.expected_kernel_shapes()
    assert want["flash_dims"] == (1, 2, 64, 32) and want["score_dim"] == 48
    assert want["attention_passes"] == 3
    assert want["attention_layers"] == 3 * passes
    pk = peaks.peaks_for(XING4_KIND)
    least = 0.0
    for name in ("forward", "backward"):
        ops, nbytes = fl.flash_pass(name, 2, 64, 48, 32)
        least += flops.roofline_seconds(ops / 2, nbytes, pk)[0] * 3 * (
            STEPS)
    measured = STEPS * 3 * (passes * XING4_FLASH[0][1]
                                  + XING4_FLASH[1][1]) * 1e-9
    got = run.reader("flash_roofline")(ctx)
    assert got == pytest.approx(100.0 * least / measured, rel=1e-9)
    checks = loops.TrainLoop(prog, None, 0, None, None).trace_checks(
        ctx["trace"]["reduced"])
    assert checks[3][0], checks[3][1]
    assert f"forward calls a required pass: {passes:.1f}" in checks[3][1]


def test_xing4_mfu_credits_both_head_passes_and_the_held_pairs(xing4_traced):
    ctx, _, _, _ = xing4_traced
    c, prog = ctx["config"], ctx["program"]
    held = c["num_experts_per_tok"] * c["n_routed_experts"] / c[
        "deployment"]["n_routed_experts"]
    parts = fl.forward_flops_per_token(c, prog.seq, held)
    rate = prog.tokens_per_step * 8 / 4.0
    got = run.reader("mfu")(ctx)
    assert got == pytest.approx(
        100.0 * 3 * sum(parts.values()) * rate / 197e12, rel=1e-9)
    assert run.reader("mfu")(dict(ctx, peaks=None)) is None
    assert parts["head"] == 2 * 2.0 * c["hidden_size"] * c["vocab_size"]
    assert parts["mtp_combine"] == 4.0 * c["hidden_size"] ** 2
    assert fl.layer_counts(c) == (3, 1, 2, 6)
