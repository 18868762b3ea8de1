"""The EvaByte cell's readers off the chip, as ``test_phi4flash_readers.py``
holds the SambaY cell's: the cell's program is built at toy widths by its
builder, its train step compiled, and a device trace synthesised from the
compiled step's own ENTRY instructions (``test_laguna_readers.synth``), with
the EVA kernels' events written in (the CPU's step has none).  What the
readers say is compared with sums taken by hand.  Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import importlib

import pytest

import hetu_tpu as ht
from chipbench import flops, flops_evabyte as fe, loops, peaks, run
from chipbench import selfcheck
from chipbench.metrics import _blocks
from chipbench.tests.test_laguna_readers import STEPS, synth

CELL = "evabyte-6.5b.b1-s8192"
KIND = "TPU v5 lite"
#: a step's kernel events: two layers' EVA passes (the forward's first result
#: is the toy's ``[B, S, 2 x 32]`` f32)
EVA = (("jvp_hetu_eva_fwd__f32_1_64_64_f32_1_1_2_64", 4e5),
       ("transpose_jvp_hetu_eva_bwd___f32_1_64_64", 9e5))


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
    reduced, want = synth(insts, [EVA[0]] * 2 + [EVA[1]] * 2)
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
    for row in ("hetu_attn", "hetu_eva", "hetu_chunk_summary", "hetu_mlp",
                "hetu_head", "hetu_norm"):
        assert table[row] == pytest.approx(want[row], rel=1e-9) and table[row]
    assert run.reader("attn_block_device_ms_per_step.zaya1")(
        ctx) == pytest.approx(want["hetu_attn"], rel=1e-9)
    assert run.reader("mlp_block_device_ms_per_step.laguna")(
        ctx) == pytest.approx(want["hetu_mlp"], rel=1e-9)
    assert run.reader_path(
        "window_attn_block_device_ms_per_step", "evabyte").endswith(
        "window_attn_block_device_ms_per_step.evabyte.py")
    assert run.reader("window_attn_block_device_ms_per_step")(
        ctx) == pytest.approx(want["hetu_eva"] + want["hetu_chunk_summary"],
                              rel=1e-9)


def test_the_eva_roofline_credits_the_plans_pairs(traced):
    ctx, _, said = traced
    prog = ctx["program"]
    shapes = prog.expected_kernel_shapes()
    assert shapes["flash_dims"] == shapes["eva_dims"] == (1, 2, 64, 32)
    assert (shapes["attention_passes"], shapes["attention_layers"],
            shapes["eva_layers"], shapes["window"], shapes["chunk"]) == (
        2, 4, 2, 32, 4)
    # 64 positions, two windows of 32: 2 x 32 x 33 / 2 local pairs, the
    # second window's 32 queries on the first's 8 summaries
    assert fe.eva_pairs(64, 32, 4) == (1056.0, 256.0)
    pk = peaks.peaks_for(KIND)
    assert run.reader_path("window_attn_roofline", "evabyte").endswith(
        "window_attn_roofline.evabyte.py")
    least = 0.0
    for name, products, tensors in (("forward", 2, 2), ("backward", 5, 4)):
        ops, nbytes = fe.eva_pass(name, 1, 2, 64, 32, 32, 4)
        assert ops == products * 2.0 * 2 * (1056 + 256) * 32
        assert nbytes == 2 * 2 * tensors * (64 + 8) * 32 * 2
        least += flops.roofline_seconds(ops, nbytes, pk)[0] * 2 * STEPS
    measured = STEPS * 2 * (EVA[0][1] + EVA[1][1]) * 1e-9
    del said[:]
    assert run.reader("window_attn_roofline")(ctx) == pytest.approx(
        100.0 * least / measured, rel=1e-9)
    assert "'hetu_flash_': 0, 'hetu_swa_': 0" in said[0]
    # a prep pair's time is measured and earns nothing
    with_prep = dict(ctx, trace=dict(ctx["trace"], reduced=dict(
        ctx["trace"]["reduced"], devices={0: sorted(
            ctx["trace"]["reduced"]["devices"][0]
            + [(s + 1.0, measured * 1e9 / (2 * STEPS), "hetu_eva_prep_fwd_x")
               for s, _, k in ctx["trace"]["reduced"]["devices"][0]
               if "hetu_eva_fwd" in k])})))
    assert run.reader("window_attn_roofline")(with_prep) == pytest.approx(
        100.0 * least / (2 * measured), rel=1e-9)
    # a program that states no EVA layer (the parent's, another family's)
    other = selfcheck.RecordedProgram(
        {k: v for k, v in shapes.items() if k != "eva_dims"}, 64, ())
    assert run.reader("window_attn_roofline")(dict(ctx, program=other)) is None


def test_the_harness_finds_the_passes_while_the_program_lives(traced):
    """``trace_checks`` holds the EVA pair by the passes' event names, which
    are the EVA kernels' between the program's ``__init__`` and ``close``."""
    ctx, _, _ = traced
    assert {p["events"] for p in flops.FLASH_PASSES.values()} == {
        "hetu_eva_fwd", "hetu_eva_bwd"}
    checks = loops.TrainLoop(ctx["program"], None, 0, None, None
                             ).trace_checks(ctx["trace"]["reduced"])
    for ok, what in checks[1:]:
        assert ok, what
    assert "forward calls a required pass: 1.0" in checks[3][1]
    assert run.reader("attn_layout_copy_ms_per_step")(ctx) == 0.0


def test_the_passes_names_are_flashs_again_behind_the_last_program():
    """Whatever other program of the family is alive in this process."""
    from chipbench.builders import evabyte as builder
    alive = builder.pass_events.entered
    with builder.pass_events():
        with builder.pass_events():
            assert flops.FLASH_PASSES["forward"]["events"] == "hetu_eva_fwd"
        assert flops.FLASH_PASSES["backward"]["events"] == "hetu_eva_bwd"
    assert builder.pass_events.entered == alive
    assert builder.pass_events.flash == {"forward": "hetu_flash_fwd",
                                         "backward": "hetu_flash_bwd"}
    if not alive:
        assert {p["events"] for p in flops.FLASH_PASSES.values()} == {
            "hetu_flash_fwd", "hetu_flash_bwd"}


def test_softmax_ce_reads_four_bytes_an_element(traced):
    ctx, _, _ = traced
    shapes = ctx["program"].expected_kernel_shapes()
    assert shapes["ce_rows"] == 64 * 8 and shapes["ce_itemsize"] == 4
    assert flops.softmax_ce_call("hetu_softmax_ce_bwd", 512, 320, 4) == (
        5.0 * 512 * 320, 2.0 * 512 * 320 * 4)
    assert run.reader_path("softmax_ce_roofline", "evabyte").endswith(
        "softmax_ce_roofline.evabyte.py")
    # the toy's loss runs the jnp form: no event, nothing to read
    assert run.reader("softmax_ce_roofline")(ctx) is None


def test_mfu_is_the_parts_times_three(traced):
    ctx, _, _ = traced
    c, prog = ctx["config"], ctx["program"]
    parts = fe.forward_flops_per_token(c, 64)
    rate = prog.tokens_per_step * 8 / 4.0
    assert run.reader("mfu")(ctx) == pytest.approx(
        100.0 * 3 * sum(parts.values()) * rate / 197e12, rel=1e-9)
    assert run.reader("mfu")(dict(ctx, peaks=None)) is None
    h = c["hidden_size"]
    assert parts["heads"] == 2.0 * h * 8 * 320
    assert parts["mlp"] == 2 * 6.0 * h * c["intermediate_size"]
    assert parts["projections"] == 2 * 8.0 * h * h
    assert parts["eva_local"] == 2 * 4.0 * h * 1056 / 64
    assert parts["eva_remote"] == 2 * 4.0 * h * 256 / 64


def test_the_cells_operations_a_token():
    """5.16 G operations a token and step, 42 T a step: the dense products
    94% (MLP 63, projections 31), EVA's pairs 4.6 (local 3.9, remote 0.7), the
    heads 1.2."""
    _, _, config, _ = run.load_cell(CELL)
    parts = fe.forward_flops_per_token(config, 8192)
    total = sum(parts.values())

    def share(name):
        return round(100 * parts[name] / total, 1)
    assert round(3 * total / 1e9, 2) == 5.16
    assert round(3 * total * 8192 / 1e12, 1) == 42.3
    assert (share("mlp"), share("projections"), share("eva_local"),
            share("eva_remote"), share("heads")) == (62.9, 31.2, 3.9, 0.7,
                                                     1.2)
    assert sum(fe.eva_pairs(8192, 2048, 16)) == 9965568
    # at the model's 32,768 bytes the remote term is 48% of the pairs
    local, remote = fe.eva_pairs(32768, 2048, 16)
    assert round(100 * remote / (local + remote)) == 48
    local, remote = fe.eva_pairs(8192, 2048, 16)
    assert round(100 * remote / (local + remote)) == 16


def test_the_eva_roofline_cannot_pass_100_at_the_cells_shape():
    """At the cell's shape a pass's least time is its operations': 0.163 T
    forward at the bf16 peak is 0.83 ms, where the bytes' 0.29 GB take 0.35."""
    pk = peaks.peaks_for(KIND)
    ops, nbytes = fe.eva_pass("forward", 1, 32, 8192, 128, 2048, 16)
    t, limit = flops.roofline_seconds(ops, nbytes, pk)
    assert limit == "compute" and round(ops / 1e12, 3) == 0.163
    assert round(t * 1e3, 2) == 0.83


def test_none_without_a_trace(traced):
    ctx, _, _ = traced
    bare = dict(ctx, trace=None)
    bare.pop("blocks", None)
    for name in ("window_attn_roofline", "softmax_ce_roofline",
                 "window_attn_block_device_ms_per_step",
                 "attn_block_device_ms_per_step.zaya1"):
        assert run.reader(name)(dict(bare)) is None, name
