"""The SDAR cell's readers off the chip, as ``test_zaya1_readers.py`` holds the
ZAYA1 cell's: the cell's program is built at toy widths by its builder (two
layers), its train step compiled, and a device trace synthesised from the
compiled step's own ENTRY instructions (``test_laguna_readers.synth``), with
the flash kernels' events written in under the names the block-diffusion mask
gives them (the CPU's step has none).  What the readers say is compared with
sums taken by hand.  Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import importlib

import pytest

import hetu_tpu as ht
from chipbench import flops, flops_sdar as fs, loops, peaks, run, selfcheck
from chipbench.metrics import _blocks
from chipbench.tests.test_laguna_readers import STEPS, synth

SDAR_CELL = "sdar-30b-a3b.b1-s8192"
SDAR_KIND = "TPU v5 lite"
#: a step's kernel events a decoder layer: names that hold the flash passes'
#: as ``trace_reduce.op_key`` writes them: the forward's first result is the
#: toy's ``[B, H, 2L, d]`` f32
SDAR_FLASH = (("jvp_hetu_flash_fwd_bd__f32_1_4_128_16_f32_4_1_1_128", 4e5),
              ("transpose_jvp_hetu_flash_bwd_bd___f32_1_4_128_16", 9e5))
#: counters of eight counted steps: pairs a layer on held experts and elsewhere
SDAR_PAIRS = {"routed": 300.0, "elsewhere": 724.0}
SDAR_DRAW = {"masked": 330.0, "kept": 310.0}


def sdar_registry(layers):
    def series(value):
        return {"samples": [{"labels": {"layer": f"layer{i}"}, "value": value}
                            for i in range(layers)]}
    registry = {f"hetu_moe_pairs_{k}_total": series(v)
                for k, v in SDAR_PAIRS.items()}
    registry["hetu_diffusion_positions_total"] = {"samples": [
        {"labels": {"state": k}, "value": v} for k, v in SDAR_DRAW.items()]}
    return registry


@pytest.fixture(scope="module")
def sdar_traced():
    _, _, config, mix = run.load_cell(SDAR_CELL)
    config = run.merge(config, config["toy"])
    mix = run.merge(mix, mix["toy"])
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    prog = builder.build(config, mix, 2 ** 31 + 7, lambda msg: None)
    hlo = prog.ex.subexecutor["train"].lower_compiled().as_text()
    insts = _blocks.entry_instructions(hlo, ht.scopes())
    (fwd, t_fwd), (bwd, t_bwd) = SDAR_FLASH
    reduced, want = synth(insts, [(fwd, t_fwd)] * 2 + [(bwd, t_bwd)] * 2)
    said = []
    ctx = selfcheck.trace_ctx(reduced, prog, SDAR_KIND, said.append)
    ends = [10.0 + 0.5 * (i + 1) for i in range(8)]
    ctx.update(config=config, mix=mix, cell={"chips": 1},
               registry=sdar_registry(2),
               rec={"t0": 10.0, "step_ends": ends,
                    "tokens_per_step": prog.tokens_per_step})
    yield ctx, want, said
    prog.close()


def test_sdar_rows_add_up_and_the_zaya1_sums_read_this_cell(sdar_traced):
    ctx, want, _ = sdar_traced
    table = _blocks.compute(dict(ctx))
    assert sum(table.values()) == pytest.approx(sum(want.values()), rel=1e-9)
    for row in ("hetu_attn", "hetu_moe_route", "hetu_head", "hetu_norm"):
        assert table[row] == pytest.approx(want[row], rel=1e-9) and table[row]
    assert not table.get("hetu_mlp")            # no dense FFN in this model
    assert run.reader("attn_block_device_ms_per_step.zaya1")(
        ctx) == pytest.approx(want["hetu_attn"], rel=1e-9)
    rest = want.get("unscoped", 0.0) + want["no_op_name"]
    assert run.reader("step_unscoped_device_share.zaya1")(
        ctx) == pytest.approx(100.0 * rest / sum(want.values()), rel=1e-9)


def test_sdar_flash_roofline_credits_the_visible_pairs(sdar_traced):
    """``L^2 + K L`` pairs a head, two products forward and five backward,
    once a layer and step, over the time of every event that holds a pass's
    name; the harness's trace checks take the ``_bd`` events as the flash
    passes'."""
    ctx, _, said = sdar_traced
    prog = ctx["program"]
    shapes = prog.expected_kernel_shapes()
    assert shapes["flash_dims"] == (1, 4, 128, 16) and prog.seq == 128
    assert (shapes["causal"], shapes["mask"], shapes["block_length"]) == (
        False, "block_diffusion", 4)
    assert shapes["attention_passes"] == 2 and shapes["attention_layers"] == 4
    assert run.reader_path("flash_roofline", "sdar").endswith(
        "flash_roofline.sdar.py")
    assert fs.visible_pairs(64, 4) == 64 * 64 + 4 * 64
    pk = peaks.peaks_for(SDAR_KIND)
    least = 0.0
    for name, products in (("forward", 2), ("backward", 5)):
        ops, nbytes = fs.flash_pass(name, 4, 64, 4, 16)
        assert ops == products * 2.0 * 4 * (64 * 64 + 4 * 64) * 16
        assert nbytes == flops.flash_pass(name, 4, 128, 16)[1]
        least += flops.roofline_seconds(ops, nbytes, pk)[0] * 2 * STEPS
    measured = STEPS * 2 * (SDAR_FLASH[0][1] + SDAR_FLASH[1][1]) * 1e-9
    del said[:]
    assert run.reader("flash_roofline")(ctx) == pytest.approx(
        100.0 * least / measured, rel=1e-9)
    assert any("named *_bd {'forward': %d, 'backward': %d}"
               % (2 * STEPS, 2 * STEPS) in s for s in said), said
    checks = loops.TrainLoop(prog, None, 0, None, None).trace_checks(
        ctx["trace"]["reduced"])
    for ok, what in checks[1:]:
        assert ok, what
    assert "forward calls a required pass: 1.0" in checks[3][1]


def test_sdar_flash_roofline_cannot_pass_100_at_the_cells_shape():
    """At the cell's shape the least time is the operations': 1.10e12 forward
    and 2.75e12 backward a layer, 19.5 ms at the bf16 peak; a kernel that
    walked only visible pairs at the peak would read 100%."""
    pk = peaks.peaks_for(SDAR_KIND)
    ops_f, _ = fs.flash_pass("forward", 32, 8192, 4, 128)
    ops_b, _ = fs.flash_pass("backward", 32, 8192, 4, 128)
    assert ops_f == 2 * 2.0 * 32 * (8192 ** 2 + 4 * 8192) * 128
    assert round(ops_f / 1e12, 2) == 1.10 and ops_b == 2.5 * ops_f
    t, limit = flops.roofline_seconds(ops_f, fs.flash_pass(
        "forward", 32, 8192, 4, 128)[1], pk)
    assert limit == "compute" and round(t * 1e3, 2) == 5.58


def test_sdar_mfu_counts_a_data_token_as_two_positions(sdar_traced):
    ctx, _, _ = sdar_traced
    c, prog = ctx["config"], ctx["program"]
    n = SDAR_PAIRS
    held = c["num_experts_per_tok"] * n["routed"] / (n["routed"]
                                                     + n["elsewhere"])
    parts = fs.forward_flops_per_token(c, 64, held)
    assert prog.tokens_per_step == 64       # the data's tokens, not positions
    rate = prog.tokens_per_step * 8 / 4.0
    assert run.reader("mfu")(ctx) == pytest.approx(
        100.0 * 3 * sum(parts.values()) * rate / 197e12, rel=1e-9)
    assert run.reader("mfu")(dict(ctx, peaks=None)) is None
    # without the counters: the held share of all experts
    bare = fs.forward_flops_per_token(c, 64, 4 * 8 / 16.0)
    assert run.reader("mfu")(dict(ctx, registry={})) == pytest.approx(
        100.0 * 3 * sum(bare.values()) * rate / 197e12, rel=1e-9)
    h, d = c["hidden_size"], c["head_dim"]
    assert parts["head"] == 2.0 * h * c["vocab_size"]       # once a token
    assert parts["attention_projections"] == 2 * 2 * 2.0 * h * (
        2 * 4 * d + 2 * 2 * d)
    assert parts["masked_attention"] == 2 * 4.0 * 4 * d * (64 + 4)


def test_diffusion_masked_share_is_the_counters_ratio(sdar_traced):
    ctx, _, _ = sdar_traced
    read = run.reader("diffusion_masked_share")
    assert read(ctx) == pytest.approx(100.0 * 330 / 640)
    assert run.reader("moe_held_pair_share")(ctx) == pytest.approx(
        100.0 * 300 / 1024)
    # a program without the counter (another family's, the parent's)
    assert read(dict(ctx, registry={})) is None
    assert read(dict(ctx, registry=None)) is None


def test_sdar_none_without_a_trace(sdar_traced):
    ctx, _, _ = sdar_traced
    bare = dict(ctx, trace=None)
    bare.pop("blocks", None)
    for name in ("flash_roofline", "moe_experts_roofline",
                 "attn_block_device_ms_per_step.zaya1"):
        assert run.reader(name)(dict(bare)) is None, name
