"""The ZAYA1 cell's readers off the chip, as ``test_xing4_readers.py`` holds
the Xing4.0 cell's: the cell's program is built at toy widths by its builder
(three layers), its train step compiled, and a device trace synthesised from
the compiled step's own ENTRY instructions (``test_laguna_readers.synth``),
with the flash kernels' events written in (the CPU's step has none).  What the
readers say is compared with sums taken by hand.  Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import importlib

import pytest

import hetu_tpu as ht
from chipbench import flops, flops_zaya1 as fz, loops, peaks, run, selfcheck
from chipbench.metrics import _blocks
from chipbench.tests.test_laguna_readers import STEPS, synth

ZAYA1_CELL = "zaya1-8b.b1-s8192"
ZAYA1_KIND = "TPU v5 lite"
#: a step's kernel events a decoder layer
ZAYA1_FLASH = (("hetu_flash_fwd.1", 4e5), ("hetu_flash_bwd.1", 9e5))
#: counters of eight counted steps: pairs a layer on held experts, elsewhere
#: and on no expert
ZAYA1_PAIRS = {"routed": 300.0, "elsewhere": 500.0, "skipped": 224.0}


def zaya1_registry(layers):
    def series(value):
        return {"samples": [{"labels": {"layer": f"layer{i}"}, "value": value}
                            for i in range(layers)]}
    return {f"hetu_moe_pairs_{k}_total": series(v)
            for k, v in ZAYA1_PAIRS.items()}


@pytest.fixture(scope="module")
def zaya1_traced():
    _, _, config, mix = run.load_cell(ZAYA1_CELL)
    config = run.merge(config, config["toy"])
    mix = run.merge(mix, mix["toy"])
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    prog = builder.build(config, mix, 2 ** 31 + 7, lambda msg: None)
    hlo = prog.ex.subexecutor["train"].lower_compiled().as_text()
    insts = _blocks.entry_instructions(hlo, ht.scopes())
    (fwd, t_fwd), (bwd, t_bwd) = ZAYA1_FLASH
    reduced, want = synth(insts, [(fwd, t_fwd)] * 3 + [(bwd, t_bwd)] * 3)
    said = []
    ctx = selfcheck.trace_ctx(reduced, prog, ZAYA1_KIND, said.append)
    ends = [10.0 + 0.5 * (i + 1) for i in range(8)]
    ctx.update(config=config, mix=mix, cell={"chips": 1},
               registry=zaya1_registry(3),
               rec={"t0": 10.0, "step_ends": ends,
                    "tokens_per_step": prog.tokens_per_step})
    yield ctx, want, said
    prog.close()


def test_zaya1_rows_add_up_with_the_new_scope(zaya1_traced):
    ctx, want, _ = zaya1_traced
    table = _blocks.compute(dict(ctx))
    assert "hetu_cca" in ht.scopes()
    assert set(table) == set(ht.scopes()) | set(_blocks.OTHER_ROWS)
    assert sum(table.values()) == pytest.approx(sum(want.values()), rel=1e-9)
    for row in ("hetu_cca", "hetu_attn", "hetu_moe_route", "hetu_head",
                "hetu_norm"):
        assert table[row] == pytest.approx(want[row], rel=1e-9) and table[row]
    # no dense FFN stands in this model: the row reads nothing
    assert not table.get("hetu_mlp")
    for name, row in (("cca_block_device_ms_per_step", "hetu_cca"),
                      ("attn_block_device_ms_per_step.zaya1", "hetu_attn")):
        assert run.reader(name)(ctx) == pytest.approx(want[row], rel=1e-9)
    rest = want.get("unscoped", 0.0) + want["no_op_name"]
    assert run.reader("step_unscoped_device_share.zaya1")(
        ctx) == pytest.approx(100.0 * rest / sum(want.values()), rel=1e-9)


def test_cca_mix_roofline_is_the_least_bytes_over_the_blocks_time(
        zaya1_traced):
    ctx, want, said = zaya1_traced
    prog, c = ctx["program"], ctx["config"]
    assert prog.expected_kernel_shapes()["cca_sublayers"] == 3
    ops, nbytes = fz.cca_sublayer(c, prog.tokens_per_step)
    lq, lk, heads, d = fz.latent(c)
    assert (lq, lk, heads, d) == (64, 32, 6, 16)
    assert nbytes == (5 * (lq + lk) + 2 * lk) * prog.tokens_per_step * 2
    t_min, _ = flops.roofline_seconds(ops, nbytes,
                                      peaks.peaks_for(ZAYA1_KIND))
    del said[:]
    got = run.reader("cca_mix_roofline")(ctx)
    assert got == pytest.approx(100.0 * 3 * t_min * 1e3 / want["hetu_cca"],
                                rel=1e-9)
    assert any("3 sublayer applications a step" in s for s in said)


def test_cca_mix_roofline_cannot_pass_100():
    """A block that moves exactly the least bytes at the chip's bandwidth
    reads 100%; the bytes are the least, so nothing reads more."""
    _, _, c, mix = run.load_cell(ZAYA1_CELL)
    pk = peaks.peaks_for(ZAYA1_KIND)
    ops, nbytes = fz.cca_sublayer(c, mix["seq"])
    assert nbytes == (5 * 1280 + 2 * 256) * 8192 * 2 == 113246208
    t_min, limit = flops.roofline_seconds(ops, nbytes, pk)
    assert limit == "hbm" and t_min == nbytes / pk["hbm_bytes_per_s"]
    # five sublayers a step at the published sizes: about 0.69 ms
    assert 5 * t_min * 1e3 == pytest.approx(0.69, abs=0.01)


def test_moe_skipped_share_is_the_counters_ratio(zaya1_traced):
    ctx, _, _ = zaya1_traced
    n = ZAYA1_PAIRS
    total = n["routed"] + n["elsewhere"] + n["skipped"]
    assert run.reader("moe_skipped_share")(ctx) == pytest.approx(
        100.0 * n["skipped"] / total)
    assert run.reader("moe_held_pair_share")(ctx) == pytest.approx(
        100.0 * n["routed"] / (n["routed"] + n["elsewhere"]))
    # a program without the counter (another family's, the parent's)
    bare = dict(ctx, registry={k: v for k, v in ctx["registry"].items()
                               if "skipped" not in k})
    assert run.reader("moe_skipped_share")(bare) is None


def test_zaya1_none_without_a_trace_or_the_scope(zaya1_traced):
    ctx, _, _ = zaya1_traced
    bare = dict(ctx, trace=None)
    bare.pop("blocks", None)
    for name in ("cca_mix_roofline", "cca_block_device_ms_per_step",
                 "moe_experts_roofline", "flash_roofline"):
        assert run.reader(name)(dict(bare)) is None, name
    other = dict(ctx, blocks={"hetu_attn": 1.0, "unscoped": 0.0})
    assert run.reader("cca_mix_roofline")(other) is None
    assert run.reader("cca_block_device_ms_per_step")(other) is None


def test_zaya1_flash_roofline_is_the_default_readers(zaya1_traced):
    ctx, _, _ = zaya1_traced
    prog = ctx["program"]
    want = prog.expected_kernel_shapes()
    assert want["flash_dims"] == (1, 4, 128, 16) and want["kv_heads"] == 2
    assert want["attention_passes"] == want["attention_layers"] == 3
    assert run.reader_path("flash_roofline", "zaya1").endswith(
        "flash_roofline.py")
    pk = peaks.peaks_for(ZAYA1_KIND)
    least = 0.0
    for name in ("forward", "backward"):
        ops, nbytes = flops.flash_pass(name, 4, 128, 16)
        least += flops.roofline_seconds(ops / 2, nbytes, pk)[0] * 3 * STEPS
    measured = STEPS * 3 * (ZAYA1_FLASH[0][1] + ZAYA1_FLASH[1][1]) * 1e-9
    assert run.reader("flash_roofline")(ctx) == pytest.approx(
        100.0 * least / measured, rel=1e-9)
    checks = loops.TrainLoop(prog, None, 0, None, None).trace_checks(
        ctx["trace"]["reduced"])
    assert checks[3][0], checks[3][1]
    assert "forward calls a required pass: 1.0" in checks[3][1]


def test_zaya1_mfu_credits_the_pairs_computed_here_alone(zaya1_traced):
    ctx, _, _ = zaya1_traced
    c, prog = ctx["config"], ctx["program"]
    n = ZAYA1_PAIRS
    held = n["routed"] / (n["routed"] + n["elsewhere"] + n["skipped"])
    parts = fz.forward_flops_per_token(c, prog.seq, held)
    rate = prog.tokens_per_step * 8 / 4.0
    assert run.reader("mfu")(ctx) == pytest.approx(
        100.0 * 3 * sum(parts.values()) * rate / 197e12, rel=1e-9)
    assert run.reader("mfu")(dict(ctx, peaks=None)) is None
    # without the counters: held experts over all seventeen choices
    bare = fz.forward_flops_per_token(c, prog.seq, 4 / 9.0)
    assert run.reader("mfu")(dict(ctx, registry={})) == pytest.approx(
        100.0 * 3 * sum(bare.values()) * rate / 197e12, rel=1e-9)
    assert parts["head"] == 2.0 * c["hidden_size"] * c["vocab_size"]
    assert parts["cca_head_mixing"] == 3 * 6 * 2 * 2.0 * 16 * 16
