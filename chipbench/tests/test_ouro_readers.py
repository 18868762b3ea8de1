"""The Ouro cell's readers off the chip.  The cell's rehearsal runs on the CPU,
where there is no device trace, so the family's readers (``metrics/mfu.ouro.py``,
``metrics/flash_roofline.ouro.py``) and the two quantities the cell brings
(``exit_block_device_ms_per_step``, ``loop_recompute_device_share``) are held
here, as ``test_granite_readers.py`` holds the Granite cell's: the toy program
(two layers walked four times, whole layers and head passes recomputed) is
built by the cell's builder, its train step compiled, and a device trace
synthesised from the compiled step's own ENTRY instructions: one event an
instruction with a time of its own, every control-flow instruction filled
with events of its bodies.  What the readers say is compared with the sum
taken by hand.  Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import importlib

import pytest

from chipbench import flops, flops_ouro as fo, loops, peaks, run, selfcheck
from chipbench import trace_reduce as tr
from chipbench.metrics import _blocks, _scopes

CELL = "ouro-2.6b.b1-s8192"
KIND = "TPU v5 lite"
MARK = "rematted_computation"
STEPS, STEP_NS = 2, 80e6


def build(remat=True):
    _, _, config, mix = run.load_cell(CELL)
    config, mix = run.merge(config, config["toy"]), run.merge(mix, mix["toy"])
    config["job"]["remat"] = remat
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    return (builder.build(config, mix, 2 ** 31 + 7, lambda msg: None),
            config, mix)


def synth(insts):
    """``(reduced trace, {row: ms a step by hand}, ms a step under MARK)``:
    ``STEPS`` executions of the step; the j-th ENTRY instruction that
    ``_scopes.py`` keys runs ``1000 + 10 j`` ns."""
    keyed = [i for i in insts if not i["key"].startswith(_scopes.NO_EVENT)]
    stolen = next(i["key"] for i in keyed
                  if not i["key"].startswith(tr.CONTAINERS))
    rows, marked = {}, 0.0
    events, modules, host = [], [], []
    for step in range(STEPS):
        t0 = 1e9 + step * STEP_NS
        host.append((t0, STEP_NS - 2e3, "executor_run"))
        at = t0 + 1e3
        for j, inst in enumerate(keyed):
            ns = 1000.0 + 10 * j
            events.append((at, ns, inst["key"]))
            if inst["key"].startswith(tr.CONTAINERS):
                for i in range(3):
                    events.append((at + (2 * i + 1) * ns / 8, ns / 16,
                                   stolen))
            rows[inst["row"]] = rows.get(inst["row"], 0.0) + ns * 1e-6 / STEPS
            if MARK in (inst["op_name"] or ""):
                marked += ns * 1e-6 / STEPS
            at += ns + 50.0
        assert at < t0 + STEP_NS - 3e3
        modules.append((t0 + 500.0, at - t0, "jit_step_fn"))
    events.sort(key=lambda e: e[0])
    return ({"devices": {0: events}, "modules": {0: modules}, "host": host},
            rows, marked)


@pytest.fixture(scope="module")
def looped():
    import hetu_tpu as ht
    prog, config, mix = build()
    hlo = prog.ex.subexecutor["train"].lower_compiled().as_text()
    reduced, rows, marked = synth(_blocks.entry_instructions(
        hlo, tuple(ht.scopes())))
    said = []
    ctx = selfcheck.trace_ctx(reduced, prog, KIND, said.append)
    ends = [10.0 + 0.5 * (i + 1) for i in range(8)]
    ctx.update(config=config, mix=mix, cell={"chips": 1}, registry={},
               rec={"t0": 10.0, "step_ends": ends,
                    "tokens_per_step": prog.tokens_per_step})
    yield ctx, rows, marked, said
    prog.close()


def test_the_by_block_table_gains_the_exit_row_and_still_adds_up(looped):
    ctx, rows, _, said = looped
    table = _blocks.compute(ctx)
    assert "hetu_exit" in table and table["hetu_exit"] > 0
    for row, ms in rows.items():
        assert table[row] == pytest.approx(ms, rel=1e-9), row
    assert sum(table.values()) == pytest.approx(sum(rows.values()), rel=1e-9)
    assert not any("split by counts" in line for line in said), said


def test_exit_block_is_the_exit_row(looped):
    ctx, rows, _, _ = looped
    got = run.reader("exit_block_device_ms_per_step")(ctx)
    assert got == pytest.approx(rows["hetu_exit"], rel=1e-9)
    assert run.reader("head_loss_device_ms_per_step.ouro")(ctx) == \
        pytest.approx(rows["hetu_embed"] + rows["hetu_head"]
                      + rows["hetu_loss"], rel=1e-9)


def test_loop_recompute_share_is_the_marked_time_over_the_tables_sum(looped):
    """The instructions whose ``op_name`` carries jax's mark of a
    checkpoint's recomputation, over every event of the step."""
    ctx, rows, marked, _ = looped
    assert marked > 0
    got = run.reader("loop_recompute_device_share")(ctx)
    assert got == pytest.approx(100.0 * marked / sum(rows.values()),
                                rel=1e-9)
    assert 0 < got < 100


def test_mfu_credits_the_models_operations_and_nothing_recomputed(looped):
    ctx, _, _, _ = looped
    c, prog = ctx["config"], ctx["program"]
    total = sum(fo.forward_flops_per_token(c, prog.seq).values())
    rate = prog.tokens_per_step * 8 / 4.0
    got = run.reader("mfu")(ctx)
    assert got == pytest.approx(100.0 * 3 * total * rate / 197e12, rel=1e-9)
    assert run.reader("mfu")(dict(ctx, peaks=None)) is None
    assert run.reader_path("mfu", "ouro").endswith("mfu.ouro.py")


#: what the reader read of the sixteen-call trace below at the parent of PR 54,
#: where it took the required passes from the forward events seen
FLASH_ROOFLINE_AT_PR53 = 0.013336589336589336


@pytest.mark.parametrize("forwards", [16, 8])
def test_flash_roofline_credits_each_pass_once_a_layer_application(forwards):
    """Sixteen forward events a step (eight applications, each recomputed)
    or eight (the kernel's output kept through the recomputation), and eight
    backward: the roofline credits eight of each, causal, from the
    configuration's ``attention_passes``, and the flash check passes both."""
    prog, config, _ = build()
    try:
        want = prog.expected_kernel_shapes()
        assert want["attention_layers"] == 16 and prog.forward_passes == 2
        assert want["attention_passes"] == 8
        b, h, s, d = want["flash_dims"]
        host, events = [], []
        for step in range(2):
            t = 1e9 + step * 1e8
            host.append((t, 1e8 - 1e3, "executor_run"))
            at = t + 1e3
            for i in range(forwards):
                events.append((at, 2e5, f"jvp_hetu_flash_fwd__bf16_{b}_{s}"
                                        f"_{h * d}_f32"))
                at += 3e5
            for i in range(8):
                events.append((at, 5e5, f"hetu_flash_bwd__bf16_{b}_{s}"
                                        f"_{h * d}_f32"))
                at += 6e5
        reduced = {"devices": {0: events}, "modules": {0: []}, "host": host}
        ctx = dict(selfcheck.trace_ctx(reduced, prog, KIND), config=config)
        assert run.reader_path("flash_roofline", "ouro").endswith(
            "flash_roofline.ouro.py")
        pk = peaks.peaks_for(KIND)
        least = sum(flops.roofline_seconds(
            flops.flash_pass(name, b * h, s, d)[0] / 2.0,
            flops.flash_pass(name, b * h, s, d)[1], pk)[0]
            for name in ("forward", "backward")) * 16
        measured = 2 * (forwards * 2e5 + 8 * 5e5) * 1e-9
        got = run.reader("flash_roofline")(ctx)
        assert got == pytest.approx(100.0 * least / measured, rel=1e-9)
        if forwards == 16:
            assert got == pytest.approx(FLASH_ROOFLINE_AT_PR53, abs=1e-9)
        else:
            assert got > FLASH_ROOFLINE_AT_PR53
        ok, what = loops.TrainLoop(prog, None, 0, None,
                                   None).trace_checks(reduced)[3]
        assert ok and (f"forward calls a required pass: {forwards / 8!r}"
                       in what), what
    finally:
        prog.close()


@pytest.mark.parametrize("name", ["exit_block_device_ms_per_step",
                                  "loop_recompute_device_share"])
def test_nothing_to_read_without_a_trace_or_without_the_mark(looped, name):
    """No trace: None.  A step in which nothing is recomputed carries no
    mark: None, said, not raised (a parent commit's program, whatever it
    runs, has neither the mark's reader nor the exit block)."""
    ctx, _, _, _ = looped
    read = run.reader(name)
    bare = {k: v for k, v in ctx.items() if k != "blocks"}
    assert read(dict(bare, trace=None)) is None
    if name == "loop_recompute_device_share":
        prog, config, _ = build(remat=False)
        try:
            said = []
            plain = dict(ctx, program=prog, config=config, say=said.append)
            plain.pop("blocks", None)
            assert read(plain) is None
            assert any("carries" in line for line in said), said
        finally:
            prog.close()
    else:
        table = dict(ctx["blocks"])
        table.pop("hetu_exit")
        assert read(dict(ctx, blocks=table)) is None
