"""The Laguna cell's readers off the chip, as ``test_ling3_readers.py`` holds
the Ling-3.0 cell's: the cell's program is built at toy widths by its builder
(layer 0 and one period, window 16 of 64 positions), its train step compiled,
and a device trace synthesised from the compiled step's own ENTRY
instructions, with the two kernel pairs' events written in (the CPU's step
has none).  What the readers say is compared with sums taken by hand.  Run
with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import importlib

import pytest

import hetu_tpu as ht
from chipbench import flops, flops_laguna as fl, loops, peaks, run, selfcheck
from chipbench import trace_reduce as tr
from chipbench.metrics import _blocks, _moe

CELL = "laguna-xs.2.b1-s8192"
KIND = "TPU v5 lite"
STEPS, STEP_NS = 2, 80e6
#: a step's kernel events: two full layers and three window layers, each
#: pass once (nothing recomputed), or each forward pass twice
FULL = (("hetu_flash_fwd.1", 4e5), ("hetu_flash_bwd.1", 9e5))
WINDOW = (("hetu_swa_fwd.1", 2e5), ("hetu_swa_bwd.1", 5e5))
#: what the two readers read of ``kernel_events(passes)`` at the parent of
#: PR 54, where they took the required passes from the forward events seen
AT_PR53 = {"flash_roofline": {1: 0.013849535080304308,
                              2: 0.010590820943762118},
           "window_attn_roofline": {1: 0.021433804290947146,
                                    2: 0.01667073667073667}}


def kernel_events(passes):
    out = []
    for pair, layers in ((FULL, 2), (WINDOW, 3)):
        (fwd, t_fwd), (bwd, t_bwd) = pair
        out += [(fwd, t_fwd)] * (layers * passes) + [(bwd, t_bwd)] * layers
    return out


def synth(insts, extra):
    """``(reduced trace, {row: ms a step by hand})``: ``STEPS`` executions of
    the step; the j-th instruction that runs something takes ``1000 + 10 j``
    ns and lands in its ``row``; ``extra``: ``(key, ns)`` events more in
    every step, which no ENTRY instruction has (``no_op_name``)."""
    runs = [i for i in insts if i["opcode"] not in _moe.NO_EVENT
            and not i["key"].startswith(_moe.NO_EVENT)
            and not i["key"].startswith(tr.CONTAINERS)]
    want, events, modules, host = {}, [], [], []
    for step in range(STEPS):
        t0 = 1e9 + step * STEP_NS
        host.append((t0, STEP_NS - 2e3, "executor_run"))
        at = t0 + 1e3
        for j, inst in enumerate(runs):
            ns = 1000.0 + 10 * j
            events.append((at, ns, inst["key"]))
            want[inst["row"]] = want.get(inst["row"], 0.0) + ns * 1e-6 / STEPS
            at += ns + 50.0
        for key, ns in extra:
            events.append((at, ns, key))
            want["no_op_name"] = want.get("no_op_name", 0.0) + ns * 1e-6 / STEPS
            at += ns + 50.0
        assert at < t0 + STEP_NS - 3e3
        modules.append((t0 + 500.0, at - t0, "jit_step_fn"))
    events.sort(key=lambda e: e[0])
    return ({"devices": {0: events}, "modules": {0: modules}, "host": host},
            want)


@pytest.fixture(scope="module", params=[None, "layer"])
def traced(request):
    _, _, config, mix = run.load_cell(CELL)
    config = run.merge(run.merge(config, config["toy"]),
                       {"job": {"remat": request.param}})
    mix = run.merge(mix, mix["toy"])
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    prog = builder.build(config, mix, 2 ** 31 + 7, lambda msg: None)
    hlo = prog.ex.subexecutor["train"].lower_compiled().as_text()
    insts = _blocks.entry_instructions(hlo, ht.scopes())
    passes = 2 if request.param == "layer" else 1
    reduced, want = synth(insts, kernel_events(passes))
    said = []
    ctx = selfcheck.trace_ctx(reduced, prog, KIND, said.append)
    ends = [10.0 + 0.5 * (i + 1) for i in range(8)]
    ctx.update(config=config, mix=mix, cell={"chips": 1}, registry={},
               rec={"t0": 10.0, "step_ends": ends,
                    "tokens_per_step": prog.tokens_per_step})
    yield ctx, want, said, passes
    prog.close()


def test_the_rows_still_add_up_with_the_window_layers_row(traced):
    ctx, want, _, _ = traced
    table = _blocks.compute(dict(ctx))
    assert "hetu_window_attn" in ht.scopes()
    assert set(table) == set(ht.scopes()) | set(_blocks.OTHER_ROWS)
    assert sum(table.values()) == pytest.approx(sum(want.values()), rel=1e-9)
    for row in ("hetu_window_attn", "hetu_attn", "hetu_mlp", "hetu_head"):
        assert table[row] == pytest.approx(want[row], rel=1e-9) and table[row]
    assert run.reader("window_attn_block_device_ms_per_step")(
        ctx) == pytest.approx(want["hetu_window_attn"], rel=1e-9)
    assert run.reader("attn_block_device_ms_per_step.laguna")(
        ctx) == pytest.approx(want["hetu_attn"], rel=1e-9)


def test_window_attn_roofline_credits_the_band_once_a_layer(traced):
    ctx, _, said, passes = traced
    prog = ctx["program"]
    want = prog.expected_kernel_shapes()
    assert prog.window_forward_passes == passes
    assert want["window_dims"] == (1, 8, 64, 16) and want["window"] == 16
    assert want["window_layers"] == 3 and want["key_heads"] == 2
    pk = peaks.peaks_for(KIND)
    least = 0.0
    for name in ("forward", "backward"):
        ops, nbytes = fl.window_pass(name, 1, 8, 2, 64, 16, 16)
        least += flops.roofline_seconds(ops, nbytes, pk)[0] * 3 * STEPS
    measured = STEPS * 3 * (passes * WINDOW[0][1] + WINDOW[1][1]) * 1e-9
    del said[:]
    got = run.reader("window_attn_roofline")(ctx)
    assert got == pytest.approx(100.0 * least / measured, rel=1e-9)
    assert got == pytest.approx(AT_PR53["window_attn_roofline"][passes],
                                abs=1e-9)
    assert "3 heads" not in said[0] and "8 heads on 2 key heads" in said[0]
    assert f"{3 * STEPS} passes required (3 a step)" in said[0]


def test_window_attn_roofline_cannot_pass_100(traced):
    """Events that take exactly the band's least time read 100% where every
    forward pass is required, and under it where one of two is recomputed."""
    ctx, _, _, passes = traced
    pk = peaks.peaks_for(KIND)
    fwd, bwd = (flops.roofline_seconds(
        *fl.window_pass(name, 1, 8, 2, 64, 16, 16), pk)[0] * 1e9
        for name in ("forward", "backward"))
    events = [(1e9 + 1e4 * i, ns, key) for i, (ns, key) in enumerate(
        ([(fwd, "hetu_swa_fwd.7")] * passes + [(bwd, "hetu_swa_bwd.7")]) * 3)]
    reduced = {"devices": {0: events}, "modules": {0: []},
               "host": [(1e9 - 1e3, 1e6, "executor_run")]}
    exact = dict(ctx, trace=selfcheck.trace_ctx(reduced, ctx["program"],
                                                KIND)["trace"])
    got = run.reader("window_attn_roofline")(exact)
    assert got == pytest.approx(100.0 * (fwd + bwd) / (passes * fwd + bwd))
    assert got <= 100.0 + 1e-9 and (got > 99.999) == (passes == 1)


def test_none_without_the_events_or_the_program(traced):
    ctx, _, _, _ = traced
    devices = {0: [e for e in ctx["trace"]["reduced"]["devices"][0]
                   if "hetu_swa" not in e[2]]}
    bare = dict(ctx, trace=dict(ctx["trace"], reduced=dict(
        ctx["trace"]["reduced"], devices=devices)))
    assert run.reader("window_attn_roofline")(bare) is None
    assert run.reader("window_attn_roofline")(dict(ctx, trace=None)) is None
    # a program that states no window (another family's, the parent's)
    other = selfcheck.RecordedProgram(
        {"flash_dims": (1, 6, 64, 16), "flash_rows": 6, "head_dim": 16,
         "compute_dtype": "float32", "ce_rows": 64}, 64, ())
    assert run.reader("window_attn_roofline")(dict(ctx, program=other)) is None
    ctx = dict(ctx)
    ctx.pop("blocks", None)
    assert run.reader("window_attn_block_device_ms_per_step")(
        dict(ctx, trace=None)) is None


def test_flash_roofline_reads_the_full_layers_alone(traced):
    ctx, _, _, passes = traced
    prog = ctx["program"]
    want = prog.expected_kernel_shapes()
    assert want["flash_dims"] == (1, 6, 64, 16)
    assert want["attention_layers"] == 2 * passes == 2 * prog.forward_passes
    assert want["attention_passes"] == 2
    pk = peaks.peaks_for(KIND)
    least = 0.0
    for name in ("forward", "backward"):
        ops, nbytes = flops.flash_pass(name, 6, 64, 16)
        least += flops.roofline_seconds(ops / 2, nbytes, pk)[0] * 2 * STEPS
    measured = STEPS * 2 * (passes * FULL[0][1] + FULL[1][1]) * 1e-9
    got = run.reader("flash_roofline")(ctx)
    assert got == pytest.approx(100.0 * least / measured, rel=1e-9)
    assert got == pytest.approx(AT_PR53["flash_roofline"][passes], abs=1e-9)
    checks = flash_checks(prog, ctx["trace"]["reduced"])
    # the CPU's step has neither the loss kernels nor the grouped products,
    # and the synthetic events carry no shapes: the COUNT is what is held
    assert checks[3][0], checks[3][1]
    assert f"forward calls a required pass: {passes:.1f}" in checks[3][1]
    assert "hetu_swa_fwd" not in checks[0][1].split("missing:")[1]


def flash_checks(prog, reduced):
    return loops.TrainLoop(prog, None, 0, None, None).trace_checks(reduced)


def test_every_second_forward_call_gone(traced):
    """A program that recomputes whole layers and keeps the kernels' outputs
    through the recomputation: the readers credit it the work of the trace
    that runs each forward pass twice and read what a step that recomputes
    nothing reads, and the flash check passes.  Where nothing is recomputed
    the same cut leaves fewer forward calls than the configuration requires:
    refused."""
    ctx, _, _, passes = traced
    seen, kept = {}, []
    for e in ctx["trace"]["reduced"]["devices"][0]:
        if e[2] in (FULL[0][0], WINDOW[0][0]):
            seen[e[2]] = seen.get(e[2], 0) + 1
            if seen[e[2]] % 2 == 0:
                continue
        kept.append(e)
    reduced = dict(ctx["trace"]["reduced"], devices={0: kept})
    cut = dict(ctx, trace=dict(ctx["trace"], reduced=reduced))
    ok, what = flash_checks(ctx["program"], reduced)[3]
    if passes == 1:
        assert not ok and "forward calls" in what
        return
    assert ok and "forward calls a required pass: 1.0" in what, what
    for name, at_parent in AT_PR53.items():
        assert run.reader(name)(cut) == pytest.approx(at_parent[1], rel=1e-9)
        assert run.reader(name)(ctx) == pytest.approx(at_parent[2], rel=1e-9)


def test_mfu_credits_the_band_and_nothing_recomputed(traced):
    ctx, _, _, _ = traced
    c, prog = ctx["config"], ctx["program"]
    held = c["num_experts_per_tok"] * c["num_experts"] / c["deployment"][
        "num_experts"]
    parts = fl.forward_flops_per_token(c, prog.seq, held)
    rate = prog.tokens_per_step * 8 / 4.0
    got = run.reader("mfu")(ctx)
    assert got == pytest.approx(
        100.0 * 3 * sum(parts.values()) * rate / 197e12, rel=1e-9)
    assert run.reader("mfu")(dict(ctx, peaks=None)) is None
    # the band: 16 S - 16 x 15 / 2 pairs a head, not S (S + 1) / 2
    assert parts["window_attention"] == 3 * 4.0 * 8 * 16 * (
        16 * 64 - 120) / 64
    assert parts["full_attention"] == 2 * 4.0 * 6 * 16 * (64 * 65 / 2) / 64


def test_flops_of_the_band():
    assert fl.window_pairs(8192, 512) == 512 * 8192 - 512 * 511 / 2
    assert fl.window_pairs(8192, 8192) == 8192 * 8193 / 2
    assert fl.window_pairs(64, 100) == 64 * 65 / 2
    ops, nbytes = fl.window_pass("forward", 1, 64, 8, 8192, 128, 512)
    assert ops == 2 * 2.0 * 64 * fl.window_pairs(8192, 512) * 128
    assert nbytes == (64 + 8) * 2 * 8192 * 128 * 2
    ops, nbytes = fl.window_pass("backward", 1, 64, 8, 8192, 128, 512)
    assert ops == 5 * 2.0 * 64 * fl.window_pairs(8192, 512) * 128
    assert nbytes == (64 + 8) * 4 * 8192 * 128 * 2
