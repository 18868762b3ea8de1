"""The Mellum cell's readers off the chip, as ``test_laguna_readers.py`` holds
the Laguna cell's: the cell's program is built at toy widths by its builder
under its strategy on four CPU devices, its train step compiled, and a device
trace synthesised from the compiled step's own ENTRY instructions ON EACH OF
THE FOUR DEVICES, with the kernels' events written in (the CPU's step has
none).  What the readers say is compared with sums taken by hand: every reader
divides like by like (events of four devices over the work of four, the block
sums a step and device), so that no share can pass 100% for being read over
one device's work.  Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import importlib

import pytest

import hetu_tpu as ht
from chipbench import flops, flops_laguna, flops_mellum as fl, loops, peaks
from chipbench import run, selfcheck
from chipbench import trace_reduce as tr
from chipbench.metrics import _blocks, _moe

CELL = "mellum2-12b-a2.5b.ep4-b4-s8192"
KIND = "TPU v5 lite"
RANKS, STEPS, STEP_NS = 4, 2, 80e6
#: a step's kernel events on ONE device: one full layer, three window layers
#: (each forward pass twice where whole layers are recomputed), three grouped
#: products forward, three dx and three dw a layer
FULL = (("hetu_flash_fwd.1", 4e5), ("hetu_flash_bwd.1", 9e5))
WINDOW = (("hetu_swa_fwd.1", 2e5), ("hetu_swa_bwd.1", 5e5))
GMM = (("hetu_moe_gmm_fwd.1", 3e5), ("hetu_moe_gmm_dx.1", 3e5),
       ("hetu_moe_gmm_dw.1", 3e5))


def kernel_events(passes):
    out = []
    for pair, layers in ((FULL, 1), (WINDOW, 3)):
        (fwd, t_fwd), (bwd, t_bwd) = pair
        out += [(fwd, t_fwd)] * (layers * passes) + [(bwd, t_bwd)] * layers
    for key, ns in GMM:
        out += [(key, ns)] * 12
    return out


def synth(insts, extra):
    """``(reduced trace, {row: ms a step and device by hand})``: ``STEPS``
    executions of the step on each of ``RANKS`` devices; the j-th instruction
    that runs something takes ``1000 + 10 j`` ns and lands in its ``row``;
    ``extra``: ``(key, ns)`` events more in every step, which no ENTRY
    instruction has (``no_op_name``)."""
    runs = [i for i in insts if i["opcode"] not in _moe.NO_EVENT
            and not i["key"].startswith(_moe.NO_EVENT)
            and not i["key"].startswith(tr.CONTAINERS)]
    want, host = {}, []
    devices, modules = ({d: [] for d in range(RANKS)} for _ in range(2))
    for step in range(STEPS):
        t0 = 1e9 + step * STEP_NS
        host.append((t0, STEP_NS - 2e3, "executor_run"))
        for d in range(RANKS):
            at = t0 + 1e3
            for j, inst in enumerate(runs):
                ns = 1000.0 + 10 * j
                devices[d].append((at, ns, inst["key"]))
                want[inst["row"]] = want.get(inst["row"], 0.0) + ns * 1e-6 / (
                    STEPS * RANKS)
                at += ns + 50.0
            for key, ns in extra:
                devices[d].append((at, ns, key))
                want["no_op_name"] = want.get("no_op_name", 0.0) + (
                    ns * 1e-6 / (STEPS * RANKS))
                at += ns + 50.0
            assert at < t0 + STEP_NS - 3e3
            modules[d].append((t0 + 500.0, at - t0, "jit_step_fn"))
    return ({"devices": devices, "modules": modules, "host": host}, want)


@pytest.fixture(scope="module", params=["layer"])
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
    pairs = prog.tokens_per_step * config["num_experts_per_tok"]
    counted = int(mix["warm_steps"]) + len(ends)
    registry = {name: {"samples": [
        {"labels": {"layer": f"layer{i}"}, "value": value}
        for i in range(4)]} for name, value in (
            ("hetu_moe_pairs_routed_total", float(pairs * counted)),
            ("hetu_moe_pairs_dropped_total", 0.0),
            ("hetu_moe_expert_load_max_over_mean", 1.5))}
    ctx.update(config=config, mix=mix, cell={"chips": RANKS},
               registry=registry,
               rec={"t0": 10.0, "step_ends": ends,
                    "tokens_per_step": prog.tokens_per_step})
    yield ctx, want, said, passes
    prog.close()


def test_the_rows_add_up_a_step_and_device_with_the_exchange_in_them(traced):
    ctx, want, _, _ = traced
    table = _blocks.compute(dict(ctx))
    assert {"hetu_moe_exchange", "hetu_window_attn"} <= set(ht.scopes())
    assert set(table) == set(ht.scopes()) | set(_blocks.OTHER_ROWS)
    assert sum(table.values()) == pytest.approx(sum(want.values()), rel=1e-9)
    for row in ("hetu_window_attn", "hetu_attn", "hetu_head", "hetu_optim",
                "collectives"):
        assert table[row] == pytest.approx(want[row], rel=1e-9) and table[row]
    for name, rows in (
            ("window_attn_block_device_ms_per_step", ("hetu_window_attn",)),
            ("attn_block_device_ms_per_step.zaya1", ("hetu_attn",)),
            ("head_loss_device_ms_per_step.zaya1",
             ("hetu_embed", "hetu_head", "hetu_loss")),
            ("optim_device_ms_per_step.zaya1",
             ("hetu_optim", "hetu_param_cast"))):
        assert run.reader(name)(ctx) == pytest.approx(
            sum(want.get(r, 0.0) for r in rows), rel=1e-9), name


def test_the_moe_block_holds_the_exchange(traced):
    """The family's reader sums the five regions a step and device; the
    exchange's collectives are under ``hetu_moe_exchange`` by their
    ``op_name`` (``_scopes.py`` reads the scope, where ``_blocks.py`` puts
    every collective in its own row), and its own time is said beside it."""
    ctx, _, said, passes = traced
    del said[:]
    got = run.reader("moe_block_device_ms_per_step")(ctx)
    assert got is not None and got > 0
    by_scope = next(s for s in said if "device ms a step by scope" in s)
    for scope in _moe.SCOPES + (fl.EXCHANGE,):
        assert f"{scope} " in by_scope and f"{scope} 0.000" not in by_scope
    own = fl.exchange_ms(ctx)
    assert 0 < own["exposed"] <= own["total"] < got
    # the synthetic events never overlap: all of it is exposed
    assert own["exposed"] == pytest.approx(own["total"], rel=1e-9)
    line = next(s for s in said if "the exchange over 4 chips" in s)
    assert f"{own['total']:.3f} ms a step and chip" in line
    # a recomputed layer gathers its tokens a third time
    assert f"{passes + 1} all-gathers and 2 reduce-scatters a layer" in line
    # every collective under the scope is one the CPU's partitioner wrote
    hlo = _moe.step_hlo(ctx)
    named = [ln for ln in hlo.splitlines() if fl.EXCHANGE in ln
             and (" all-gather" in ln or " reduce-scatter" in ln
                  or " all-reduce" in ln or " all-to-all" in ln)]
    assert named
    assert run.reader("moe_block_device_ms_per_step")(
        dict(ctx, registry={})) is None
    assert fl.exchange_ms(dict(ctx, trace=None)) is None


def test_experts_roofline_divides_four_chips_events_by_four_chips_work(traced):
    ctx, _, said, _ = traced
    c, prog = ctx["config"], ctx["program"]
    pairs = prog.tokens_per_step * c["num_experts_per_tok"]
    t_min, _ = flops.roofline_seconds(
        *fl.held_gmm_call(pairs / RANKS, c["num_experts"] // RANKS,
                          c["hidden_size"], c["moe_intermediate_size"]),
        peaks.peaks_for(KIND))
    least = STEPS * 4 * RANKS * 9 * t_min
    measured = STEPS * RANKS * 36 * 3e5 * 1e-9
    got = run.reader("moe_experts_roofline")(ctx)
    assert got == pytest.approx(100.0 * least / measured, rel=1e-9)
    # events that take exactly the least time on every chip read 100%
    exact = [(1e9 + 1e4 * i, t_min * 1e9, "hetu_moe_gmm_fwd.7")
             for i in range(36)]
    reduced = {"devices": {d: exact for d in range(RANKS)},
               "modules": {d: [] for d in range(RANKS)},
               "host": [(1e9 - 1e3, 1e6, "executor_run")]}
    once = dict(ctx, trace=selfcheck.trace_ctx(reduced, prog, KIND)["trace"])
    assert run.reader("moe_experts_roofline")(once) == pytest.approx(100.0)
    assert run.reader("moe_experts_roofline")(dict(ctx, trace=None)) is None


def test_attention_rooflines_credit_one_chips_shard_on_each_chip(traced):
    ctx, _, _, passes = traced
    prog = ctx["program"]
    want = prog.expected_kernel_shapes()
    assert want["flash_dims"] == want["window_dims"] == (1, 8, 64, 16)
    assert want["attention_passes"] == 1 and want["window_layers"] == 3
    pk = peaks.peaks_for(KIND)
    least = 0.0
    for name in ("forward", "backward"):
        ops, nbytes = flops.flash_pass(name, 8, 64, 16)
        least += flops.roofline_seconds(ops / 2, nbytes, pk)[0]
    measured = passes * FULL[0][1] + FULL[1][1]
    assert run.reader("flash_roofline")(ctx) == pytest.approx(
        100.0 * least / (measured * 1e-9), rel=1e-9)
    least = 0.0
    for name in ("forward", "backward"):
        least += flops.roofline_seconds(*flops_laguna.window_pass(
            name, 1, 8, 2, 64, 16, 16), pk)[0]
    measured = passes * WINDOW[0][1] + WINDOW[1][1]
    assert run.reader("window_attn_roofline")(ctx) == pytest.approx(
        100.0 * least / (measured * 1e-9), rel=1e-9)
    checks = loops.TrainLoop(prog, None, 0, None, None).trace_checks(
        ctx["trace"]["reduced"])
    assert checks[1][0] and "[0, 1, 2, 3]" in checks[1][1], checks[1][1]
    assert checks[3][0], checks[3][1]


def test_mfu_is_four_chips_tokens_over_four_chips_peak(traced):
    ctx, _, _, _ = traced
    c, prog = ctx["config"], ctx["program"]
    parts = fl.forward_flops_per_token(c, prog.seq)
    rate = prog.tokens_per_step * 8 / 4.0
    assert prog.tokens_per_step == 4 * 64
    got = run.reader("mfu")(ctx)
    assert got == pytest.approx(
        100.0 * 3 * sum(parts.values()) * rate / (RANKS * 197e12), rel=1e-9)
    assert run.reader("mfu")(dict(ctx, peaks=None)) is None
    assert parts["window_attention"] == 3 * 4.0 * 8 * 16 * (
        16 * 64 - 120) / 64
    assert parts["full_attention"] == 4.0 * 8 * 16 * (64 * 65 / 2) / 64
    assert parts["experts"] == 4 * 4 * 6.0 * 64 * 32
    assert parts["head"] == 2.0 * 64 * 2048


def test_counters_read_the_hosts_counts(traced):
    ctx, _, _, _ = traced
    assert run.reader("moe_dropped_share")(ctx) == 0.0
    assert run.reader("moe_load_max_over_mean")(ctx) == 1.5
    assert run.reader("moe_dropped_share")(dict(ctx, registry={})) is None
