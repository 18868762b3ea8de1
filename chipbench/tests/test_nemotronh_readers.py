"""The Nemotron-H cell's readers off the chip.  The cell's rehearsal builds
one block of each kind on the CPU, where there is no device trace (the
configuration's ``why_pattern``), so ``ssm_block_device_ms_per_step``,
``ssd_scan_roofline`` and the family's readers of the expert blocks
(``metrics/*.nemotron_h.py``) are held here: the hybrid
(``MEMEM*EME``) is built at toy widths by the cell's builder, its train step
compiled, and a device trace synthesised from the compiled step's own ENTRY
instructions: one event an instruction with a time of its own, every
control-flow instruction (the walks over chunk states are ``while`` loops)
filled with events of its bodies, some under keys that ENTRY has too, and
grouped-product events beside them.  What the readers say is compared with
the sum taken by hand.  Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import importlib

import pytest

from chipbench import flops, flops_nemotronh as fn, peaks, run, selfcheck
from chipbench import trace_reduce as tr
from chipbench.metrics import _moe, _scopes

CELL = "nemotron-3-nano-30b-a3b.b1-s8192"
KIND = "TPU v5 lite"
SSM = ("hetu_ssm_proj", "hetu_ssm_conv", "hetu_ssm_scan", "hetu_ssm_out")
MOE = _moe.SCOPES + ("hetu_moe_shared",)
STEPS, STEP_NS = 2, 80e6
GMM_NS = {"hetu_moe_gmm_fwd": 3000.0, "hetu_moe_gmm_dx": 4000.0,
          "hetu_moe_gmm_dw": 5000.0}
#: pairs a block and step the fabricated counters say were computed here
PAIRS = {"layer0": 40.0, "layer1": 24.0, "layer2": 32.0, "layer3": 16.0}
ELSEWHERE, WARM = 96.0, 3


def build(hybrid):
    """The cell's program at toy widths; ``hybrid``: the cell's pattern over
    sixteen chunks of positions, so that the walk is a loop; else two
    attention blocks and no mixer."""
    _, _, config, mix = run.load_cell(CELL)
    config, mix = run.merge(config, config["toy"]), run.merge(mix, mix["toy"])
    if hybrid:
        config.update(num_hidden_layers=9,
                      hybrid_override_pattern="MEMEM*EME")
        mix["seq"] = 256
    else:
        config.update(num_hidden_layers=2, hybrid_override_pattern="**")
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    return (builder.build(config, mix, 2 ** 31 + 7, lambda msg: None),
            config, mix)


def synth(by_key):
    """``(reduced trace, {scope: ms a step by hand}, loops)``: ``STEPS``
    executions of the step; the j-th instruction (in ``by_key``'s order)
    runs ``1000 + 10 j`` ns.  A control-flow event is filled with three
    events of a body under the key of a scoped instruction that is not
    control flow, and one nested loop.  After the instructions each step
    runs four blocks' six grouped products."""
    flat = [(key, scope) for key, scopes in by_key.items()
            for scope in scopes]
    stolen = next(key for key, scope in flat
                  if scope and not key.startswith(tr.CONTAINERS))
    want = dict.fromkeys(SSM + MOE, 0.0)
    events, modules, host, loops = [], [], [], 0
    for step in range(STEPS):
        t0 = 1e9 + step * STEP_NS
        host.append((t0, STEP_NS - 2e3, "executor_run"))
        at = t0 + 1e3
        for j, (key, scope) in enumerate(flat):
            ns = 1000.0 + 10 * j
            events.append((at, ns, key))
            if key.startswith(tr.CONTAINERS):
                loops += step == 0
                inner = ns / 8
                for i in range(3):
                    events.append((at + (2 * i + 1) * inner, inner / 2,
                                   stolen))
                events.append((at + 7 * inner, inner / 2, "while_f32_1"))
                events.append((at + 7.1 * inner, inner / 4, stolen))
            if scope:
                want[scope] += ns * 1e-6 / STEPS
            at += ns + 50.0
        for _ in PAIRS:
            for name, ns in GMM_NS.items():
                for _ in range(2):                      # up and down
                    events.append((at, ns, f"{name}_custom-call_f32_8_8"))
                    at += ns + 50.0
        assert at < t0 + STEP_NS - 3e3
        modules.append((t0 + 500.0, at - t0, "jit_step_fn"))
    events.sort(key=lambda e: e[0])
    return ({"devices": {0: events}, "modules": {0: modules}, "host": host},
            want, loops)


def registry(steps):
    """Counters as ``record_moe_load`` leaves them after ``steps`` counted
    steps, in a registry snapshot's form."""
    def series(values):
        return {"samples": [{"labels": {"layer": k}, "value": v}
                            for k, v in values.items()]}
    return {
        "hetu_moe_pairs_routed_total": series(
            {k: v * steps for k, v in PAIRS.items()}),
        "hetu_moe_pairs_dropped_total": series(dict.fromkeys(PAIRS, 0.0)),
        "hetu_moe_pairs_elsewhere_total": series(
            {k: ELSEWHERE * steps for k in PAIRS}),
        "hetu_moe_expert_load_max_over_mean": series(
            dict(zip(PAIRS, (1.2, 1.9, 1.4, 1.1))))}


@pytest.fixture(scope="module")
def hybrid():
    prog, config, mix = build(hybrid=True)
    hlo = prog.ex.subexecutor["train"].lower_compiled().as_text()
    reduced, want, loops = synth(_scopes.entry_scopes(hlo, SSM + MOE))
    said = []
    ctx = selfcheck.trace_ctx(reduced, prog, KIND, said.append)
    ends = [10.0 + 0.5 * (i + 1) for i in range(8)]
    ctx.update(config=config, mix=dict(mix, warm_steps=WARM),
               cell={"chips": 1}, registry=registry(WARM + len(ends)),
               rec={"t0": 10.0, "step_ends": ends,
                    "tokens_per_step": prog.tokens_per_step})
    yield ctx, want, loops, said
    prog.close()


def test_the_hybrids_step_carries_every_scope_and_loops_under_the_scan(hybrid):
    ctx, want, loops, _ = hybrid
    assert all(want[s] > 0 for s in SSM + MOE), want
    by_key = _scopes.entry_scopes(_scopes.step_hlo(ctx), ("hetu_ssm_scan",))
    in_scan = [k for k, sc in by_key.items()
               if k.startswith(tr.CONTAINERS) and any(sc)]
    assert in_scan and loops >= 4 * 2      # forward and backward, 4 mixers


def test_ssm_block_is_the_sum_of_its_scopes_with_loops_taken_whole(hybrid):
    ctx, want, _, said = hybrid
    del said[:]
    got = _scopes.scoped_ms(ctx, SSM, "ssm")
    assert got == pytest.approx({s: want[s] for s in SSM}, rel=1e-9)
    assert f"{STEPS} executions of 'jit_step_fn'" in said[0]
    assert not any("split by counts" in line for line in said), said
    got = run.reader("ssm_block_device_ms_per_step")(ctx)
    assert got == pytest.approx(sum(want[s] for s in SSM), rel=1e-9)


def test_ssd_scan_roofline_is_the_chunked_scans_work_over_the_scope(hybrid):
    ctx, want, _, _ = hybrid
    c, prog = ctx["config"], ctx["program"]
    ops, nbytes = fn.ssd_step(c, prog.tokens_per_step)
    least, _ = flops.roofline_seconds(ops, nbytes, peaks.peaks_for(KIND))
    by_hand = 100.0 * 4 * least / (want["hetu_ssm_scan"] * 1e-3)
    got = run.reader("ssd_scan_roofline")(ctx)
    assert got == pytest.approx(by_hand, rel=1e-9)


def test_moe_block_counts_the_shared_expert(hybrid):
    ctx, want, _, _ = hybrid
    got = run.reader("moe_block_device_ms_per_step")(ctx)
    assert got == pytest.approx(sum(want[s] for s in MOE), rel=1e-9)
    assert want["hetu_moe_shared"] > 0
    assert run.reader("moe_block_device_ms_per_step")(
        dict(ctx, registry={})) is None


def test_experts_roofline_credits_six_products_of_the_pairs_here(hybrid):
    ctx, _, _, _ = hybrid
    c = ctx["config"]
    least = sum(6 * flops.roofline_seconds(*fn.held_gmm_call(
        n, c["n_routed_experts"], c["hidden_size"],
        c["moe_intermediate_size"]), peaks.peaks_for(KIND))[0]
        for n in PAIRS.values())
    measured = STEPS * len(PAIRS) * 2 * sum(GMM_NS.values()) * 1e-9
    got = run.reader("moe_experts_roofline")(ctx)
    assert got == pytest.approx(100.0 * STEPS * least / measured, rel=1e-9)


def test_mfu_and_the_counters_readers(hybrid):
    ctx, _, _, _ = hybrid
    c, prog = ctx["config"], ctx["program"]
    here = sum(PAIRS.values())
    held = c["num_experts_per_tok"] * here / (here + ELSEWHERE * len(PAIRS))
    total = sum(fn.forward_flops_per_token(c, prog.seq, held).values())
    rate = prog.tokens_per_step * 8 / 4.0
    got = run.reader("mfu")(ctx)
    assert got == pytest.approx(100.0 * 3 * total * rate / 197e12, rel=1e-9)
    assert run.reader("moe_held_pair_share")(ctx) == pytest.approx(
        100.0 * here / (here + ELSEWHERE * len(PAIRS)))
    assert run.reader("moe_dropped_share")(ctx) == 0.0
    assert run.reader("moe_load_max_over_mean")(ctx) == 1.9


@pytest.mark.parametrize("name", ["ssm_block_device_ms_per_step",
                                  "ssd_scan_roofline"])
def test_nothing_to_read_without_a_trace_or_without_the_scopes(hybrid, name):
    """No trace: None.  A step with no Mamba-2 mixer (the rehearsal's
    program; a parent commit's, whatever it runs): None, said, not raised."""
    ctx, _, _, _ = hybrid
    read = run.reader(name)
    assert read(dict(ctx, trace=None)) is None
    prog, config, _ = build(hybrid=False)
    try:
        said = []
        plain = dict(ctx, program=prog, config=config, say=said.append)
        assert read(plain) is None
        assert any("carries" in line for line in said), said
    finally:
        prog.close()
