"""The Qwen3-Next cell's scope readers off the chip.  The cell's rehearsal
builds one Gated DeltaNet layer on the CPU, where there is no device trace
(the configuration's ``why_pattern``), so
``gdn_block_device_ms_per_step``, ``gdn_scan_roofline`` and the control flow
of ``metrics/_scopes.py`` are held here: the hybrid (three DeltaNet layers, one
attention layer) is built at toy widths by the cell's builder, its train step
compiled, and a device trace synthesised from the compiled step's own ENTRY
instructions: one event an instruction with a time of its own, every
control-flow instruction (the walks over chunk states are ``while`` loops)
filled with events of its bodies, some under keys that ENTRY has too.  What
the readers say is compared with the sum taken by hand.  Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import pytest

from chipbench import flops, flops_qwen3next as fq, peaks, run, selfcheck
from chipbench import trace_reduce as tr
from chipbench.metrics import _scopes

CELL = "qwen3-next-80b-a3b.b1-s8192"
KIND = "TPU v5 lite"
SCOPES = ("hetu_gdn_proj", "hetu_gdn_conv", "hetu_gdn_scan", "hetu_gdn_out")
STEPS, STEP_NS = 2, 50e6


def build(hybrid):
    """The cell's program at toy widths; ``hybrid``: the published layer
    pattern over four chunks of positions, so that the walk is a loop; else
    two attention layers and no DeltaNet layer."""
    import importlib
    _, _, config, mix = run.load_cell(CELL)
    config, mix = run.merge(config, config["toy"]), run.merge(mix, mix["toy"])
    if hybrid:
        config.update(num_hidden_layers=4, full_attention_interval=4)
        mix["seq"] = 256
    else:
        config.update(num_hidden_layers=2, full_attention_interval=1)
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    return builder.build(config, mix, 2 ** 31 + 7, lambda msg: None), config


def synth(by_key):
    """``(reduced trace, {scope: ms a step by hand}, loops)``: ``STEPS``
    executions of the step; the j-th instruction (in ``by_key``'s order)
    runs ``1000 + 10 j`` ns.  A control-flow event is filled with three
    events of a body under the key of a scoped instruction that is not
    control flow, and one nested loop."""
    flat = [(key, scope) for key, scopes in by_key.items()
            for scope in scopes]
    stolen = next(key for key, scope in flat
                  if scope and not key.startswith(tr.CONTAINERS))
    want, events, modules, host, loops = dict.fromkeys(SCOPES, 0.0), [], [], \
        [], 0
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
        assert at < t0 + STEP_NS - 3e3
        modules.append((t0 + 500.0, at - t0, "jit_step_fn"))
    events.sort(key=lambda e: e[0])
    return ({"devices": {0: events}, "modules": {0: modules}, "host": host},
            want, loops)


@pytest.fixture(scope="module")
def hybrid():
    prog, config = build(hybrid=True)
    hlo = prog.ex.subexecutor["train"].lower_compiled().as_text()
    reduced, want, loops = synth(_scopes.entry_scopes(hlo, SCOPES))
    said = []
    ctx = selfcheck.trace_ctx(reduced, prog, KIND, said.append)
    ctx["config"] = config
    yield ctx, want, loops, said
    prog.close()


def test_the_hybrids_step_carries_every_scope_and_loops_under_the_scan(hybrid):
    ctx, want, loops, _ = hybrid
    assert all(want[s] > 0 for s in SCOPES), want
    by_key = _scopes.entry_scopes(_scopes.step_hlo(ctx), ("hetu_gdn_scan",))
    in_scan = [k for k, sc in by_key.items()
               if k.startswith(tr.CONTAINERS) and any(sc)]
    assert in_scan and loops >= 3 * 2      # forward and backward, 3 layers


def test_scoped_ms_takes_loops_whole_and_leaves_their_bodies(hybrid):
    ctx, want, _, said = hybrid
    del said[:]
    got = _scopes.scoped_ms(ctx, SCOPES, "gdn")
    assert got == pytest.approx(want, rel=1e-9)
    assert f"{STEPS} executions of 'jit_step_fn'" in said[0]
    assert not any("split by counts" in line for line in said), said


def test_gdn_block_is_the_sum_of_its_scopes(hybrid):
    ctx, want, _, _ = hybrid
    got = run.reader("gdn_block_device_ms_per_step")(ctx)
    assert got == pytest.approx(sum(want.values()), rel=1e-9)


def test_gdn_scan_roofline_is_the_chunked_rules_work_over_the_scope(hybrid):
    from hetu_tpu.ops.gated_delta import CHUNK
    ctx, want, _, _ = hybrid
    c, prog = ctx["config"], ctx["program"]
    ops, nbytes = fq.delta_rule_step(c, prog.tokens_per_step, CHUNK)
    least, _ = flops.roofline_seconds(ops, nbytes, peaks.peaks_for(KIND))
    by_hand = 100.0 * 3 * least / (want["hetu_gdn_scan"] * 1e-3)
    got = run.reader("gdn_scan_roofline")(ctx)
    assert got == pytest.approx(by_hand, rel=1e-9)


@pytest.mark.parametrize("name", ["gdn_block_device_ms_per_step",
                                  "gdn_scan_roofline"])
def test_nothing_to_read_without_a_trace_or_without_the_scopes(hybrid, name):
    """No trace: None.  A step with no DeltaNet layer (the rehearsal's
    program; a parent commit's, whatever it runs): None, said, not raised."""
    ctx, _, _, _ = hybrid
    read = run.reader(name)
    assert read(dict(ctx, trace=None)) is None
    prog, config = build(hybrid=False)
    try:
        said = []
        plain = dict(ctx, program=prog, config=config, say=said.append)
        assert read(plain) is None
        assert any("carries" in line for line in said), said
    finally:
        prog.close()
