"""The Granite 4.0-H cell's readers off the chip.  The cell's rehearsal builds
one Mamba-2 layer on the CPU, where there is no device trace (the
configuration's ``why_pattern``), so the family's readers (``metrics/
*.granite_hybrid.py``) and those that read the mixers' scopes are held here, as
``test_nemotronh_readers.py`` holds the Nemotron-H cell's: the hybrid
(``MMMMM*MMMM``) is built at toy widths by the cell's builder, its train step
compiled, and a device trace synthesised from the compiled step's own ENTRY
instructions: one event an instruction with a time of its own, every
control-flow instruction (on the CPU the walks over chunk states are
``while`` loops) filled with events of its bodies.  What the readers say is
compared with the sum taken by hand.  Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import importlib

import pytest

from chipbench import flops, flops_granitehybrid as fg, peaks, run, selfcheck
from chipbench import trace_reduce as tr
from chipbench.metrics import _scopes

CELL = "granite-4.0-h-micro.b1-s8192"
KIND = "TPU v5 lite"
KINDS = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
SSM = ("hetu_ssm_proj", "hetu_ssm_conv", "hetu_ssm_scan", "hetu_ssm_out")
BLOCKS = ("hetu_mlp", "hetu_attn", "hetu_norm", "hetu_head", "hetu_embed")
STEPS, STEP_NS = 2, 80e6


def build(hybrid):
    """The cell's program at toy widths; ``hybrid``: the cell's period over
    two chunks of positions, so that the walk is a loop; else two attention
    layers and no mixer."""
    _, _, config, mix = run.load_cell(CELL)
    config, mix = run.merge(config, config["toy"]), run.merge(mix, mix["toy"])
    if hybrid:
        config.update(num_hidden_layers=10, layer_types=KINDS)
        mix["seq"] = 256
    else:
        config.update(num_hidden_layers=2, layer_types=["attention"] * 2)
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    return (builder.build(config, mix, 2 ** 31 + 7, lambda msg: None),
            config, mix)


def synth(by_key):
    """``(reduced trace, {scope: ms a step by hand}, loops)``: ``STEPS``
    executions of the step; the j-th instruction (in ``by_key``'s order)
    runs ``1000 + 10 j`` ns.  A control-flow event is filled with three
    events of a body under the key of a scoped instruction that is not
    control flow."""
    flat = [(key, scope) for key, scopes in by_key.items()
            for scope in scopes]
    stolen = next(key for key, scope in flat
                  if scope and not key.startswith(tr.CONTAINERS))
    want = dict.fromkeys(SSM + BLOCKS, 0.0)
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
    prog, config, mix = build(hybrid=True)
    hlo = prog.ex.subexecutor["train"].lower_compiled().as_text()
    reduced, want, loops = synth(_scopes.entry_scopes(hlo, SSM + BLOCKS))
    said = []
    ctx = selfcheck.trace_ctx(reduced, prog, KIND, said.append)
    ends = [10.0 + 0.5 * (i + 1) for i in range(8)]
    ctx.update(config=config, mix=mix, cell={"chips": 1}, registry={},
               rec={"t0": 10.0, "step_ends": ends,
                    "tokens_per_step": prog.tokens_per_step})
    yield ctx, want, loops, said
    prog.close()


def test_the_hybrids_step_carries_every_scope(hybrid):
    """Nine mixers' four scopes, the MLPs', the attention layer's, the scaled
    residual sums under ``hetu_norm`` and the two multipliers under
    ``hetu_embed`` and ``hetu_head``; the recomputed forward stands under the
    same names."""
    ctx, want, loops, _ = hybrid
    assert all(want[s] > 0 for s in SSM + BLOCKS), want
    by_key = _scopes.entry_scopes(_scopes.step_hlo(ctx), ("hetu_ssm_scan",))
    in_scan = [k for k, sc in by_key.items()
               if k.startswith(tr.CONTAINERS) and any(sc)]
    assert in_scan and loops >= 9 * 2      # forward and backward, 9 mixers


def test_ssm_block_is_the_sum_of_its_scopes_with_loops_taken_whole(hybrid):
    ctx, want, _, said = hybrid
    del said[:]
    got = run.reader("ssm_block_device_ms_per_step")(ctx)
    assert got == pytest.approx(sum(want[s] for s in SSM), rel=1e-9)
    assert f"{STEPS} executions of 'jit_step_fn'" in said[0]
    assert not any("split by counts" in line for line in said), said


def test_ssd_scan_roofline_credits_one_group_at_the_chunk_run(hybrid):
    """The work is ``flops_granitehybrid.ssd_step`` at ``job.scan_chunk``
    (128: not ``mamba_chunk_size``), ``C B^T`` once for all of a group's
    heads, nine layers a step, over the time under ``hetu_ssm_scan``."""
    ctx, want, _, _ = hybrid
    c, prog = ctx["config"], ctx["program"]
    assert c["job"]["scan_chunk"] == 128 and c["mamba_chunk_size"] == 256
    ops, nbytes = fg.ssd_step(c, prog.tokens_per_step, 128)
    least, _ = flops.roofline_seconds(ops, nbytes, peaks.peaks_for(KIND))
    by_hand = 100.0 * 9 * least / (want["hetu_ssm_scan"] * 1e-3)
    got = run.reader("ssd_scan_roofline")(ctx)
    assert got == pytest.approx(by_hand, rel=1e-9)
    other = fg.ssd_step(c, prog.tokens_per_step, 256)
    assert other != (ops, nbytes)


def test_mfu_credits_the_models_operations_and_nothing_recomputed(hybrid):
    ctx, _, _, _ = hybrid
    c, prog = ctx["config"], ctx["program"]
    total = sum(fg.forward_flops_per_token(c, prog.seq).values())
    rate = prog.tokens_per_step * 8 / 4.0
    got = run.reader("mfu")(ctx)
    assert got == pytest.approx(100.0 * 3 * total * rate / 197e12, rel=1e-9)
    assert run.reader("mfu")(dict(ctx, peaks=None)) is None


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
