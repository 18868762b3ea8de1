"""The Ling-3.0 cell's readers off the chip.  The cell's rehearsal builds
one KDA layer on the CPU, where there is no device trace (the configuration's
``why_pattern``), so the readers that read the mixers' scopes and those that
credit a recomputed layer once are held here, as ``test_granite_readers.py``
holds the Granite cell's: the hybrid (a dense layer, then ``K K K K A K``,
whole layers recomputed) is built at toy widths by the cell's builder, its
train step compiled, and a device trace synthesised from the compiled step's
own ENTRY instructions.  What the readers say is compared with the sum taken by hand.
Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import importlib

import pytest

from chipbench import flops, flops_ling3 as fl, peaks, run, selfcheck
from chipbench import trace_reduce as tr
from chipbench.metrics import _scopes

CELL = "ling-3.0-flash-vl.b1-s8192"
KIND = "TPU v5 lite"
KDA = ("hetu_kda_proj", "hetu_kda_conv", "hetu_kda_scan", "hetu_kda_out")
BLOCKS = ("hetu_mlp", "hetu_attn", "hetu_norm", "hetu_head")
STEPS, STEP_NS = 2, 80e6


def build(hybrid):
    """The cell's program at toy widths; ``hybrid``: the published period
    behind one dense layer, whole layers recomputed; else two latent-attention
    layers and no mixer."""
    _, _, config, mix = run.load_cell(CELL)
    config, mix = run.merge(config, config["toy"]), run.merge(mix, mix["toy"])
    if hybrid:
        config = run.merge(config, {"num_hidden_layers": 7,
                                    "layer_group_size": 6,
                                    "job": {"remat": "layer"}})
        mix["seq"] = 128
    else:
        config.update(num_hidden_layers=2, layer_group_size=1)
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    return (builder.build(config, mix, 2 ** 31 + 7, lambda msg: None),
            config, mix)


def synth(by_key, extra=()):
    """``(reduced trace, {scope: ms a step by hand})``: ``STEPS`` executions
    of the step; the j-th instruction (in ``by_key``'s order) runs ``1000 +
    10 j`` ns; a control-flow event is filled with three events of a body.
    ``extra``: ``(key, ns)`` events more in every step (kernels that the
    CPU's step does not have)."""
    flat = [(key, scope) for key, scopes in by_key.items()
            for scope in scopes]
    stolen = next(key for key, scope in flat
                  if scope and not key.startswith(tr.CONTAINERS))
    want = dict.fromkeys(KDA + BLOCKS, 0.0)
    events, modules, host = [], [], []
    for step in range(STEPS):
        t0 = 1e9 + step * STEP_NS
        host.append((t0, STEP_NS - 2e3, "executor_run"))
        at = t0 + 1e3
        for j, (key, scope) in enumerate(flat):
            ns = 1000.0 + 10 * j
            events.append((at, ns, key))
            if key.startswith(tr.CONTAINERS):
                inner = ns / 8
                for i in range(3):
                    events.append((at + (2 * i + 1) * inner, inner / 2,
                                   stolen))
            if scope:
                want[scope] += ns * 1e-6 / STEPS
            at += ns + 50.0
        for key, ns in extra:
            events.append((at, ns, key))
            at += ns + 50.0
        assert at < t0 + STEP_NS - 3e3
        modules.append((t0 + 500.0, at - t0, "jit_step_fn"))
    events.sort(key=lambda e: e[0])
    return ({"devices": {0: events}, "modules": {0: modules}, "host": host},
            want)


#: a recomputed layer's flash events a step: two forward, one backward
FLASH = (("hetu_flash_fwd.1", 4e5), ("hetu_flash_fwd.2", 4e5),
         ("hetu_flash_bwd.1", 9e5))


@pytest.fixture(scope="module")
def hybrid():
    prog, config, mix = build(hybrid=True)
    hlo = prog.ex.subexecutor["train"].lower_compiled().as_text()
    reduced, want = synth(_scopes.entry_scopes(hlo, KDA + BLOCKS), FLASH)
    said = []
    ctx = selfcheck.trace_ctx(reduced, prog, KIND, said.append)
    ends = [10.0 + 0.5 * (i + 1) for i in range(8)]
    ctx.update(config=config, mix=mix, cell={"chips": 1}, registry={},
               rec={"t0": 10.0, "step_ends": ends,
                    "tokens_per_step": prog.tokens_per_step})
    yield ctx, want, said
    prog.close()


def test_the_hybrids_step_carries_every_scope(hybrid):
    ctx, want, _ = hybrid
    assert all(want[s] > 0 for s in KDA + BLOCKS), want


def test_kda_block_is_the_sum_of_its_scopes(hybrid):
    ctx, want, said = hybrid
    del said[:]
    got = run.reader("kda_block_device_ms_per_step")(ctx)
    assert got == pytest.approx(sum(want[s] for s in KDA), rel=1e-9)
    assert f"{STEPS} executions of 'jit_step_fn'" in said[0]


def test_kda_scan_roofline_credits_six_layers_once(hybrid):
    """The work is ``flops_ling3.kda_step`` at ``job.scan_chunk``, six KDA
    layers a step, over all the time under ``hetu_kda_scan`` (the recomputed
    forward pass included)."""
    ctx, want, _ = hybrid
    c, prog = ctx["config"], ctx["program"]
    assert fl.layer_counts(c) == (6, 1, 1, 6)
    ops, nbytes = fl.kda_step(c, prog.tokens_per_step, 64)
    least, _ = flops.roofline_seconds(ops, nbytes, peaks.peaks_for(KIND))
    by_hand = 100.0 * 6 * least / (want["hetu_kda_scan"] * 1e-3)
    assert run.reader("kda_scan_roofline")(ctx) == pytest.approx(by_hand,
                                                                 rel=1e-9)


#: what the reader read of ``FLASH`` at the parent of PR 54, where it took the
#: required passes from the forward events seen
FLASH_ROOFLINE_AT_PR53 = 0.017651368239603532


@pytest.mark.parametrize("kept", [False, True])
def test_flash_roofline_credits_a_recomputed_layer_once(hybrid, kept):
    """Two forward events and one backward a step, or ONE forward where the
    kernel's output is kept through the recomputation: ONE pass each is
    required (the configuration's ``attention_passes``), at scores 48 and
    values 32 wide (the toy's), causal."""
    ctx, _, _ = hybrid
    prog = ctx["program"]
    want = prog.expected_kernel_shapes()
    assert prog.forward_passes == 2 and want["score_dim"] == 48
    assert (want["attention_passes"], want["attention_layers"]) == (1, 2)
    flash = [e for e in FLASH if not (kept and e[0] == "hetu_flash_fwd.2")]
    if kept:
        reduced = ctx["trace"]["reduced"]
        reduced = dict(reduced, devices={0: [
            e for e in reduced["devices"][0] if e[2] != "hetu_flash_fwd.2"]})
        ctx = dict(ctx, trace=dict(ctx["trace"], reduced=reduced))
    pk = peaks.peaks_for(KIND)
    least = 0.0
    for name in ("forward", "backward"):
        ops, nbytes = fl.flash_pass(name, want["flash_rows"], prog.seq, 48,
                                    want["head_dim"])
        least += flops.roofline_seconds(ops / 2, nbytes, pk)[0] * STEPS
    measured = STEPS * sum(ns for _, ns in flash) * 1e-9
    got = run.reader("flash_roofline")(ctx)
    assert got == pytest.approx(100.0 * least / measured, rel=1e-9)
    if kept:
        assert got == pytest.approx(FLASH_ROOFLINE_AT_PR53 * 17 / 13,
                                    rel=1e-9)
    else:
        assert got == pytest.approx(FLASH_ROOFLINE_AT_PR53, abs=1e-9)
        # the accepted reader would credit every forward event a backward
        # pass
        other = dict(ctx, config=dict(ctx["config"], builder="qwen3_next"))
        assert run.reader("flash_roofline")(other) > 1.5 * got


def test_mfu_credits_the_models_operations_and_nothing_recomputed(hybrid):
    ctx, _, _ = hybrid
    c, prog = ctx["config"], ctx["program"]
    held = c["num_experts_per_tok"] * c["num_experts"] / c["deployment"][
        "num_experts"]
    total = sum(fl.forward_flops_per_token(c, prog.seq, held).values())
    rate = prog.tokens_per_step * 8 / 4.0
    got = run.reader("mfu")(ctx)
    assert got == pytest.approx(100.0 * 3 * total * rate / 197e12, rel=1e-9)
    assert run.reader("mfu")(dict(ctx, peaks=None)) is None


@pytest.mark.parametrize("name", ["kda_block_device_ms_per_step",
                                  "kda_scan_roofline",
                                  "flash_roofline",
                                  "moe_experts_roofline",
                                  "moe_block_device_ms_per_step"])
def test_nothing_to_read_without_a_trace_or_without_the_scopes(hybrid, name):
    """No trace: None.  A step with no KDA mixer (the rehearsal's program; a
    parent commit's, whatever it runs): None, said, not raised."""
    ctx, _, _ = hybrid
    read = run.reader(name)
    assert read(dict(ctx, trace=None)) is None
    if not name.startswith("kda"):
        return
    prog, config, _ = build(hybrid=False)
    try:
        said = []
        plain = dict(ctx, program=prog, config=config, say=said.append)
        assert read(plain) is None
        assert any("carries" in line for line in said), said
    finally:
        prog.close()
