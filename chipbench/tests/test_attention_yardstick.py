"""What the benchmark says of flash attention, on synthetic reduced traces
(no chip): ``loops.TrainLoop.trace_checks``, both ``flash_roofline`` readers
and ``attn_layout_copy_ms_per_step`` judge the work (rows x positions x head
size on the local shard, passes a step), not the cut into kernels or the
order of the dimensions.  Run with

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest chipbench/tests -q
"""

import importlib

import pytest

from chipbench import flops, loops, peaks, run, selfcheck
from chipbench import trace_reduce as tr
# the declarations' cases, which tier-1 collects here: this is the one file
# of this directory that tests/ imports
from chipbench.tests.test_per_layer_entries import *  # noqa: F401,F403
from chipbench.tests.test_per_layer_entries import (KIND, MASK_AT_PR45,
                                                     bench)

#: BERT-base on one shard of 64 sequences: 12 heads of 64, 512 positions
SHAPES = {"flash_dims": (64, 12, 512, 64), "flash_elements": 25165824,
          "flash_rows": 768, "head_dim": 64, "attention_layers": 12,
          "causal": False, "compute_dtype": "bfloat16"}
KERNELS = ("hetu_softmax_ce_fwd", "hetu_softmax_ce_bwd")
LAYOUTS = {"rows": "bf16_768_512_64", "in_place": "bf16_64_512_768",
           "head_groups": "bf16_64_512_12_64"}
CUTS = {"dq_dkv": ("_dq", "_dkv"), "fused": ("",)}
STEP_NS, FWD_NS, BWD_NS = 100e6, 1.3e6, 2.6e6


def program(**over):
    return selfcheck.RecordedProgram(dict(SHAPES, **over), 512, KERNELS)


def synth(fwd="bf16_768_512_64", cut=("_dq", "_dkv"), layers=12, steps=2,
          devices=(0,), bare=(), extra=(), forwards=None):
    """A reduced trace of ``steps`` steps on ``devices``: per layer one
    forward event writing ``fwd`` and the backward pass cut into the
    kernels ``cut`` (their times sum to ``BWD_NS`` whatever the cut), the
    loss kernels once a step, and ``extra`` ``(key, ns)`` events a step.
    Devices in ``bare`` run everything but flash attention.
    ``forwards(device, step)``: the forward events of a step where they are
    not one a layer (a recomputed layer's two)."""
    forwards = forwards or (lambda d, i: layers)
    host, events = [], {d: [] for d in devices}
    for i in range(steps):
        t = 1e9 + i * STEP_NS
        host.append((t, STEP_NS - 1e3, "executor_run"))
        for d in devices:
            ev, at = events[d], t + 5e6

            def put(key, ns):
                nonlocal at
                ev.append((at, ns, key))
                at += ns + 1e3
            put("jvp_hetu_softmax_ce_fwd__f32_8192", 1e6)
            for _ in range(forwards(d, i) if d not in bare else 0):
                put(f"jvp_hetu_flash_fwd__{fwd}_f32_768_1_512", FWD_NS)
            for key, ns in extra:
                put(key, ns)
            for _ in range(layers if d not in bare else 0):
                for suffix in cut:
                    put(f"transpose_jvp_hetu_flash_bwd{suffix}___"
                        "bf16_768_512_64", BWD_NS / len(cut))
            put("transpose_jvp_hetu_softmax_ce_bwd___bf16_8192_30522", 2e6)
    return {"devices": events, "modules": {}, "host": host}


def checks(reduced, prog=None):
    loop = loops.TrainLoop(prog or program(), None, 0, None, None)
    return loop.trace_checks(reduced)


def by_hand(causal=False):
    """Roofline share of 2 + 5 products over FWD_NS + BWD_NS a layer."""
    pk = peaks.peaks_for(KIND)
    least = 0.0
    for name in ("forward", "backward"):
        ops, nbytes = flops.flash_pass(name, 768, 512, 64)
        least += flops.roofline_seconds(ops / 2 if causal else ops,
                                        nbytes, pk)[0]
    return 100.0 * least / ((FWD_NS + BWD_NS) * 1e-9)


# -- (a) layouts and cuts that do the same work read the same ------------------

@pytest.mark.parametrize("cut", CUTS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_any_layout_and_cut_passes_the_trace_checks(layout, cut):
    for ok, what in checks(synth(LAYOUTS[layout], CUTS[cut])):
        assert ok, what


#: a program that recomputes every layer whole states the twelve passes a
#: step REQUIRES beside the most forward calls it may make, two a pass
RECOMPUTED = {"attention_passes": 12, "attention_layers": 24}
FORWARDS = {"twice_a_pass": 24, "once_a_pass": 12, "in_between": 18}


@pytest.mark.parametrize("calls", FORWARDS)
@pytest.mark.parametrize("devices", [(0,), (0, 1, 2, 3)])
def test_a_recomputing_program_may_run_its_forward_once_or_twice(calls,
                                                                 devices):
    """How many forward calls get a required pass done is the program's,
    between one and a whole recomputation's two; the check's line says how
    many it was."""
    reduced = synth(forwards=lambda d, i: FORWARDS[calls], devices=devices)
    results = checks(reduced, program(**RECOMPUTED))
    for ok, what in results:
        assert ok, what
    a_pass = FORWARDS[calls] / 12
    assert f"forward calls a required pass: {a_pass!r}" in results[3][1]


def test_a_program_that_states_no_required_passes_reads_as_before():
    """The five builders that recompute no attention state
    ``attention_layers`` alone: required and most are the same number."""
    ok, what = checks(synth())[3]
    assert ok and "forward calls a required pass: 1.0" in what
    ok, what = checks(synth(forwards=lambda d, i: 13))[3]
    assert not ok and "forward calls" in what


def ling3_share(forwards, steps=2, devices=(0,), **shapes):
    said = []
    ctx = selfcheck.trace_ctx(
        synth(forwards=lambda d, i: forwards, steps=steps, devices=devices),
        program(causal=True, **shapes), KIND, said.append)
    return run.reader("flash_roofline", "ling3")(ctx), said[0]


@pytest.mark.parametrize("devices", [(0,), (0, 1, 2, 3)])
@pytest.mark.parametrize("steps", [2, 3])
def test_the_recomputing_cells_reader_credits_the_required_passes(steps,
                                                                  devices):
    """The Ling-3.0 cell's reader (Ouro's and Laguna's too): the least time
    is the configuration's passes x steps x devices whatever the forward
    events seen, so a step that keeps the kernel's output reads higher by
    exactly the forward time that is gone."""
    n = steps * len(devices)
    least = by_hand(causal=True) / 100.0 * (FWD_NS + BWD_NS) * 1e-9 * 12 * n
    twice, said_twice = ling3_share(24, steps, devices, **RECOMPUTED)
    once, said_once = ling3_share(12, steps, devices, **RECOMPUTED)
    measured = 12 * n * (2 * FWD_NS + BWD_NS) * 1e-9
    assert twice == pytest.approx(100.0 * least / measured, rel=1e-12)
    assert once == pytest.approx(
        100.0 * least / (measured - 12 * n * FWD_NS * 1e-9), rel=1e-12)
    assert once == pytest.approx(by_hand(causal=True), rel=1e-12)
    for said in (said_twice, said_once):
        assert f"{12 * n} passes required (12 a step)" in said
    # a program that states no required passes: its forward calls a step
    plain, _ = ling3_share(12, steps, devices)
    assert plain == once


@pytest.mark.parametrize("reader, causal", [("flash_roofline", False),
                                            ("flash_roofline.dp4", False),
                                            ("flash_roofline", True)])
@pytest.mark.parametrize("cut", CUTS)
@pytest.mark.parametrize("layout", LAYOUTS)
def test_flash_roofline_reads_the_work_not_the_cut(layout, cut, reader,
                                                   causal):
    """The mask is what the program states (``expected_kernel_shapes``)."""
    said = []
    ctx = selfcheck.trace_ctx(synth(LAYOUTS[layout], CUTS[cut]),
                              program(causal=causal), KIND, said.append)
    assert abs(run.reader(reader)(ctx) - by_hand(causal)) < 1e-9
    assert "24 forward calls" in said[0]


def test_flash_roofline_averages_nothing_away_on_four_devices():
    ctx = selfcheck.trace_ctx(synth(devices=(0, 1, 2, 3)), program(), KIND)
    assert abs(run.reader("flash_roofline.dp4")(ctx) - by_hand()) < 1e-9
    for ok, what in checks(synth(devices=(0, 1, 2, 3))):
        assert ok, what


def test_recorded_trace_reads_what_the_reader_by_kernel_names_read():
    """The chip trace under ``testdata/``: the values recorded before the
    readers were rewritten, and the four trace checks."""
    selfcheck.check_attention()


# -- (b) what the trace checks refuse -----------------------------------------

WRONG = {
    "no_backward_event": (dict(cut=()), "hetu_flash_bwd"),
    "forward_on_the_global_batch": (dict(fwd="bf16_3072_512_64"),
                                    "local shard"),
    "forward_in_f32": (dict(fwd="f32_768_512_64"), "local shard"),
    "eleven_calls_where_twelve_layers": (dict(layers=11), "forward calls"),
    "recomputed_one_call_short_of_required": (
        dict(forwards=lambda d, i: 12 - i, over=RECOMPUTED), "forward calls"),
    "recomputed_one_call_over_a_whole_recomputation": (
        dict(forwards=lambda d, i: 24 + i, over=RECOMPUTED), "forward calls"),
    "recomputed_devices_that_differ": (
        dict(forwards=lambda d, i: 24 - 12 * d, devices=(0, 1),
             over=RECOMPUTED), "the same on every device"),
    "a_device_without_flash": (dict(devices=(0, 1), bare=(1,)),
                               "without one: [1]"),
    "no_flash_at_all": (dict(bare=(0,)), "without one: [0]"),
}


@pytest.mark.parametrize("case", WRONG)
def test_wrong_traces_fail_the_check_that_names_the_fault(case):
    kwargs, names_it = WRONG[case]
    kwargs = dict(kwargs)
    prog = program(**kwargs.pop("over", {}))
    failed = [what for ok, what in checks(synth(**kwargs), prog) if not ok]
    assert failed and any(names_it in what for what in failed), failed


def test_a_named_kernel_that_is_missing_still_fails_by_name():
    prog = program()
    prog.KERNELS = KERNELS + ("hetu_moe_gmm_fwd",)
    ok, what = checks(synth(), prog)[0]
    assert not ok and "hetu_moe_gmm_fwd" in what


def test_one_layer_at_head_128_as_the_olmoe_cell():
    shapes = {"flash_dims": (2, 16, 4096, 128), "flash_rows": 32,
              "flash_elements": 2 * 16 * 4096 * 128, "head_dim": 128,
              "attention_layers": 1}
    prog = selfcheck.RecordedProgram(dict(SHAPES, **shapes), 4096, KERNELS)
    for ok, what in checks(synth("bf16_32_4096_128", layers=1), prog):
        assert ok, what
    failed = [w for ok, w in checks(synth("bf16_32_4096_128", layers=2),
                                    prog) if not ok]
    assert len(failed) == 1 and "forward calls" in failed[0]


# -- (c) the head transposes ---------------------------------------------------

COPIES = {
    # key: counted?
    "copy_bf16_64_12_512_64": True,
    "copy_bf16_64_512_12_64": True,
    "transpose_bf16_12_64_512_64": True,
    "transpose_jvp_hetu_flash_bwd_dkv___bf16_768_512_64_bf16_768_512_64":
        False,
    "copy_bf16_64_512_768": False,
    "copy_f32_64_12_512_64": False,
    "copy_bf16_64_12_512_128": False,
    "copy_bf16_64_12_512_64_bf16_64_12_512_64": False,
    "copy-done_bf16_64_12_512_64": False,
    "copy-start_bf16_64_12_512_64_bf16_64_12_512_64_u32": False,
    "fusion_bf16_64_12_512_64": False,
}


@pytest.mark.parametrize("key", COPIES)
def test_layout_copy_counts_xlas_copies_of_the_heads_only(key):
    reduced = synth(extra=[(key, 0.25e6)] * 8, devices=(0, 1))
    ctx = selfcheck.trace_ctx(reduced, program(), KIND)
    got = run.reader("attn_layout_copy_ms_per_step")(ctx)
    assert got == pytest.approx(2.0 if COPIES[key] else 0.0, abs=1e-12)
    assert isinstance(got, float)


@pytest.mark.parametrize("name, family", [
    ("attn_layout_copy_ms_per_step", None),
    ("attn_layout_copy_ms_per_step.dp4", "bert"),
    ("attn_layout_copy_ms_per_step", "llama")])
def test_layout_copy_is_zero_with_flash_and_nothing_without(name, family):
    read, config = run.reader(name), family and {"builder": family}
    with_flash = dict(selfcheck.trace_ctx(synth(), program(), KIND),
                      config=config)
    assert read(with_flash) == 0.0
    without = dict(selfcheck.trace_ctx(synth(bare=(0,)), program(), KIND),
                   config=config)
    assert read(without) is None
    assert read(dict(with_flash, trace=None)) is None


def test_layout_copy_says_what_it_took():
    said = []
    reduced = synth(extra=[("copy_bf16_64_12_512_64", 0.18e6)] * 84
                    + [("copy_bf16_64_512_12_64", 0.2e6)] * 12)
    ctx = selfcheck.trace_ctx(reduced, program(), KIND, said.append)
    got = run.reader("attn_layout_copy_ms_per_step")(ctx)
    assert got == pytest.approx(84 * 0.18 + 12 * 0.2)
    assert "copy_bf16_64_12_512_64 x 84 = 15.120 ms" in said[0]
    assert "copy_bf16_64_512_12_64 x 12 = 2.400 ms" in said[0]


# -- the parts ----------------------------------------------------------------

@pytest.mark.parametrize("key, after, want", [
    ("jvp_hetu_flash_fwd__bf16_768_512_64_f32_768_1_512", "hetu_flash_fwd",
     ("bf16", (768, 512, 64))),
    ("hetu_flash_fwd_bf16_64_512_12_64", "hetu_flash_fwd",
     ("bf16", (64, 512, 12, 64))),
    ("hetu_flash_fwd_g128__bf16_64_512_768_f32_64_6_512", "hetu_flash_fwd",
     ("bf16", (64, 512, 768))),
    ("fusion_f32_3072_768_f32_3072_768", "", ("f32", (3072, 768))),
    ("reduce_f32", "", ("f32", ())),
    ("hetu_flash_fwd", "hetu_flash_fwd", None),
    ("copy_bf16_64_12_512_64", "hetu_flash_fwd", None),
])
def test_first_result_of_an_op_key(key, after, want):
    assert tr.first_result(key, after) == want


def test_first_result_inverts_op_key():
    name = ("%jvp_hetu_flash_fwd_.7 = (bf16[64,512,768]{2,1,0:T(8,128)(2,1)}"
            ", f32[768,1,512]{2,1,0}) custom-call(bf16[64,512,768] %a)")
    assert tr.first_result(tr.op_key(name), "hetu_flash_fwd") == (
        "bf16", (64, 512, 768))


def test_flash_passes_name_events_not_kernels():
    assert {p["events"] for p in flops.FLASH_PASSES.values()} == {
        "hetu_flash_fwd", "hetu_flash_bwd"}
    assert flops.flash_pass("forward", 768, 512, 64) == (
        2 * 2.0 * 768 * 512 * 512 * 64, 4.0 * 768 * 512 * 64 * 2)
    assert flops.flash_pass("backward", 768, 512, 64) == (
        5 * 2.0 * 768 * 512 * 512 * 64, 8.0 * 768 * 512 * 64 * 2)


# -- the benchmark's own files agree with one another ---------------------------

@pytest.mark.parametrize("cell", [w["name"] for w in bench()["workloads"]])
def test_builders_state_the_attention_work_consistently(cell):
    """Each cell's program at toy size: the element count is the product of
    the four dimensions and of rows x positions x head size;
    ``attention_layers`` is the flash forward calls of one step, counted on
    the program itself (the attention nodes of its train subgraph, one inside
    ``ht.remat()`` twice: its forward pass runs again in the backward pass);
    ``causal`` is the mask of those nodes, all alike, and the one the
    family's ``flash_roofline`` file held before the program was asked
    (``test_per_layer_entries.MASK_AT_PR45``, where the family had one)."""
    _, entry, config, mix = run.load_cell(cell)
    config, mix = run.merge(config, config["toy"]), run.merge(mix, mix["toy"])
    if entry["chips"] > 1:
        import jax
        if len(jax.devices()) < entry["chips"]:
            pytest.skip("needs --xla_force_host_platform_device_count=4")
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    prog = builder.build(config, mix, 2 ** 31 + 5, lambda msg: None)
    try:
        want = prog.expected_kernel_shapes()
        nodes = [node for node in prog.ex.subexecutor["train"].topo
                 if type(node).__name__ == "ScaledDotProductAttentionOp"]
        calls = sum(1 if node.remat_scope is None else 2 for node in nodes)
    finally:
        prog.close()
    b, h, s, d = want["flash_dims"]
    assert (s, d) == (prog.seq, want["head_dim"])
    assert want["flash_rows"] == b * h
    assert want["flash_elements"] == b * h * s * d
    assert want["attention_layers"] == calls > 0
    assert {node.causal for node in nodes} == {want["causal"]}
    assert MASK_AT_PR45.get(config["builder"], want["causal"]) is \
        want["causal"]
    assert want["compute_dtype"] in tr.HLO_DTYPES
    assert not any("flash" in k for k in prog.KERNELS)
