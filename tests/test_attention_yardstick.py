"""The benchmark's synthetic-trace cases for flash attention, collected by
tier-1: ``chipbench/tests/test_attention_yardstick.py`` holds
``trace_checks``, both ``flash_roofline`` readers and
``attn_layout_copy_ms_per_step`` to traces built by hand, and lives beside
the benchmark, where ``pytest tests/`` does not look.  Its cases are
imported here unchanged; one more is built from the event names of the
kernels as they are since PR 28 (the heads read in place, one backward
kernel)."""

from chipbench.tests.test_attention_yardstick import *  # noqa: F401,F403
from chipbench.tests.test_attention_yardstick import (KIND, checks, program,
                                                      synth)
from chipbench import run, selfcheck

#: op keys of a BERT shard's two flash kernels on the chip (PR 28, call 28.6):
#: the forward writes the context [64, 512, 768] first and the logsumexp
#: [64, 6, 2, 512] second; the backward writes dq, dk, dv as [64, 512, 768]
FWD_KEY = "hetu_flash_fwd_bf16_64_512_768_f32_64_6_2_512"
BWD_KEY = "hetu_flash_bwd_bf16_64_512_768_bf16_64_512_768_bf16_64_512_768"


def in_place_trace(**kw):
    """``synth``'s trace of a step with the forward writing [64, 512, 768]
    and one backward kernel, its events renamed to the keys above."""
    reduced = synth(fwd="bf16_64_512_768", cut=("",), **kw)
    for events in reduced["devices"].values():
        for i, (start, ns, key) in enumerate(events):
            if "hetu_flash_fwd" in key:
                events[i] = (start, ns, FWD_KEY)
            elif "hetu_flash_bwd" in key:
                events[i] = (start, ns, BWD_KEY)
    return reduced


def test_in_place_heads_and_one_backward_kernel_pass_and_copy_nothing():
    for devices in ((0,), (0, 1, 2, 3)):
        reduced = in_place_trace(devices=devices)
        for ok, what in checks(reduced):
            assert ok, what
        ctx = selfcheck.trace_ctx(reduced, program(), KIND)
        for name in ("attn_layout_copy_ms_per_step",
                     "attn_layout_copy_ms_per_step.dp4"):
            assert run.reader(name)(ctx) == 0.0
    # the 3-D arrays the kernels now read and write are no head transposes,
    # and XLA's copies of them are not counted as such
    reduced = in_place_trace(extra=[("copy_bf16_64_512_768", 0.09e6)] * 12)
    ctx = selfcheck.trace_ctx(reduced, program(), KIND)
    assert run.reader("attn_layout_copy_ms_per_step")(ctx) == 0.0
