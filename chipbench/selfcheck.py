"""``python -m chipbench.selfcheck``: what can be checked where there is no
chip.  (1) The trace reduction against the small recorded trace under
``testdata/``, and on the same trace the attention readers and trace checks
against what was recorded of it.  (2) The traffic generator's promise that
the seed never changes the amount of work.  (3) Every cell's control flow at
toy size on the cpu platform, every line labelled, no result line.  Exits
non-zero on the first failure.  Run it under ``JAX_PLATFORMS=cpu`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""

from __future__ import annotations

import json
import os
import sys

import numpy as np

from . import loops, peaks, run, traffic, trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def check(ok, what):
    print(("ok    " if ok else "WRONG ") + what, flush=True)
    if not ok:
        sys.exit(1)


def brute_busy(events, lo, hi, grain):
    """Busy time by marking a fine grid: slow, obviously right."""
    n = int((hi - lo) / grain) + 1
    mark = np.zeros(n, bool)
    for s, d, _ in events:
        a = int(np.ceil((max(s, lo) - lo) / grain))
        b = int(np.floor((min(s + d, hi) - lo) / grain))
        if b >= a:
            mark[a:b + 1] = True
    return mark.sum() * grain


def check_reduction():
    with open(os.path.join(HERE, "testdata", "expected.json")) as f:
        expected = json.load(f)
    for name, want in expected.items():
        reduced = tr.load_saved(os.path.join(HERE, "testdata", name))
        summ = tr.summary(reduced, want["step_spans"], want["span_order"])
        lo, hi = summ["lo"], summ["hi"]
        for dev, events in reduced["devices"].items():
            brute = brute_busy(events, lo, hi, 100.0) * 1e-9
            got = summ["busy_s_per_device"][dev]
            check(abs(got - brute) <= 2e-3 * brute + 1e-6,
                  f"{name}: busy union on device {dev} {got:.6f} s agrees "
                  f"with a 100 ns grid {brute:.6f} s")
        idle = 1.0 - summ["busy_s"] / summ["window_s"]
        check(abs(idle - want["idle_share"]) < 1e-6,
              f"{name}: idle share {idle:.6f} as recorded "
              f"{want['idle_share']:.6f}")
        for key, sec in want["op_seconds"].items():
            got = dict(summ["device_ops"]).get(key)
            check(got is not None and abs(got - sec) < 1e-9,
                  f"{name}: {key} {got} s as recorded {sec} s")
        check([g[0] for g in summ["idle_gaps"][:3]] == want["top_gaps"],
              f"{name}: the three longest idle gaps fall to "
              f"{want['top_gaps']}")
        gap_total = sum(g[1] for g in tr.attribute_gaps(
            tr.gaps(tr.busy(next(iter(reduced["devices"].values())), lo,
                            hi), lo, hi), reduced["host"],
            want["span_order"]))
        first = next(iter(summ["busy_s_per_device"].values()))
        check(abs(gap_total + first - summ["window_s"]) < 1e-9,
              f"{name}: gaps and busy time add up to the window")


class RecordedProgram:
    """What the readers and ``trace_checks`` ask of a program, for a trace
    that was recorded: the shapes as the builder's
    ``expected_kernel_shapes`` gives them."""

    def __init__(self, shapes, seq, kernels):
        self.shapes, self.seq, self.KERNELS = dict(shapes), seq, kernels
        self.shapes["flash_dims"] = tuple(self.shapes["flash_dims"])

    def expected_kernel_shapes(self):
        return self.shapes


def trace_ctx(reduced, program, device_kind, say=lambda msg: None):
    """A reader's ``ctx`` for a reduced trace alone."""
    summ = tr.summary(reduced, loops.STEP_SPANS, loops.SPAN_ORDER)
    return {"trace": {"reduced": reduced, "summary": summ},
            "program": program, "peaks": peaks.peaks_for(device_kind),
            "say": say}


def check_attention():
    """The flash readers and the layout-copy reader give on the recorded
    trace what the readers before them gave (recorded in ``expected.json``
    before they were rewritten), and the trace passes ``trace_checks``."""
    with open(os.path.join(HERE, "testdata", "expected.json")) as f:
        expected = json.load(f)
    for name, want in expected.items():
        want = want.get("attention")
        if want is None:
            continue
        reduced = tr.load_saved(os.path.join(HERE, "testdata", name))
        program = RecordedProgram(want["shapes"], want["seq"],
                                  tuple(want["kernels"]))
        ctx = trace_ctx(reduced, program, want["device_kind"])
        got = run.reader("flash_roofline")(ctx)
        check(abs(got - want["flash_roofline"]) < 1e-9,
              f"{name}: flash_roofline {got!r} as the reader by kernel "
              f"names read it, {want['flash_roofline']!r}")
        by_hand = sum(sec for _, sec in want["layout_copies"].values())
        steps = tr.count_spans(reduced["host"], loops.STEP_SPANS)
        got = run.reader("attn_layout_copy_ms_per_step")(ctx)
        check(abs(got - want["attn_layout_copy_ms_per_step"]) < 1e-9
              and abs(got - by_hand / steps * 1e3) < 1e-9,
              f"{name}: attn_layout_copy_ms_per_step {got!r} is the "
              f"recorded copies' {by_hand:.6f} s over {steps} steps")
        loop = loops.TrainLoop(program, None, 0, None, None)
        for ok, what in loop.trace_checks(reduced):
            check(ok, f"{name}: {what}")


def check_traffic():
    for name in sorted(os.listdir(os.path.join(HERE, "traffic"))):
        mix = traffic.load(name[:-5])
        mix = run.merge(mix, mix["toy"])
        a, b = (traffic.mlm_batches(mix, seed, 2, 2048)
                for seed in (1, 2 ** 31 + 7))
        counts = {int((x["mlm_labels"] >= 0).sum()) for x in a + b}
        check(len(counts) == 1
              and not (a[0]["input_ids"] == b[0]["input_ids"]).all(),
              f"{name}: two seeds draw other tokens and mask the same "
              f"{counts} positions in every batch")


def main():
    check_reduction()
    check_attention()
    check_traffic()
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        cells = [w["name"] for w in json.load(f)["workloads"]]
    for cell in cells:
        for trace in (0, 1):
            rc = run.main(["--workload", cell, "--seed", str(2 ** 31 + 11),
                           "--seconds", "3", "--trace", str(trace)],
                          rehearsal=True)
            check(rc == 0, f"rehearsal of {cell} with --trace {trace}")
    print("selfcheck passed; nothing above is a chip result", flush=True)


if __name__ == "__main__":
    main()
