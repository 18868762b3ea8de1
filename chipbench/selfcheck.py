"""``python -m chipbench.selfcheck``: what can be checked where there is no
chip.  (1) The trace reduction against the small recorded trace under
``testdata/``.  (2) The traffic generator's promise that the seed never
changes the amount of work.  (3) Every cell's control flow at toy size on
the cpu platform, every line labelled, no result line.  Exits non-zero on
the first failure.  Run it under ``JAX_PLATFORMS=cpu`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``."""

from __future__ import annotations

import json
import os
import sys

import numpy as np

from . import run, traffic, trace_reduce as tr

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
