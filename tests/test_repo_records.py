"""Tier-1 static check: one benchmark, one record.

The repository's measurements are ``chipbench/`` (what ``BENCHMARK.json``
runs on the chip) and the driver's ``PERF_LEDGER.jsonl``.  A second
harness once stood beside them: a root ``bench.py`` with a mode a
subsystem, a regression differ over its CPU wall-clock captures, and
result files it wrote into the tracked tree at every test run, which the
documents then cited as evidence.  This gate (the
``test_no_wallclock_timing.py`` pattern) keeps both halves from growing
back:

* no Python file names a results file inside the checkout as a place to
  write to — a run's output goes where the caller says, or to a tmp dir;
* no document, and no docstring of the package, cites the retired harness
  or its capture files.
"""

import glob
import os
import re

ROOT = os.path.join(os.path.dirname(__file__), "..")

#: the capture files the retired harness wrote beside the sources
RESULT_FILES = re.compile(
    r"history\.jsonl|_FULL\.json|benchmarks/BASELINE\.json")
#: the retired harness itself (``ps_scale_bench.py`` and its kin are
#: other programs: the lookbehind keeps ``*_bench.py`` out)
RETIRED = re.compile(r"(?<![\w])bench\.py|perf_diff|_FULL\.json")


def _python_files(*dirs):
    for d in dirs:
        for dirpath, dirnames, files in os.walk(os.path.join(ROOT, d)):
            dirnames[:] = [x for x in dirnames if x != "__pycache__"]
            for fn in sorted(files):
                path = os.path.join(dirpath, fn)
                if fn.endswith(".py") and \
                        not os.path.samefile(path, __file__):
                    yield path


def _hits(paths, pattern):
    out = []
    for path in paths:
        with open(path, encoding="utf-8") as f:
            for no, line in enumerate(f, 1):
                if pattern.search(line):
                    rel = os.path.relpath(path, ROOT)
                    out.append(f"{rel}:{no}: {line.strip()[:90]}")
    return out


def test_no_results_file_inside_the_checkout():
    hits = _hits(_python_files("tests", "hetu_tpu", "tools", "benchmarks"),
                 RESULT_FILES)
    assert not hits, (
        "a results file inside the checkout is named as a place to "
        "write (a test run must leave `git status` clean; write under "
        "tmp_path or where the caller says):\n  " + "\n  ".join(hits))


def test_documents_cite_no_retired_harness():
    docs = [os.path.join(ROOT, "README.md"), os.path.join(ROOT, "PARITY.md"),
            os.path.join(ROOT, "MIGRATION.md"),
            os.path.join(ROOT, "chip_smoke.py")]
    docs += sorted(glob.glob(os.path.join(ROOT, "docs", "*.md")))
    docs += list(_python_files("hetu_tpu", "examples"))
    hits = _hits(docs, RETIRED)
    assert not hits, (
        "the retired bench.py / perf_diff harness or one of its "
        "*_FULL.json captures is cited; name the test that holds the "
        "property, or the PERF_LEDGER.jsonl line that measured it:\n  "
        + "\n  ".join(hits))
