"""chipbench: the repository's benchmark on the chip.

One command runs one cell of ``BENCHMARK.json`` once::

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that decides a number lives here, where later PRs may add files
but change none: traffic generation (``traffic.py``), the loops that offer
it (``loops.py``), the reduction from a profiler trace to intervals
(``trace_reduce.py``), the table of peaks (``peaks.py``), operations and
bytes from shapes (``flops.py``), a plain reference of every configuration
(``reference/``) and the readers of the per-layer metrics (``metrics/``).
See ``README.md``.
"""
