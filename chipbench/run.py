"""Run one cell of ``BENCHMARK.json`` once, in this process, on the chips of
this machine, and print the contract's one JSON line last.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Nothing here knows a cell, a configuration or a traffic mix by name: the
cell names its configuration and traffic, the configuration file names its
builder, the traffic file its kind, and every metric is a reader file under
``chipbench/metrics/`` found by the metric's quantity and the builder's name.
Lines before the last are for people: each starts with ``chipbench:``.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REHEARSAL_TAG = "[REHEARSAL cpu toy-size] "
IMPORTED_AT = time.perf_counter()
#: Host memory the TPU client pins for transfers when it starts.  libtpu's
#: own 4 GiB took 7.3-12.2 s of ``jax.devices()`` on the chip machine, in two
#: modes 3.7 s apart (12% of a 30 s set-up: the check of PR 54 read medians
#: of 27.88 and 32.31 s from one tree), 1 GiB 3.5-3.9 s and 256 MiB 1.9-2.1 s
#: (calls 54.7, 54.8).  No cell's step moves more than 2.6 MB to the chip;
#: a transfer larger than the buffer still goes, unpinned (256 MiB up in
#: 1.44 s where 0.43).  A traffic file that needs more says
#: ``premapped_buffer_bytes`` (Ouro's: its comparison reads 6.4 GB of logits
#: back); a value the machine sets is left alone.
PREMAPPED_BUFFER_BYTES = 256 << 20


def process_age():
    """Seconds since this process started, from the kernel's record; the
    imports before this module's first line are part of set-up."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - IMPORTED_AT


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_of(bench, name):
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json "
                     f"(has {[w['name'] for w in bench['workloads']]})")


def load_cell(name):
    """``(bench, cell, config, mix)`` of the workload ``name``: the cell's
    entry, its configuration file and its traffic file."""
    from . import traffic
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = cell_of(bench, name)
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return (bench, cell, load_json(ROOT, entry["file"]),
            traffic.load(cell["traffic"]))


def metrics_of(bench, group, cell):
    """The metrics of ``group`` this cell reports: those with no
    ``workloads`` key and those that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def reader_path(name, builder=None):
    """The file that reads the metric ``name`` for a configuration whose
    ``builder`` is the family named, or None.  The quantity is the name
    before the first dot: what follows (``.dp4``) only tells entries of one
    quantity apart that move different end-to-end metrics, or that a test
    still pins one to a cell, and finds no file.
    ``metrics/<quantity>.<builder>.py`` where the family has arithmetic of
    its own, else ``metrics/<quantity>.py``; a quantity that has only
    families' files (``mfu``) has nothing for a family without one."""
    quantity = name.split(".")[0]
    for stem in ([f"{quantity}.{builder}"] if builder else []) + [quantity]:
        path = os.path.join(HERE, "metrics", stem + ".py")
        if os.path.exists(path):
            return path
    return None


def reader(name, family=None):
    """``read(ctx)`` of the metric ``name`` (``reader_path``).  The family is
    the one ``ctx["config"]`` names, looked up at the call; only a family's
    file that hands its ``read`` on to another family's arithmetic
    (``metrics/moe_experts_roofline.ling3.py``) names that ``family``
    instead.  Where no file reads the quantity for the family, ``read`` says
    so and returns None: the metric is left out of the line."""
    def read(ctx):
        whose = family or (ctx.get("config") or {}).get("builder")
        path = reader_path(name, whose)
        if path is None:
            ctx["say"](f"{name}: no reader of {name.split('.')[0]} for the "
                       f"family {whose!r} under chipbench/metrics/")
            return None
        spec = importlib.util.spec_from_file_location(
            "chipbench.metrics."
            + os.path.basename(path)[:-3].replace(".", "__"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx)
    return read


def merge(base, over):
    """``base`` with ``over``'s keys, nested dicts merged key by key."""
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if (isinstance(v, dict)
                                      and isinstance(out.get(k), dict)) else v
    return out


def memory_peak(devices):
    """Peak bytes on the fullest chip, from the allocator alone.  On a TPU
    ``peak_bytes_in_use`` is the high-water mark of the arrays the process
    holds; a compiled program's temporaries are not in it but in the region
    the allocator reserves for them (``peak_bytes_reserved``; 12.05 GB for
    the BERT step, where XLA's memory analysis says 12.48 GB of
    temporaries).  The two regions are disjoint, and their sum is the
    peak."""
    stats = [d.memory_stats() or {} for d in devices]
    return max(int(s.get("peak_bytes_in_use", 0))
               + int(s.get("peak_bytes_reserved", 0)) for s in stats)


PEAK_SOURCE = "memory_stats peak_bytes_in_use + peak_bytes_reserved"


def main(argv=None, rehearsal=False):
    ap = argparse.ArgumentParser(prog="python3 -m chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the reduced trace (and the raw .xplane.pb) "
                         "into this directory; for building test data")
    ns = ap.parse_args(argv)
    tag = REHEARSAL_TAG if rehearsal else ""

    def say(msg):
        print(f"{tag}chipbench: {msg}", flush=True)

    bench, cell, config, mix = load_cell(ns.workload)
    if rehearsal:
        config = merge(config, config["toy"])
        mix = merge(mix, mix["toy"])

    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(int(mix.get(
        "premapped_buffer_bytes", PREMAPPED_BUFFER_BYTES))))
    import jax
    devices = jax.devices()
    d0 = devices[0]
    say(f"platform {d0.platform}, device_kind {d0.device_kind}, "
        f"{len(devices)} device(s), jax {jax.__version__}; cell "
        f"{cell['name']} = {cell['config']} x {cell['traffic']} on "
        f"{cell['chips']} chip(s), seed {ns.seed}, {ns.seconds} s, "
        f"trace {ns.trace}")
    wanted = "cpu" if rehearsal else "tpu"
    if d0.platform != wanted or len(devices) < cell["chips"]:
        say(f"FAIL: this run needs {cell['chips']} device(s) of platform "
            f"{wanted!r}; jax found {len(devices)} of {d0.platform!r}. "
            "Nothing was run.")
        return 3

    from hetu_tpu import telemetry
    from hetu_tpu.platform import enable_compile_cache
    from . import loops, peaks as peaks_mod, trace_reduce
    say(f"compile cache: {enable_compile_cache()}; premapped host buffer "
        f"{os.environ['TPU_PREMAPPED_BUFFER_SIZE']} bytes")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    telemetry.enable()     # the registry counts retraces and kernel choices
    peaks = None if rehearsal else peaks_mod.peaks_for(d0.device_kind)

    builder = importlib.import_module(
        "chipbench.builders." + config["builder"])
    program = builder.build(config, mix, ns.seed, say)
    spans = loops.Spans()
    loop = loops.LOOPS[mix["kind"]](program, mix, ns.seed, spans, say)
    loop.prepare()

    trace_dir = os.path.join(ROOT, ".chipbench_trace")
    tracer = loops.Tracer()
    if ns.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracer = loops.Tracer(trace_dir, ns.seconds,
                              float(mix["trace_seconds"]))
    gc.collect()
    gc.freeze()            # nothing made so far is scanned in the window
    setup_s = process_age()
    say(f"set-up {setup_s:.2f} s; window opens")
    rec = loop.window(ns.seconds, tracer)
    gc.unfreeze()
    checks = loop.finish()

    peak = memory_peak(program.devices)
    say(f"memory peak {peak / 2**30:.2f} GiB on the fullest chip "
        f"({PEAK_SOURCE}); allocator says "
        f"{ {k: v for k, v in (program.devices[0].memory_stats() or {}).items() if 'bytes' in k} }")
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": int(cell["chips"]), "memory_peak_bytes": int(peak)}

    ctx = {"rec": rec, "spans": spans.rec, "config": config, "mix": mix,
           "cell": cell, "program": program, "peaks": peaks,
           "memory_peak_bytes": peak, "memory_peak_source": PEAK_SOURCE,
           "registry": telemetry.get_registry().snapshot(), "trace": None,
           "say": say}
    breakdown = None
    if ns.trace:
        t = time.perf_counter()
        reduced = trace_reduce.load(trace_reduce.newest_xplane(trace_dir),
                                    set(loops.SPAN_ORDER))
        summ = trace_reduce.summary(reduced, loops.STEP_SPANS,
                                    loops.SPAN_ORDER)
        ctx["trace"] = {"reduced": reduced, "summary": summ}
        device["busy_s"] = summ["busy_s"]
        device["window_s"] = summ["window_s"]
        breakdown = {"device_ops": [list(x) for x in summ["device_ops"]],
                     "idle_gaps": [list(x) for x in summ["idle_gaps"]]}
        say(f"trace: profiler start {tracer.start_cost:.2f} s, stop "
            f"{tracer.stop_cost:.2f} s, reduction "
            f"{time.perf_counter() - t:.2f} s; window {summ['window_s']:.3f}"
            f" s, busy per device {summ['busy_s_per_device']}")
        if not rehearsal:      # the cpu platform has no device plane
            checks.append((summ["busy_s"] > 0, "operations ran on the "
                           "device in the traced window"))
            if hasattr(loop, "trace_checks"):
                checks.extend(loop.trace_checks(reduced))
        if ns.keep_trace:
            os.makedirs(ns.keep_trace, exist_ok=True)
            trace_reduce.save(reduced, os.path.join(
                ns.keep_trace, f"{cell['name']}.reduced.json.gz"))
            shutil.copy(trace_reduce.newest_xplane(trace_dir),
                        os.path.join(ns.keep_trace,
                                     f"{cell['name']}.xplane.pb"))
        shutil.rmtree(trace_dir, ignore_errors=True)

    metrics = {}
    group = "per_layer" if ns.trace else "end_to_end"
    for m in metrics_of(bench, group, cell["name"]):
        value = (setup_s if m["name"] == "setup_s"
                 else reader(m["name"])(ctx))
        if value is None:
            say(f"metric {m['name']}: nothing to read, left out")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        say(f"metric {m['name']} = {float(value):.6g} {m['unit']}")

    for ok, what in checks:
        say(("  ok    " if ok else "  WRONG ") + what)
    correct = all(ok for ok, _ in checks)
    program.close()
    telemetry.shutdown()
    if rehearsal:
        say(f"rehearsal complete (correct={correct}); not a chip result")
        return 0 if correct else 1
    line = {"correct": correct, "attempted": int(rec["attempted"]),
            "failed": int(rec["failed"]), "metrics": metrics,
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
