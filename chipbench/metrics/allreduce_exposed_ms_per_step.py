"""Time a step in which a collective operation ran on a device and no other
operation did (trace), averaged over the devices."""
from chipbench import trace_reduce as tr

COLLECTIVES = ("all-reduce", "all_reduce", "reduce-scatter",
               "reduce_scatter", "all-gather", "all_gather",
               "collective-permute", "all-to-all")


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    steps = sum(1 for s, d, n in t["reduced"]["host"]
                if n == "executor_run" and lo <= s and s + d <= hi)
    exposed = []
    for events in t["reduced"]["devices"].values():
        coll = [e for e in events if e[2].startswith(COLLECTIVES)]
        rest = [e for e in events if not e[2].startswith(COLLECTIVES)]
        if not coll:
            continue
        compute = tr.busy(rest, lo, hi)
        exposed.append(sum((e - s) - tr.overlap(compute, s, e)
                           for s, e in tr.busy(coll, lo, hi)))
    if not exposed or not steps:
        return None
    return sum(exposed) / len(exposed) / steps * 1e-6
