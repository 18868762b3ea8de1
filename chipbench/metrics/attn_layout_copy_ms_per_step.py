"""Device time a step that goes into moving attention's heads about: XLA's
own ``copy`` and ``transpose`` operations whose one result is a 4-D array
of the compute type with the dimensions (local batch, local heads,
positions, head size) in any order, summed per device over the traced
window, averaged over the devices, divided by the traced steps.  These are
the transposes between the projections' ``[B, S, H*D]`` and the
``[B, H, S, D]`` the flash kernels walk.  An operation is known by its key
(``trace_reduce.op_key``: the HLO instruction's name and its result), so
the Pallas events jax names ``transpose_jvp_hetu_...`` are not taken, nor is
a transposition the compiler folded into a ``fusion`` or into a matrix
product's operand layout (that is no copy), nor an asynchronous
``copy-start`` / ``copy-done`` pair.  With flash events in the window and no
such copy it is 0.0, a reading; without flash events there is nothing to
read."""
import re

from chipbench import flops, trace_reduce as tr
from chipbench.loops import STEP_SPANS

MOVE = re.compile(r"^(?:copy|transpose)_([a-z]+\d+)((?:_\d+){4})$")


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    fwd = tr.events_holding(t["reduced"], lo, hi,
                            flops.FLASH_PASSES["forward"]["events"])
    steps = tr.count_spans(t["reduced"]["host"], STEP_SPANS)
    if not any(fwd.values()) or not steps:
        return None
    want = ctx["program"].expected_kernel_shapes()
    dtype = tr.HLO_DTYPES[want["compute_dtype"]]
    dims = sorted(want["flash_dims"])
    taken = {}
    for events in tr.events_holding(t["reduced"], lo, hi, "").values():
        for _, d, key in events:
            m = MOVE.match(key)
            if (m and m.group(1) == dtype and dims == sorted(
                    int(x) for x in m.group(2).split("_")[1:])):
                n, ns = taken.get(key, (0, 0.0))
                taken[key] = (n + 1, ns + d)
    per = 1.0 / (len(fwd) * steps)
    ctx["say"](f"layout copies of {dtype} {want['flash_dims']} in any order,"
               f" a step and device ({steps} steps, {len(fwd)} device(s)): "
               + ("; ".join(f"{k} x {n * per:g} = {ns * per * 1e-6:.3f} ms"
                            for k, (n, ns) in sorted(taken.items()))
                  or "none"))
    return sum(ns for _, ns in taken.values()) * per * 1e-6
