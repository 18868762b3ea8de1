"""Flash attention's least possible time over the measured device time of
its kernels (trace events named ``hetu_flash_*``), forward and backward
pass together.  Operations and bytes are the algorithm's, by pass, from the
local shard's shapes (``chipbench/flops.py``): the backward pass is held
to five matrix products over the summed time of both backward kernels."""
from chipbench import flops
from chipbench.metrics._lib import kernel_events


def read(ctx):
    passes = flops.FLASH_PASSES
    found = kernel_events(ctx, [k for p in passes.values()
                                for k in p["kernels"]])
    if not found or not all(found.values()):
        return None
    want = ctx["program"].expected_kernel_shapes()
    least = measured = 0.0
    limits = {}
    for name, p in passes.items():
        t_min, limits[name] = flops.roofline_seconds(
            *flops.flash_pass(name, want["flash_rows"], ctx["program"].seq,
                              want["head_dim"]), ctx["peaks"])
        least += t_min * max(len(found[k]) for k in p["kernels"])
        measured += sum(sum(found[k]) for k in p["kernels"]) * 1e-9
    ctx["say"](f"roofline of flash attention: least {least:.4f} s over "
               f"measured {measured:.4f} s; bound by {limits}")
    return 100.0 * least / measured
