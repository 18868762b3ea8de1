"""``flash_roofline`` under a causal mask: the algorithm needs half the
products of ``flops.flash_pass`` (keys after the query contribute nothing),
the tensors are read and written whole.  Otherwise as ``flash_roofline.py``:
least time over the measured device time of the ``hetu_flash_*`` events,
forward and backward together."""
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
        ops, nbytes = flops.flash_pass(name, want["flash_rows"],
                                       ctx["program"].seq, want["head_dim"])
        t_min, limits[name] = flops.roofline_seconds(ops / 2.0, nbytes,
                                                     ctx["peaks"])
        least += t_min * max(len(found[k]) for k in p["kernels"])
        measured += sum(sum(found[k]) for k in p["kernels"]) * 1e-9
    ctx["say"](f"roofline of causal flash attention: least {least:.4f} s "
               f"over measured {measured:.4f} s; bound by {limits}")
    return 100.0 * least / measured
