"""``flash_roofline`` of the layers that attend over ALL earlier keys (the
full layer and the cross layer; the window layer's kernels go by
``hetu_swa_*`` and have ``window_attn_roofline``): ``_differential.share`` over
the ``hetu_flash_*`` events, each pass required once a layer and step (the
builder's ``attention_passes``), causal."""
from chipbench import flops
from chipbench.metrics._differential import share


def read(ctx):
    if ctx["trace"] is None:
        return None
    want = ctx["program"].expected_kernel_shapes()
    return share(ctx, {n: p["events"] for n, p in flops.FLASH_PASSES.items()},
                 flops.flash_passes_a_step(want), None,
                 "over all earlier keys")
