"""``window_attn_roofline`` of the differential layer under a window:
``_differential.share`` over the ``hetu_swa_*`` events at the band's pairs
(``flops_laguna.window_pass`` knows one head size; a pair has two)."""
from chipbench import flops_phi4flash as fl
from chipbench.metrics._differential import share


def read(ctx):
    if ctx["trace"] is None:
        return None
    want = ctx["program"].expected_kernel_shapes()
    return share(ctx, fl.WINDOW_EVENTS, want["window_layers"],
                 want["window"], f"over a window of {want['window']}")
