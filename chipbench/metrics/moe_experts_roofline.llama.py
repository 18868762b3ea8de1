"""The nine grouped expert products of a train step (forward gate, up,
down; their ``dx``; their ``dw``): least possible time over their measured
device time.  Each is ``2 x pairs x hidden x width`` operations over the
rows' two activations and the experts' weights, each moved once
(``flops_moe.gmm_call``); rows of padding and recomputation earn nothing.
Events: the program's ``hetu_moe_gmm_*`` kernels, or XLA's own ``ragged-dot``
custom calls where the program took ``jax.lax.ragged_dot``."""
from chipbench import flops, flops_moe
from chipbench.metrics._lib import kernel_events


def read(ctx):
    if ctx["trace"] is None:
        return None
    found = kernel_events(ctx, flops_moe.GMM_KERNELS)
    if not any(found.values()):
        found = kernel_events(ctx, ("ragged-dot",))
        if not any(found.values()):
            return None
    c = ctx["config"]
    want = ctx["program"].expected_kernel_shapes()
    t_min, limit = flops.roofline_seconds(
        *flops_moe.gmm_call(want["moe_pairs"], c["num_experts"],
                            c["hidden_size"], c["intermediate_size"]),
        ctx["peaks"])
    s = ctx["trace"]["summary"]
    steps = sum(1 for t0, d, n in ctx["trace"]["reduced"]["host"]
                if n == "executor_run" and s["lo"] <= t0
                and t0 + d <= s["hi"])
    measured = sum(sum(v) for v in found.values()) * 1e-9
    ctx["say"](f"roofline of the grouped expert products: nine a step over "
               f"{steps} steps, least {9 * steps * t_min:.4f} s, over the "
               f"measured {measured:.4f} s of the events "
               f"{ {k: len(v) for k, v in found.items() if v} }; bound by "
               f"{limit}")
    return 100.0 * 9 * steps * t_min / measured
