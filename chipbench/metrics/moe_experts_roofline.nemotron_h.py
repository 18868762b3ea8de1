"""``moe_experts_roofline`` where the chip holds a share of experts that are
not gated: the SIX grouped products of each expert block (forward up, down;
their ``dx``; their ``dw``) over the pairs the counters say were computed
here (``hetu_moe_pairs_routed_total`` less ``_dropped_total``, a block and
step: about 1/16 of ``T k``), against the 8 held experts' weights
(``flops_nemotronh.held_gmm_call``); least possible time over the measured
time of the ``hetu_moe_gmm_*`` events (or ``ragged-dot``).  The base reader
credits ``T k`` pairs and nine products a step."""
from chipbench import flops, flops_moe, flops_nemotronh as fn
from chipbench.metrics._lib import kernel_events
from chipbench.metrics._moe import sample

PRODUCTS = 6


def read(ctx):
    if ctx["trace"] is None:
        return None
    routed = sample(ctx, "hetu_moe_pairs_routed_total")
    dropped = sample(ctx, "hetu_moe_pairs_dropped_total")
    found = kernel_events(ctx, flops_moe.GMM_KERNELS)
    if not any(found.values()):
        found = kernel_events(ctx, ("ragged-dot",))
    if not routed or dropped is None or not any(found.values()):
        return None
    c, rec = ctx["config"], ctx["rec"]
    # the program counts once a step it trains: the warm steps and the window
    counted = int(ctx["mix"]["warm_steps"]) + len(rec["step_ends"])
    pairs = {block: (routed[block] - dropped.get(block, 0.0)) / counted
             for block in routed}
    least = 0.0
    limits = set()
    for n in pairs.values():
        t_min, limit = flops.roofline_seconds(
            *fn.held_gmm_call(n, c["n_routed_experts"], c["hidden_size"],
                              c["moe_intermediate_size"]), ctx["peaks"])
        least += PRODUCTS * t_min
        limits.add(limit)
    s = ctx["trace"]["summary"]
    steps = sum(1 for t0, d, n in ctx["trace"]["reduced"]["host"]
                if n == "executor_run" and s["lo"] <= t0
                and t0 + d <= s["hi"])
    measured = sum(sum(v) for v in found.values()) * 1e-9
    ctx["say"](f"roofline of the held relu2 experts' grouped products: "
               f"{PRODUCTS} a block in {len(pairs)} blocks over {steps} "
               f"steps, pairs a block and step "
               f"{ {k: round(v) for k, v in pairs.items()} } (counters over "
               f"{counted} counted steps); least {steps * least:.4f} s over "
               f"the measured {measured:.4f} s of "
               f"{ {k: len(v) for k, v in found.items() if v} }; bound by "
               f"{sorted(limits)}")
    return 100.0 * steps * least / measured
