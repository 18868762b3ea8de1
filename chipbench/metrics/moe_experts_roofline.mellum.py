"""``moe_experts_roofline`` where the experts are spread over the chips: the
nine grouped products REQUIRED of each expert layer (forward gate, up, down;
their ``dx``; their ``dw``) on each chip over the pairs that chip computed,
against its own experts' weights (``flops_qwen3next.held_gmm_call``); least
possible time, summed over the chips, over the measured time of the
``hetu_moe_gmm_*`` events of all chips (or ``ragged-dot``): like by like.  The
counters are the host's (``hetu_moe_pairs_routed_total`` less
``_dropped_total``, a layer and step, over all chips' tokens); a chip's pairs
are taken as their mean over the chips, which is exact where the products are
bound by compute (least time is then linear in the pairs).  A recomputed
forward's products earn nothing."""
from chipbench import flops, flops_moe, flops_mellum as fl
from chipbench.metrics._lib import kernel_events
from chipbench.metrics._moe import sample


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
    c, rec, ranks = ctx["config"], ctx["rec"], ctx["program"].ranks
    # the program counts once a step it trains: the warm steps and the window
    counted = int(ctx["mix"]["warm_steps"]) + len(rec["step_ends"])
    pairs = {layer: (routed[layer] - dropped.get(layer, 0.0)) / counted
             for layer in routed}
    least = 0.0
    limits = set()
    for n in pairs.values():
        t_min, limit = flops.roofline_seconds(
            *fl.held_gmm_call(n / ranks, c["num_experts"] // ranks,
                              c["hidden_size"], c["moe_intermediate_size"]),
            ctx["peaks"])
        least += ranks * 9 * t_min
        limits.add(limit)
    s = ctx["trace"]["summary"]
    steps = sum(1 for t0, d, n in ctx["trace"]["reduced"]["host"]
                if n == "executor_run" and s["lo"] <= t0
                and t0 + d <= s["hi"])
    measured = sum(sum(v) for v in found.values()) * 1e-9
    ctx["say"](f"roofline of the grouped products on {ranks} chips: nine a "
               f"layer and chip in {len(pairs)} layers over {steps} steps, "
               f"the host's pairs a layer and step "
               f"{ {k: round(v) for k, v in pairs.items()} } (counters over "
               f"{counted} counted steps), a chip's their mean; least "
               f"{steps * least:.4f} s over the measured {measured:.4f} s of "
               f"{ {k: len(v) for k, v in found.items() if v} } on all "
               f"chips; bound by {sorted(limits)}")
    return 100.0 * steps * least / measured
