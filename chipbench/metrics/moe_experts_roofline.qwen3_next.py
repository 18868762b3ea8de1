"""``moe_experts_roofline`` where the chip holds a share of the experts: the
nine grouped products of each layer (forward gate, up, down; their ``dx``;
their ``dw``) over the pairs the counters say were computed here
(``hetu_moe_pairs_routed_total`` less ``_dropped_total``, a layer and step:
about 1/16 of ``T k``), against the held experts' weights
(``flops_qwen3next.held_gmm_call``); least possible time over the measured
time of the ``hetu_moe_gmm_*`` events (or ``ragged-dot``).  The base reader
credits ``T k`` pairs and nine products a step and would read far over
100% here."""
from chipbench import flops, flops_moe, flops_qwen3next as fq
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
    c, rec = ctx["config"], ctx["rec"]
    # the program counts once a step it trains: the warm steps and the window
    counted = int(ctx["mix"]["warm_steps"]) + len(rec["step_ends"])
    pairs = {layer: (routed[layer] - dropped.get(layer, 0.0)) / counted
             for layer in routed}
    least = 0.0
    limits = set()
    for n in pairs.values():
        t_min, limit = flops.roofline_seconds(
            *fq.held_gmm_call(n, c["num_experts"], c["hidden_size"],
                              c["moe_intermediate_size"]), ctx["peaks"])
        least += 9 * t_min
        limits.add(limit)
    s = ctx["trace"]["summary"]
    steps = sum(1 for t0, d, n in ctx["trace"]["reduced"]["host"]
                if n == "executor_run" and s["lo"] <= t0
                and t0 + d <= s["hi"])
    measured = sum(sum(v) for v in found.values()) * 1e-9
    ctx["say"](f"roofline of the held experts' grouped products: nine a "
               f"layer in {len(pairs)} layers over {steps} steps, pairs a "
               f"layer and step { {k: round(v) for k, v in pairs.items()} } "
               f"(counters over {counted} counted steps); least "
               f"{steps * least:.4f} s over the measured {measured:.4f} s of "
               f"{ {k: len(v) for k, v in found.items() if v} }; bound by "
               f"{sorted(limits)}")
    return 100.0 * steps * least / measured
