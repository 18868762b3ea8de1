"""``flash_roofline`` where keys are wider than values and whole layers are
recomputed: causal (half the products), the forward pass ``2 S^2 (d_qk +
d_v)`` a head and the backward pass its five products over their own widths
(``flops_ling3.flash_pass``), each REQUIRED once a layer and step; the
measured time is that of every flash event, the recomputed forward pass
included, which earns nothing."""
from chipbench import flops, flops_ling3 as fl, trace_reduce as tr


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    found = {name: [d for ev in tr.events_holding(
                        t["reduced"], lo, hi, p["events"]).values()
                    for _, d, _ in ev]
             for name, p in flops.FLASH_PASSES.items()}
    if not all(found.values()):
        return None
    p = ctx["program"]
    want = p.expected_kernel_shapes()
    required = len(found["forward"]) / getattr(p, "forward_passes", 1)
    least = measured = 0.0
    limits = {}
    for name, durs in found.items():
        ops, nbytes = fl.flash_pass(name, want["flash_rows"], p.seq,
                                    want.get("score_dim", want["head_dim"]),
                                    want["head_dim"])
        t_min, limits[name] = flops.roofline_seconds(ops / 2.0, nbytes,
                                                     ctx["peaks"])
        least += t_min * required
        measured += sum(durs) * 1e-9
    ctx["say"](f"roofline of causal flash attention, scores "
               f"{want.get('score_dim')} and values {want['head_dim']} wide: "
               f"{required:g} passes required, events a pass "
               f"{ {k: len(v) for k, v in found.items()} }; least "
               f"{least:.4f} s over measured {measured:.4f} s; bound by "
               f"{limits}")
    return 100.0 * least / measured
