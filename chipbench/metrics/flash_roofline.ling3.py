"""``flash_roofline`` where keys are wider than values and whole layers are
recomputed: causal (half the products), the forward pass ``2 S^2 (d_qk +
d_v)`` a head and the backward pass its five products over their own widths
(``flops_ling3.flash_pass``), each REQUIRED once a layer application and
step.  The required passes are the configuration's
(``flops.flash_passes_a_step``: the builder's ``attention_passes``) x the
traced steps x the devices a pass's events ran on (``_lib.passes_due``),
never a count of forward events: how often the
program runs the forward kernel to get a pass done, once or again inside the
backward pass, is the program's.  The measured time is that of every flash
event, a recomputed forward pass included, which earns nothing."""
from chipbench import flops, flops_ling3 as fl, trace_reduce as tr
from chipbench.metrics._lib import passes_due


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    by_device = {name: tr.events_holding(t["reduced"], lo, hi, p["events"])
                 for name, p in flops.FLASH_PASSES.items()}
    found = {name: [d for ev in by.values() for _, d, _ in ev]
             for name, by in by_device.items()}
    if not all(found.values()):
        return None
    p = ctx["program"]
    want = p.expected_kernel_shapes()
    a_step = flops.flash_passes_a_step(want)
    required = passes_due(ctx, a_step, by_device)
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
               f"{required} passes required ({a_step} a step), events a "
               f"pass { {k: len(v) for k, v in found.items()} }; least "
               f"{least:.4f} s over measured {measured:.4f} s; bound by "
               f"{limits}")
    return 100.0 * least / measured
