"""``flash_roofline`` under the block-diffusion mask: the pairs the mask shows
a head, ``L^2 + K L`` of the ``4 L^2`` of a pass over ``2 L`` positions
(``flops_sdar.visible_pairs``), two products forward and five backward, each
pass REQUIRED once a layer application and step: the configuration's passes
(``flops.flash_passes_a_step``: the builder's ``attention_passes``) x the
traced steps x the devices a pass's events ran on (``_lib.passes_due``), never
a count of forward events.  The measured time is that of every event that
holds the pass's name (``hetu_flash_fwd_bd``, ``hetu_flash_bwd_bd``): a tile
walked that holds no visible pair, or a forward kernel run again in the
backward pass, lowers it and earns nothing."""
from chipbench import flops, flops_sdar as fl, trace_reduce as tr
from chipbench.metrics._lib import passes_due


def read(ctx):
    t = ctx["trace"]
    if t is None:
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    by_device = {name: tr.events_holding(t["reduced"], lo, hi, p["events"])
                 for name, p in flops.FLASH_PASSES.items()}
    found = {name: [(d, key) for ev in by.values() for _, d, key in ev]
             for name, by in by_device.items()}
    if not all(found.values()):
        return None
    p = ctx["program"]
    want = p.expected_kernel_shapes()
    a_step = flops.flash_passes_a_step(want)
    required = passes_due(ctx, a_step, by_device)
    least = measured = 0.0
    limits = {}
    for name, events in found.items():
        t_min, limits[name] = flops.roofline_seconds(
            *fl.flash_pass(name, want["flash_rows"], p.seq // 2,
                           want["block_length"], want["head_dim"]),
            ctx["peaks"])
        least += t_min * required
        measured += sum(d for d, _ in events) * 1e-9
    named = {name: sum(f"{p['events']}_bd" in key for _, key in found[name])
             for name, p in flops.FLASH_PASSES.items()}
    ctx["say"](f"roofline of flash attention under the block-diffusion mask "
               f"(blocks of {want['block_length']}, "
               f"{fl.visible_pairs(p.seq // 2, want['block_length'])} pairs a "
               f"head of {p.seq ** 2}): {required} passes required ({a_step} "
               f"a step), events a pass "
               f"{ {k: len(v) for k, v in found.items()} }, of them named "
               f"*_bd {named}; least {least:.4f} s over measured "
               f"{measured:.4f} s; bound by {limits}")
    return 100.0 * least / measured
