"""``mfu`` for the SDAR share: MODEL operations a DATA token over what THIS
chip computes, from ``flops_sdar.forward_flops_per_token`` (six layers; a
token is two positions through the layers: the attention projections, the
block-diffusion mask's pairs, the router, the experts a position's pairs on
held experts take, read from the counters: a pair on an expert held elsewhere
earns nothing; the untied head over the slice once), times three for the step,
times the tokens per second of the steps before the profiler was switched on,
over chips times the bf16 peak."""
from chipbench import flops_sdar as fl
from chipbench.metrics._moe import sample


def read(ctx):
    rec, p, c = ctx["rec"], ctx["program"], ctx["config"]
    if ctx["peaks"] is None:
        return None
    cut = rec.get("trace_started_at")
    ends = [e for e in rec["step_ends"] if cut is None or e < cut]
    if len(ends) < 2:
        ends = rec["step_ends"]
    rate = rec["tokens_per_step"] * len(ends) / (ends[-1] - rec["t0"])
    here = sample(ctx, "hetu_moe_pairs_routed_total")
    elsewhere = sample(ctx, "hetu_moe_pairs_elsewhere_total")
    k = c["num_experts_per_tok"]
    held = k * c["num_experts"] / float(c["deployment"]["num_experts"])
    if here and elsewhere:
        held = k * sum(here.values()) / (sum(here.values())
                                         + sum(elsewhere.values()))
    parts = fl.forward_flops_per_token(c, p.seq // 2, held)
    total = sum(parts.values())
    ctx["say"](f"mfu: {held:.3f} pairs a position on held experts; forward "
               "operations a data token "
               + ", ".join(f"{part} {v / 1e6:.1f} M ({100 * v / total:.0f}%)"
                           for part, v in parts.items())
               + f"; x3 for the step = {3 * total / 1e6:.1f} M")
    return 100.0 * 3.0 * total * rate / (
        ctx["cell"]["chips"] * ctx["peaks"]["bf16_flops_per_s"])
