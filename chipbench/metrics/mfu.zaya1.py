"""``mfu`` for the ZAYA1 share: MODEL operations a token over what THIS chip
computes, from ``flops_zaya1.forward_flops_per_token`` (five layers: the five
latent products, causal attention at half the keys and 8 heads of 128, the
head-mixing taps, the router's MLP, the experts a token's pairs on held experts
take, read from the counters: a pair that chose no expert or an expert held
elsewhere earns nothing; the tied head over the slice), times three for the
step, times the tokens per second of the steps before the profiler was
switched on, over chips times the bf16 peak."""
from chipbench import flops_zaya1 as fl
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
    skipped = sample(ctx, "hetu_moe_pairs_skipped_total")
    k = c["num_experts_per_tok"]
    held = k * c["num_experts"] / (c["deployment"]["num_experts"] + 1.0)
    if here and elsewhere and skipped:
        held = k * sum(here.values()) / (
            sum(here.values()) + sum(elsewhere.values())
            + sum(skipped.values()))
    parts = fl.forward_flops_per_token(c, p.seq, held)
    total = sum(parts.values())
    ctx["say"](f"mfu: {held:.3f} pairs a token on held experts; forward "
               "operations a token "
               + ", ".join(f"{part} {v / 1e6:.1f} M ({100 * v / total:.0f}%)"
                           for part, v in parts.items())
               + f"; x3 for the step = {3 * total / 1e6:.1f} M")
    return 100.0 * 3.0 * total * rate / (
        ctx["cell"]["chips"] * ctx["peaks"]["bf16_flops_per_s"])
