"""``mfu`` for the sparse-expert decoder: operations a token from
``flops_moe.olmoe_train_flops_per_token`` (3 x 357.4 M at depth 1 and 4,096
positions: the experts a token takes, not all 64), times the tokens per
second of the steps before the profiler was switched on, over chips times
the bf16 peak."""
from chipbench import flops_moe


def read(ctx):
    rec, p = ctx["rec"], ctx["program"]
    if ctx["peaks"] is None:
        return None
    cut = rec.get("trace_started_at")
    ends = [e for e in rec["step_ends"] if cut is None or e < cut]
    if len(ends) < 2:
        ends = rec["step_ends"]
    rate = rec["tokens_per_step"] * len(ends) / (ends[-1] - rec["t0"])
    parts = flops_moe.olmoe_forward_flops_per_token(ctx["config"], p.seq)
    total = sum(parts.values())
    ctx["say"]("mfu: forward operations a token "
               + ", ".join(f"{k} {v / 1e6:.1f} M ({100 * v / total:.0f}%)"
                           for k, v in parts.items())
               + f"; x3 for the step = {3 * total / 1e6:.1f} M")
    return 100.0 * 3.0 * total * rate / (
        ctx["cell"]["chips"] * ctx["peaks"]["bf16_flops_per_s"])
