"""Operations the forward and backward passes require per token, times the
tokens per second of the steps before the profiler was switched on, over
chips times the bf16 peak."""
from chipbench import flops


def read(ctx):
    rec, p = ctx["rec"], ctx["program"]
    if ctx["peaks"] is None:
        return None
    cut = rec.get("trace_started_at")
    ends = [e for e in rec["step_ends"] if cut is None or e < cut]
    if len(ends) < 2:
        ends = rec["step_ends"]
    rate = rec["tokens_per_step"] * len(ends) / (ends[-1] - rec["t0"])
    per_token = flops.bert_train_flops_per_token(
        ctx["config"], p.seq, ctx["mix"]["mask_fraction"])
    return 100.0 * per_token * rate / (
        ctx["cell"]["chips"] * ctx["peaks"]["bf16_flops_per_s"])
