"""``mfu`` for the Ouro stage: MODEL operations a token from
``flops_ouro.forward_flops_per_token`` (``P x k`` layer applications with
causal attention at half the keys, the untied head and the exit gate ``P``
times), times three for the step (nothing recomputed is credited, so the
recomputed layers and head passes read as lost ``mfu``), times the tokens per
second of the steps before the profiler was switched on, over chips times the
bf16 peak."""
from chipbench import flops_ouro as fo


def read(ctx):
    rec, p = ctx["rec"], ctx["program"]
    if ctx["peaks"] is None:
        return None
    cut = rec.get("trace_started_at")
    ends = [e for e in rec["step_ends"] if cut is None or e < cut]
    if len(ends) < 2:
        ends = rec["step_ends"]
    rate = rec["tokens_per_step"] * len(ends) / (ends[-1] - rec["t0"])
    parts = fo.forward_flops_per_token(ctx["config"], p.seq)
    total = sum(parts.values())
    ctx["say"]("mfu: forward operations a token "
               + ", ".join(f"{k} {v / 1e6:.1f} M ({100 * v / total:.1f}%)"
                           for k, v in parts.items())
               + f"; x3 for the step = {3 * total / 1e6:.1f} M")
    return 100.0 * 3.0 * total * rate / (
        ctx["cell"]["chips"] * ctx["peaks"]["bf16_flops_per_s"])
