"""``mfu`` for the Mellum cell: MODEL operations a token from
``flops_mellum.forward_flops_per_token`` (the whole layers: every routed pair
is computed on one of the chips; a full layer's attention at its causal pairs,
a window layer's at its BAND; the whole vocabulary), times three for the step
(nothing recomputed is credited), times the tokens per second of ALL the chips
in the steps before the profiler was switched on, over the cell's chips times
one chip's bf16 peak: tokens of four chips over the peak of four."""
from chipbench import flops_mellum as fl


def read(ctx):
    rec, p, c = ctx["rec"], ctx["program"], ctx["config"]
    if ctx["peaks"] is None:
        return None
    cut = rec.get("trace_started_at")
    ends = [e for e in rec["step_ends"] if cut is None or e < cut]
    if len(ends) < 2:
        ends = rec["step_ends"]
    rate = rec["tokens_per_step"] * len(ends) / (ends[-1] - rec["t0"])
    parts = fl.forward_flops_per_token(c, p.seq)
    total = sum(parts.values())
    ctx["say"]("mfu: forward operations a token "
               + ", ".join(f"{part} {v / 1e6:.1f} M ({100 * v / total:.0f}%)"
                           for part, v in parts.items())
               + f"; x3 for the step = {3 * total / 1e6:.1f} M; "
               f"{rate:.0f} tokens/s over {ctx['cell']['chips']} chips")
    return 100.0 * 3.0 * total * rate / (
        ctx["cell"]["chips"] * ctx["peaks"]["bf16_flops_per_s"])
