"""Operations and bytes of the Mellum decoder's step, and the time of its
experts' exchange, from shapes and the device trace (``flops.py``'s rules: the
algorithm's requirements, a product of ``[m, k] @ [k, n]`` is ``2 m k n``
operations; nothing recomputed, no masked pair and no row of padding is
credited).  Window attention's operations and bytes are
``flops_laguna.window_pass``'s, the grouped products' ``held_gmm_call``'s."""

from __future__ import annotations

import bisect

from chipbench import trace_reduce as tr
from chipbench.flops_laguna import layers_of, window_pairs
from chipbench.flops_qwen3next import held_gmm_call  # noqa: F401

#: the scope the program puts around each collective of an expert layer
EXCHANGE = "hetu_moe_exchange"


def forward_flops_per_token(c, seq):
    """Forward pass, per token, by part: the WHOLE layers (every pair a token
    routes is computed on one of the chips) and the whole vocabulary."""
    h, d, kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    heads, layers = c["num_attention_heads"], c["num_hidden_layers"]
    full, win = (len(layers_of(c, k)) for k in ("full_attention",
                                                "sliding_attention"))
    return {
        "projections": layers * 2.0 * h * (2 * heads * d + 2 * kv * d),
        "full_attention": full * 4.0 * heads * d
        * window_pairs(seq, seq) / seq,
        "window_attention": win * 4.0 * heads * d
        * window_pairs(seq, c["sliding_window"]) / seq,
        "router": layers * 2.0 * h * c["num_experts"],
        "experts": layers * c["num_experts_per_tok"] * 6.0 * h
        * c["moe_intermediate_size"],
        "head": 2.0 * h * c["vocab_size"]}


def exchange_call(tokens_a_chip, hidden, k, ranks, itemsize=2):
    """``{"gather": bytes, "scatter": bytes}`` ONE chip receives in the
    forward pass's all-gather of every chip's tokens (each with ``k`` int32
    choices and f32 weights) and in its reduce-scatter of the partial sums
    over ``ranks`` chips.  A training step runs the pair and its transpose (a
    gather of the sums' cotangent, a scatter of the tokens'), and a layer
    that is recomputed gathers its tokens once more: three all-gathers and
    two reduce-scatters a layer (``hetu_tpu/ops/moe.py
    exchange_bytes_a_step``)."""
    rows = (ranks - 1) * tokens_a_chip
    return {"gather": rows * (hidden * itemsize + 8 * k),
            "scatter": rows * hidden * itemsize}


def exchange_ms(ctx):
    """``{"total": ms, "exposed": ms}`` a step and chip of the device
    operations under ``hetu_moe_exchange`` (the expert layers' collectives and
    what XLA hung on them), all layers, forward and backward: their time, and
    the part of it in which nothing else ran on that chip (what no
    computation hides).  What ``moe_exchange_device_ms_per_step`` and its
    exposed part would report (PERF.md section 7).  None where there is no
    trace, no compiled step or no such scope in it (a parent's program)."""
    from chipbench.metrics._scopes import entry_scopes, step_hlo, step_program
    t = ctx["trace"]
    if t is None:
        return None
    hlo = step_hlo(ctx)
    if hlo is None:
        return None
    by_key = entry_scopes(hlo, (EXCHANGE,))
    if not any(s for sc in by_key.values() for s in sc):
        return None
    lo, hi = t["summary"]["lo"], t["summary"]["hi"]
    modules = t["reduced"]["modules"]
    program = step_program(modules, lo, hi)
    total = exposed = 0.0
    steps = 0
    for dev, events in t["reduced"]["devices"].items():
        starts = [e[0] for e in events]
        for r_lo, r_hi in ((s, s + d) for s, d, n in modules.get(dev, ())
                           if n == program and lo <= s and s + d <= hi):
            steps += 1
            inside = events[bisect.bisect_left(starts, r_lo):
                            bisect.bisect_left(starts, r_hi)]
            seen, mine, rest = {}, [], []
            for e in inside:
                scopes = by_key.get(e[2])
                i = seen[e[2]] = seen.get(e[2], -1) + 1
                if scopes and i < len(scopes) and scopes[i]:
                    mine.append(e)
                elif not e[2].startswith(tr.CONTAINERS):
                    rest.append(e)
            other = tr.busy(rest, r_lo, r_hi)
            for s, e in tr.busy(mine, r_lo, r_hi):
                total += e - s
                exposed += (e - s) - tr.overlap(other, s, e)
    if not steps:
        return None
    return {"total": total * 1e-6 / steps, "exposed": exposed * 1e-6 / steps}
