"""``moe_block_device_ms_per_step`` where the experts are spread over the
chips: device time a step AND CHIP under ``hetu_moe_route``, ``_dispatch``,
``_exchange`` (the all-gathers of tokens and the reduce-scatters of partial
sums), ``_experts`` and ``_combine``, all expert layers, forward, recomputed
forward and backward (``_scopes.py``: the events of every device over the
executions of the step program on every device).  Says the exchange's own
numbers beside it (``flops_mellum.exchange_ms``): its time, the part no
computation hides, and the bytes the traced shapes say a chip receives.
Nothing without the program's ``hetu_moe_pairs_routed_total``."""
from chipbench import flops_mellum as fl
from chipbench.metrics._moe import SCOPES, sample
from chipbench.metrics._scopes import scoped_ms


def read(ctx):
    if sample(ctx, "hetu_moe_pairs_routed_total") is None:
        return None
    ms = scoped_ms(ctx, SCOPES + (fl.EXCHANGE,), "moe")
    if ms is None:
        return None
    p, c = ctx["program"], ctx["config"]
    a_call = fl.exchange_call(p.tokens_per_step // p.ranks, c["hidden_size"],
                              c["num_experts_per_tok"], p.ranks)
    own = fl.exchange_ms(ctx)
    ctx["say"](f"moe: the exchange over {p.ranks} chips: the forward "
               f"all-gather brings a chip {a_call['gather'] / 1e6:.1f} MB, "
               f"its reduce-scatter {a_call['scatter'] / 1e6:.1f} MB; a step "
               f"runs {p.forward_passes + 1} all-gathers and 2 "
               f"reduce-scatters a layer; device time under {fl.EXCHANGE} "
               + ("not read" if own is None else
                  f"{own['total']:.3f} ms a step and chip, of which "
                  f"{own['exposed']:.3f} ms with nothing else running"))
    return sum(ms.values())
