"""Operations and bytes of a SambaY decoder's step (Phi-4-mini-flash-
reasoning), of differential attention and of the Mamba-1 selective scan, from
shapes alone (``flops.py``'s rules: the algorithm's requirements, a product of
``[m, k] @ [k, n]`` is ``2 m k n`` operations; nothing recomputed, no masked
pair and no row of padding is credited).

Differential attention, a query PAIR and a (query, key) position pair: two
score products over a head's 64 and two value products over the pair's 128,
``2 (2 x 64) + 2 (2 x 128) = 768`` operations forward; the backward pass's ten
products (a softmax's five: the scores again and dK, dQ over 64, dP and dV
over 128), ``2 x 2 (3 x 64 + 2 x 128) = 1,792``.  That the program's kernels
see the halves of a pair as heads of 128 with a zero half is the program's.
"""

from __future__ import annotations

from chipbench.flops import FLASH_PASSES
from chipbench.flops_laguna import WINDOW_EVENTS, window_pairs  # noqa: F401
from chipbench.reference.phi4flash import kind_of


def kinds(c):
    return [kind_of(c, c["first_layer_index"] + i)
            for i in range(c["num_hidden_layers"])]


def sizes(c):
    a = c["assumed"]
    return {"d": c["hidden_size"], "inner": a["mamba_expand"]
            * c["hidden_size"], "n": a["mamba_d_state"],
            "r": a["mamba_dt_rank"], "k": a["mamba_d_conv"],
            "heads": c["num_attention_heads"],
            "kv": c["num_key_value_heads"],
            "head": c["hidden_size"] // c["num_attention_heads"]}


#: operations a channel, state and position of the scan, by pass: forward
#: ``Delta A``, ``exp``, ``a h``, ``(Delta u) B``, the sum, ``h C`` and its
#: sum; backward the recurrence once more (5) and the adjoint's 17
SCAN_OPS = {"forward": 7.0, "backward": 22.0}


def pair_ops(name, head):
    """Operations a query pair and (query, key) position pair of a pass."""
    if name == "forward":
        return 2.0 * (2 * head) + 2.0 * (2 * 2 * head)
    return 2.0 * 2 * (3 * head + 2 * 2 * head)


def forward_flops_per_token(c, seq):
    """Forward pass, per token, by part."""
    s = sizes(c)
    d, inner, n, r = s["d"], s["inner"], s["n"], s["r"]
    pairs, head = s["heads"] // 2, s["head"]
    ks = kinds(c)
    q_o = 2.0 * d * s["heads"] * head * 2        # the query and the output
    k_v = 2.0 * d * 2 * s["kv"] * head
    attend = pairs * pair_ops("forward", head)
    return {
        "mlp": len(ks) * 6.0 * d * c["intermediate_size"],
        "mamba_projections": ks.count("mamba") * (
            2.0 * d * 2 * inner + 2.0 * s["k"] * inner
            + 2.0 * inner * (r + 2 * n) + 2.0 * r * inner + 2.0 * inner * d),
        "scan": ks.count("mamba") * SCAN_OPS["forward"] * inner * n,
        "attention_projections": (
            sum(k in ("window", "full") for k in ks) * (q_o + k_v)
            + ks.count("cross") * q_o),
        "gmu": ks.count("gmu") * 4.0 * d * inner,
        "full_attention": sum(k in ("full", "cross") for k in ks) * attend
        * window_pairs(seq, seq) / seq,
        "window_attention": ks.count("window") * attend
        * window_pairs(seq, c["sliding_window"]) / seq,
        "head": 2.0 * d * c["vocab_size"]}


def differential_pass(name, batch, seq, c, window=None, itemsize=2):
    """``(operations, bytes)`` of one pass of one differential attention
    layer over the causal pairs (``window``: the band's): q and the output
    (and their cotangents) at the query heads' width, k and v (and theirs) at
    the key heads', each moved once."""
    s = sizes(c)
    pairs = window_pairs(seq, window or seq)
    wide, narrow = s["heads"] * s["head"], s["kv"] * s["head"]
    moved = {"forward": 2 * wide + 2 * narrow,
             "backward": 5 * wide + 4 * narrow}[name]
    assert FLASH_PASSES[name]["tensors"] in (4, 8)
    return (batch * (s["heads"] // 2) * pairs * pair_ops(name, s["head"]),
            float(batch * seq * moved * itemsize))


def selective_scan_step(c, tokens, itemsize=2):
    """``(operations, bytes)`` of ONE Mamba layer's scan a step, forward and
    backward: a state update and a read-out of ``N`` states a channel and
    position; ``xc`` (the compute type), ``Delta`` (f32), ``B`` and ``C`` (f32)
    read and ``y`` written once; backward the same inputs and ``dy`` read, the
    four cotangents written once and the recurrence run once more."""
    s = sizes(c)
    inner, n = s["inner"], s["n"]
    ops = (SCAN_OPS["forward"] + SCAN_OPS["backward"]) * tokens * inner * n
    forward = tokens * (inner * (itemsize + 4 + itemsize) + 2 * n * 4)
    backward = tokens * (inner * (itemsize + 4 + itemsize + itemsize + 4)
                         + 4 * n * 4)
    return ops, float(forward + backward)
