"""Operations and bytes of the Xing4.0 decoder's step, of flash attention with
keys wider than values, of the held experts' grouped products and of the
hyper-connections, from shapes alone (``flops.py``'s rules: the algorithm's
requirements, a product of ``[m, k] @ [k, n]`` is ``2 m k n`` operations;
nothing recomputed and no row of padding is credited)."""

from __future__ import annotations

from chipbench.flops_ling3 import flash_pass  # noqa: F401
from chipbench.reference.xing4 import decoder_layers, is_dense


def layer_counts(c):
    """``(decoder layers walked, dense FFNs, expert blocks, hyper-connected
    sublayers)``: the MTP depth's layer is an expert layer among them."""
    layers = decoder_layers(c)
    dense = sum(is_dense(c, i) for i in range(layers))
    return layers, dense, layers - dense, 2 * layers


def forward_flops_per_token(c, seq, held_pairs_per_token):
    """Forward pass, per token, by part.  MODEL operations over what THIS
    chip computes: causal attention reads on average ``seq / 2`` keys, its
    scores ``d_n + d_r`` wide and its values ``d_v``; the experts are those a
    token's pairs on HELD experts take; the head is walked twice (the next
    token's and the MTP depth's); a hyper-connected sublayer is its one
    product ``[n C] x [2 n + n^2]`` and the three mixes."""
    h, n = c["hidden_size"], c["hc_mult"]
    layers, n_dense, n_moe, n_hc = layer_counts(c)
    nh = c["num_attention_heads"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    rq, r = c["q_lora_rank"], c["kv_lora_rank"]
    f = c["moe_intermediate_size"]
    mtp = c["num_nextn_predict_layers"]
    return {
        "attention_projections": layers * 2.0 * (
            h * rq + rq * nh * (dn + dr) + h * (r + dr)
            + r * nh * (dn + dv) + nh * dv * h),
        "causal_attention": layers * 2.0 * (seq / 2.0) * nh * (dn + dr + dv),
        "dense_mlp": n_dense * 6.0 * h * c["intermediate_size"],
        "router": n_moe * 2.0 * h * c["deployment"]["n_routed_experts"],
        "shared_expert": n_moe * 6.0 * h * f * c["n_shared_experts"],
        "held_experts": n_moe * held_pairs_per_token * 6.0 * h * f,
        "hyper_connections": n_hc * (2.0 * n * h * (2 * n + n * n)
                                     + 2.0 * h * (2 * n + n * n)),
        "mtp_combine": mtp * 2.0 * 2 * h * h,
        "head": (1 + mtp) * 2.0 * h * c["vocab_size"]}


def hc_sublayer(c, tokens, itemsize=2):
    """``(operations, bytes)`` a train step REQUIRES of ONE hyper-connected
    sublayer application over ``tokens``, whatever implements it: the LEAST
    bytes, in stream-widths (``C`` values a token) of the compute type.

    Forward, ``3 n + 2``: the streams are read before the sublayer's function
    (maps and ``u = Hpre X``: ``n`` read, 1 written) and again behind it
    (``X' = Hres X + Hpost^T y``: ``n + 1`` read, ``n`` written); its
    function stands between and no fast memory holds ``tokens x n C``.
    Backward, ``4 n + 3``: ``dy = Hpost dX'`` before the function's backward
    pass (``n`` read, 1 written), and behind it ``dX`` with every map's
    gradient (``dX'``, ``X``, ``y`` and ``du`` read: ``2 n + 2``; ``n``
    written).  The maps themselves (24 numbers a token in f32) and ``phi``
    are not counted: under 1%.  A recomputed forward pass earns nothing.
    Operations: three times the forward's (``forward_flops_per_token``)."""
    n, h = c["hc_mult"], c["hidden_size"]
    widths = (3 * n + 2) + (4 * n + 3)
    ops = 3.0 * tokens * (2.0 * n * h * (2 * n + n * n)
                          + 2.0 * h * (2 * n + n * n))
    return ops, float(widths * h * tokens * itemsize)
