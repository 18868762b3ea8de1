"""Operations and bytes of the Laguna decoder's step and of attention over a
window, from shapes alone (``flops.py``'s rules: the algorithm's
requirements, a product of ``[m, k] @ [k, n]`` is ``2 m k n`` operations;
nothing recomputed, no masked pair and no row of padding is credited)."""

from __future__ import annotations

from chipbench.flops import FLASH_PASSES
from chipbench.flops_qwen3next import held_gmm_call  # noqa: F401

#: the window pair's events, by pass, as ``flops.FLASH_PASSES`` names flash's
WINDOW_EVENTS = {"forward": "hetu_swa_fwd", "backward": "hetu_swa_bwd"}


def window_pairs(seq, window):
    """(query, key) pairs a head attends to with ``0 <= i - j < window``: ``w
    S - w (w - 1) / 2`` (the first ``w - 1`` rows see fewer); all earlier
    keys where the window holds them all."""
    w = min(window, seq)
    return w * seq - w * (w - 1) / 2.0


def layers_of(c, kind):
    return [i for i, k in enumerate(c["layer_types"]) if k == kind]


def forward_flops_per_token(c, seq, held_pairs_per_token):
    """Forward pass, per token, by part.  A full layer's attention reads the
    ``S (S + 1) / 2`` causal pairs, a window layer's its band alone; the
    experts are those a token's pairs on HELD experts take."""
    h, d, kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    heads = c["num_attention_heads_per_layer"]
    full, win = (layers_of(c, k) for k in ("full_attention",
                                           "sliding_attention"))

    def projections(layers):
        # q and o at the layer's heads, k and v at the key heads, the gate
        return sum(2.0 * h * (2 * heads[i] * d + 2 * kv * d + heads[i])
                   for i in layers)

    def products(layers, pairs):
        # QK^T and PV over the pairs a head sees, a token's share
        return sum(4.0 * heads[i] * d * pairs / seq for i in layers)
    n_dense = list(c["mlp_layer_types"]).count("dense")
    n_moe = len(c["mlp_layer_types"]) - n_dense
    return {
        "full_projections": projections(full),
        "full_attention": products(full, window_pairs(seq, seq)),
        "window_projections": projections(win),
        "window_attention": products(win, window_pairs(
            seq, c["sliding_window"])),
        "dense_mlp": n_dense * 6.0 * h * c["intermediate_size"],
        "router": n_moe * 2.0 * h * c["deployment"]["num_experts"],
        "shared_expert": n_moe * 6.0 * h
        * c["shared_expert_intermediate_size"],
        "held_experts": n_moe * held_pairs_per_token * 6.0 * h
        * c["moe_intermediate_size"],
        "head": 2.0 * h * c["vocab_size"]}


def window_pass(name, batch, heads, key_heads, seq, head_dim, window,
                itemsize=2):
    """``(operations, bytes)`` of one pass of attention over a window:
    ``flops.FLASH_PASSES``'s products (2 forward, 5 backward) over the band's
    pairs a head; q and o (and do, dq) at ``heads`` query heads, k and v (and
    dk, dv) at the ``key_heads`` the model has, each moved once: the copies
    of a key head for its query heads are the program's, not the
    algorithm's."""
    p = FLASH_PASSES[name]
    pairs = window_pairs(seq, window)
    rows = p["tensors"] // 2 * seq * head_dim * itemsize
    return (p["products"] * 2.0 * batch * heads * pairs * head_dim,
            float(batch * (heads + key_heads) * rows))
