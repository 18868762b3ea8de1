"""Operations and bytes of the ZAYA1 decoder's step, of the held experts'
grouped products and of the mixing inside compressed convolutional attention,
from shapes alone (``flops.py``'s rules: the algorithm's requirements, a
product of ``[m, k] @ [k, n]`` is ``2 m k n`` operations; nothing recomputed
and no row of padding is credited)."""

from __future__ import annotations

from chipbench.flops_qwen3next import held_gmm_call  # noqa: F401


def latent(c):
    """``(L_q, L_k, heads in the mixing, d)``: the latent's widths."""
    d = c["head_dim"]
    heads = c["num_attention_heads"] + c["num_key_value_heads"]
    return (c["num_attention_heads"] * d, c["num_key_value_heads"] * d,
            heads, d)


def forward_flops_per_token(c, seq, held_pairs_per_token):
    """Forward pass, per token, by part.  MODEL operations over what THIS
    chip computes: the five latent products (``W_q``, ``W_k``, ``W_v1``,
    ``W_v2`` down, ``W_o`` up); causal attention at half the keys, ``H`` query
    heads of ``d``; the head-mixing taps (two ``d x d`` products a head of the
    latent; the depthwise taps, means and norms are no products); the
    router's MLP (whole on every rank); the experts a token's pairs on HELD
    experts take (a pair that chose no expert or an expert held elsewhere
    earns nothing); the tied head over the slice."""
    h, layers = c["hidden_size"], c["num_hidden_layers"]
    lq, lk, heads, d = latent(c)
    r = c["router_hidden_size"]
    outputs = c["deployment"]["num_experts"] + 1
    return {
        "cca_projections": layers * 2.0 * h * (2 * lq + 2 * lk),
        "causal_attention": layers * 4.0 * (seq / 2.0) * lq,
        "cca_head_mixing": layers * heads * 2 * 2.0 * d * d,
        "router": layers * 2.0 * (h * r + 2 * r * r + r * outputs),
        "held_experts": layers * held_pairs_per_token * 6.0 * h
        * c["moe_intermediate_size"],
        "head": 2.0 * h * c["vocab_size"]}


def cca_sublayer(c, tokens, itemsize=2):
    """``(operations, bytes)`` a train step REQUIRES of ONE application of
    what stands under ``hetu_cca`` over ``tokens``, whatever implements it:
    the LEAST bytes, in values of the compute type a token.

    Forward, ``2 (L_q + L_k) + L_k``: ``[q~ | k~]`` read and ``[q^ | k^]``
    written once (both convolutions, the means, the norms and the temperature
    between them, no array in between), the values' shifted half (``L_k / 2``:
    the shift of ``u`` for ``W_v2`` is the shift of the product) read and
    written.  Backward, ``3 (L_q + L_k) + L_k``: ``[q~ | k~]`` read again (the
    forward pass keeps nothing but its operands), the cotangent of ``[q^ |
    k^]`` read, that of ``[q~ | k~]`` written, the shifted half's cotangent
    read and written.  The taps, biases and temperature (0.33 M numbers a
    layer) are not counted: under 2%.  Operations: three times the forward's
    head-mixing products."""
    lq, lk, heads, d = latent(c)
    values = 5 * (lq + lk) + 2 * lk
    ops = 3.0 * tokens * heads * 2 * 2.0 * d * d
    return ops, float(values * tokens * itemsize)
