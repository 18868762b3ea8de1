"""Operations and bytes of the sparse-expert decoder's step and of its
grouped products, from shapes alone (``flops.py``'s rules: the algorithm's
requirements, a product of ``[m, k] @ [k, n]`` is ``2 m k n`` operations;
nothing recomputed and no row of padding is credited)."""

from __future__ import annotations

#: the nine grouped products of a train step by kernel: forward gate, up
#: and down; their ``dx``; their ``dw``
GMM_KERNELS = ("hetu_moe_gmm_fwd", "hetu_moe_gmm_dx", "hetu_moe_gmm_dw")


def olmoe_forward_flops_per_token(c, seq):
    """Forward pass of the OLMoE decoder, per token, by part: per layer the
    four ``H x H`` attention projections, causal attention's two products
    against on average ``seq / 2`` keys, the router, and ``k`` SwiGLU
    experts of three ``H x F`` products; once, the untied head."""
    h, f = c["hidden_size"], c["intermediate_size"]
    layers = c["num_hidden_layers"]
    return {
        "attention_projections": layers * 8.0 * h * h,
        "causal_attention": layers * 4.0 * (seq / 2.0) * h,
        "router": layers * 2.0 * h * c["num_experts"],
        "experts": layers * c["num_experts_per_tok"] * 6.0 * h * f,
        "head": 2.0 * h * c["vocab_size"]}


def olmoe_train_flops_per_token(c, seq):
    """Forward plus backward (twice forward), per input token."""
    return 3.0 * sum(olmoe_forward_flops_per_token(c, seq).values())


def gmm_call(pairs, num_experts, hidden, inter, itemsize=2):
    """``(operations, bytes)`` of one grouped product over ``pairs`` rows
    between the widths ``hidden`` and ``inter``: the same for each of the
    nine (forward ``[P, a] x [E, a, b]``, ``dx`` the same shapes back,
    ``dw`` ``[P, a]^T [P, b]`` into ``[E, a, b]``): the rows' two
    activations and the experts' weights, each read or written once."""
    return (2.0 * pairs * hidden * inter,
            float((pairs * (hidden + inter) + num_experts * hidden * inter)
                  * itemsize))
