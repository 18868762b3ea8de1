"""Operations of the Ouro looped decoder's step, from shapes alone
(``flops.py``'s rules: the algorithm's requirements, a product of ``[m, k] @
[k, n]`` is ``2 m k n`` operations; nothing recomputed is credited)."""

from __future__ import annotations


def forward_flops_per_token(c, seq):
    """Forward pass, per token, by part.  MODEL operations: ``P =
    total_ut_steps`` passes over ``k = num_hidden_layers`` layers are ``P k``
    layer applications (the weights are shared, the work is not), each four
    ``H x H`` projections, causal attention's two products over on average
    ``seq / 2`` keys and the gated MLP's three products; the untied head over
    the whole vocabulary and the exit gate ``H -> 1`` once a pass."""
    h, passes = c["hidden_size"], c["total_ut_steps"]
    calls = passes * c["num_hidden_layers"]
    return {
        "attention_projections": calls * 8.0 * h * h,
        "causal_attention": calls * 4.0 * (seq / 2.0) * h,
        "mlp": calls * 6.0 * h * c["intermediate_size"],
        "head": passes * 2.0 * h * c["vocab_size"],
        "exit_gate": passes * 2.0 * h}
