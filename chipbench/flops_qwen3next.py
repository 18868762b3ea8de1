"""Operations and bytes of the Qwen3-Next decoder's step, of the gated delta
rule and of the held experts' grouped products, from shapes alone
(``flops.py``'s rules: the algorithm's requirements, a product of ``[m, k] @
[k, n]`` is ``2 m k n`` operations; nothing recomputed and no row of padding
is credited)."""

from __future__ import annotations

from chipbench.reference.qwen3_next import layer_kinds  # noqa: F401


def forward_flops_per_token(c, seq, held_pairs_per_token):
    """Forward pass, per token, by part.  MODEL operations: the delta rule is
    the recurrence's three rank-one passes over a ``d_k x d_v`` state a value
    head (decay and read ``S^T k``, write ``k u^T``, read ``S^T q``: ``6 d_k
    d_v``), not the chunked form's products; causal attention reads on
    average ``seq / 2`` keys; the experts are those a token's pairs on HELD
    experts take (``held_pairs_per_token``: 10 x 32 / 512 expected)."""
    h = c["hidden_size"]
    kinds = layer_kinds(c)
    n_gdn, n_att = kinds.count("linear_attention"), kinds.count(
        "full_attention")
    nk, nv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    key_dim, value_dim = nk * dk, nv * dv
    inner = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    f, fs = c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
    return {
        "deltanet_projections": n_gdn * (
            2.0 * h * (2 * key_dim + 2 * value_dim + 2 * nv)
            + 2.0 * value_dim * h
            + 2.0 * c["linear_conv_kernel_dim"] * (2 * key_dim + value_dim)),
        "delta_rule": n_gdn * 6.0 * nv * dk * dv,
        "attention_projections": n_att * (2.0 * h * (2 * inner + 2 * kv)
                                          + 2.0 * inner * h),
        "causal_attention": n_att * 4.0 * (seq / 2.0) * inner,
        "router": len(kinds) * 2.0 * h * c["deployment"]["num_experts"],
        "shared_expert": len(kinds) * (6.0 * h * fs + 2.0 * h),
        "held_experts": len(kinds) * held_pairs_per_token * 6.0 * h * f,
        "head": 2.0 * h * c["vocab_size"]}


def delta_rule_chunk(chunk, dk, dv):
    """Forward operations of one chunk of one value head in the chunked
    form: ``K K^T``, ``Q K^T`` and ``P U`` (``2 C^2 d`` each), the unit
    lower triangular solve for ``V'`` and ``W`` by substitution (``C^2 (d_k
    + d_v)``; the program forms the inverse by block products, which costs
    more and earns nothing), and the three products with the state (``W S``,
    ``Q S``, ``K^T U``: ``2 C d_k d_v`` each)."""
    c = float(chunk)
    return (2 * c * c * dk * 2 + 2 * c * c * dv + c * c * (dk + dv)
            + 3 * 2 * c * dk * dv)


def delta_rule_step(c, tokens, chunk, itemsize=2):
    """``(operations, bytes)`` of the delta rule of ONE DeltaNet layer in a
    train step over ``tokens`` positions: forward as ``delta_rule_chunk``
    over every chunk and value head; q, k, v read and o written in the
    compute type, g and beta read in f32, one f32 ``d_k x d_v`` state a
    chunk and head written, each moved once.  The backward pass is taken as
    twice the forward, in operations (two products for each) and in bytes
    (it reads what the forward read and wrote and writes the five
    gradients)."""
    nv = c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    chunks = -(-tokens // chunk)
    ops = chunks * nv * delta_rule_chunk(chunk, dk, dv)
    nbytes = (tokens * nv * (2 * dk + 2 * dv) * itemsize
              + tokens * nv * 2 * 4 + chunks * nv * dk * dv * 4)
    return 3.0 * ops, 3.0 * nbytes


def held_gmm_call(pairs, count, hidden, inter, itemsize=2):
    """``(operations, bytes)`` of one grouped product over ``pairs`` rows of
    ``count`` held experts between the widths ``hidden`` and ``inter``
    (``flops_moe.gmm_call``'s rule with the experts that are here)."""
    return (2.0 * pairs * hidden * inter,
            float((pairs * (hidden + inter) + count * hidden * inter)
                  * itemsize))
