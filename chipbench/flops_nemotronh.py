"""Operations and bytes of the Nemotron-H decoder's step, of the chunked
state-space scan and of the held relu2 experts' grouped products, from shapes
alone (``flops.py``'s rules: the algorithm's requirements, a product of ``[m,
k] @ [k, n]`` is ``2 m k n`` operations; nothing recomputed and no row of
padding is credited)."""

from __future__ import annotations

from chipbench.flops_qwen3next import held_gmm_call  # noqa: F401


def forward_flops_per_token(c, seq, held_pairs_per_token):
    """Forward pass, per token, by part.  MODEL operations: the state-space
    scan is the recurrence's three passes over a ``p x n`` state a head
    (decay, write ``dt x B^T``, read ``S C``: ``6 p n``) and the skip, not
    the chunked form's products; causal attention reads on average ``seq /
    2`` keys; the experts are those a token's pairs on HELD experts take
    (``held_pairs_per_token``: 6 x 8 / 128 expected) and are not gated (two
    products an FFN)."""
    h = c["hidden_size"]
    pattern = c["hybrid_override_pattern"]
    n_m, n_e, n_a = (pattern.count(k) for k in "ME*")
    heads, p = c["mamba_num_heads"], c["mamba_head_dim"]
    d, gn = heads * p, c["n_groups"] * c["ssm_state_size"]
    inner = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    f = c["moe_intermediate_size"]
    fs = c["moe_shared_expert_intermediate_size"] * c["n_shared_experts"]
    return {
        "mamba_projections": n_m * (
            2.0 * h * (2 * d + 2 * gn + heads) + 2.0 * d * h
            + 2.0 * c["conv_kernel"] * (d + 2 * gn)),
        "ssm_scan": n_m * (6.0 * heads * p * c["ssm_state_size"] + 2.0 * d),
        "attention_projections": n_a * (2.0 * h * (inner + 2 * kv)
                                        + 2.0 * inner * h),
        "causal_attention": n_a * 4.0 * (seq / 2.0) * inner,
        "router": n_e * 2.0 * h * c["deployment"]["n_routed_experts"],
        "shared_expert": n_e * 4.0 * h * fs,
        "held_experts": n_e * held_pairs_per_token * 4.0 * h * f,
        "head": 2.0 * h * c["vocab_size"]}


def ssd_chunk(chunk, p, n, heads_a_group):
    """Forward operations of one chunk of one head in the chunked form: ``C
    B^T`` (``2 L^2 n``, shared by a group's heads), the masked scores times
    ``dt x`` (``2 L^2 p``), the chunk's own end state and ``C S`` of its
    start state (``2 L p n`` each)."""
    L = float(chunk)
    return 2 * L * L * (n / heads_a_group + p) + 4 * L * p * n


def ssd_step(c, tokens, itemsize=2):
    """``(operations, bytes)`` of the chunked scan of ONE Mamba-2 mixer in a
    train step over ``tokens`` positions at the configuration's chunk:
    forward as ``ssd_chunk`` over every chunk and head; x read and y written
    in the compute type, B and C read in it, dt read in f32, one f32 ``p x
    n`` state a chunk and head written, each moved once.  The backward pass
    is taken as twice the forward, in operations (two products for each) and
    in bytes (it reads what the forward read and wrote and writes the five
    gradients)."""
    heads, p = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n = c["n_groups"], c["ssm_state_size"]
    chunks = -(-tokens // c["chunk_size"])
    ops = chunks * heads * ssd_chunk(c["chunk_size"], p, n, heads // g)
    nbytes = (tokens * (2 * heads * p + 2 * g * n) * itemsize
              + tokens * heads * 4 + chunks * heads * p * n * 4)
    return 3.0 * ops, 3.0 * nbytes
