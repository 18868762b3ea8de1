"""Operations and bytes of the Granite 4.0-H decoder's step and of its
chunked state-space scan, from shapes alone (``flops.py``'s rules: the
algorithm's requirements, a product of ``[m, k] @ [k, n]`` is ``2 m k n``
operations; nothing recomputed and no row of padding is credited)."""

from __future__ import annotations

from chipbench.flops_nemotronh import ssd_chunk


def forward_flops_per_token(c, seq):
    """Forward pass, per token, by part.  MODEL operations: the state-space
    scan is the recurrence's three passes over a ``p x n`` state a head
    (decay, write ``dt x B^T``, read ``S C``: ``6 p n``) and the skip, not
    the chunked form's products; causal attention reads on average ``seq /
    2`` keys; every layer's gated MLP is three products; the tied head is
    over the rows of the vocabulary held."""
    h = c["hidden_size"]
    kinds = c["layer_types"]
    n_m, n_a = kinds.count("mamba"), kinds.count("attention")
    heads, p = c["mamba_n_heads"], c["mamba_d_head"]
    d, gn = heads * p, c["mamba_n_groups"] * c["mamba_d_state"]
    kv = c["num_key_value_heads"] * (h // c["num_attention_heads"])
    return {
        "mamba_projections": n_m * (
            2.0 * h * (2 * d + 2 * gn + heads) + 2.0 * d * h
            + 2.0 * c["mamba_d_conv"] * (d + 2 * gn)),
        "ssm_scan": n_m * (6.0 * heads * p * c["mamba_d_state"] + 2.0 * d),
        "attention_projections": n_a * (2.0 * h * (h + 2 * kv) + 2.0 * h * h),
        "causal_attention": n_a * 4.0 * (seq / 2.0) * h,
        "mlp": len(kinds) * 6.0 * h * c["shared_intermediate_size"],
        "head": 2.0 * h * c["vocab_size"]}


def ssd_step(c, tokens, chunk, itemsize=2):
    """``(operations, bytes)`` of the chunked scan of ONE Mamba-2 mixer in a
    train step over ``tokens`` positions at ``chunk`` positions a chunk (the
    chunk the program runs, not ``mamba_chunk_size``): forward as
    ``flops_nemotronh.ssd_chunk`` over every chunk and head, ``C B^T``
    credited ONCE for the heads of a group (all 64 here); x read and y
    written in the compute type, B and C read in it once (not once a block
    of heads), dt read in f32, one f32 ``p x n`` state a chunk and head
    written, each moved once.  The backward pass is taken as twice the
    forward, in operations and in bytes."""
    heads, p = c["mamba_n_heads"], c["mamba_d_head"]
    g, n = c["mamba_n_groups"], c["mamba_d_state"]
    chunks = -(-tokens // chunk)
    ops = chunks * heads * ssd_chunk(chunk, p, n, heads // g)
    nbytes = (tokens * (2 * heads * p + 2 * g * n) * itemsize
              + tokens * heads * 4 + chunks * heads * p * n * 4)
    return 3.0 * ops, 3.0 * nbytes
