"""Operations and bytes of SDAR's block-diffusion training step and of flash
attention under the block-diffusion mask, from shapes alone (``flops.py``'s
rules: the algorithm's requirements, a product of ``[m, k] @ [k, n]`` is ``2 m
k n`` operations; nothing recomputed, no tile's masked part and no row of
padding is credited)."""

from __future__ import annotations

from chipbench import flops
from chipbench.flops_qwen3next import held_gmm_call  # noqa: F401


def visible_pairs(tokens, block):
    """The (query, key) pairs a head sees in one pass over a clean and a
    noised copy of ``tokens`` tokens in blocks of ``block``: with ``n = tokens
    / block`` blocks, clean on clean ``block^2 n (n + 1) / 2``, noised on clean
    ``block^2 n (n - 1) / 2``, noised on noised ``block^2 n``: ``tokens^2 +
    block x tokens`` of the ``4 tokens^2`` of the square."""
    assert tokens % block == 0, (tokens, block)
    return tokens * tokens + block * tokens


def flash_pass(name, rows, tokens, block, head_dim, itemsize=2):
    """``(operations, bytes)`` of one flash pass under the block-diffusion
    mask over ``rows`` (batch x heads) sequences of ``2 tokens`` positions:
    the pass's products (``flops.FLASH_PASSES``) over the visible pairs, each
    tensor of ``2 tokens`` rows read or written once."""
    p = flops.FLASH_PASSES[name]
    return (p["products"] * 2.0 * rows * visible_pairs(tokens, block)
            * head_dim,
            float(p["tensors"] * rows * 2 * tokens * head_dim * itemsize))


def forward_flops_per_token(c, tokens, held_pairs_per_position):
    """Forward pass, per DATA token, by part.  MODEL operations over what THIS
    chip computes: a token is two positions through the layers (its clean and
    its noised copy): the four attention projections (``W_q`` and ``W_o`` at
    ``H d``, ``W_k`` and ``W_v`` at ``KV d``), the mask's pairs (two products
    over ``visible_pairs`` a head, a token's share), the router (whole on
    every rank), the experts a position's pairs on HELD experts take; the
    untied head over the slice ONCE (the noised position alone)."""
    h, layers = c["hidden_size"], c["num_hidden_layers"]
    d = c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    block = c["assumed"]["block_length"]
    return {
        "attention_projections": layers * 2 * 2.0 * h * (2 * q + 2 * kv),
        "masked_attention": layers * 4.0 * q
        * visible_pairs(tokens, block) / tokens,
        "router": layers * 2 * 2.0 * h * c["deployment"]["num_experts"],
        "held_experts": layers * 2 * held_pairs_per_position * 6.0 * h
        * c["moe_intermediate_size"],
        "head": 2.0 * h * c["vocab_size"]}
