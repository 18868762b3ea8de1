"""Operations and bytes of the Ling-3.0 decoder's step, of the delta rule
with a decay a channel, of flash attention with keys wider than values and of
the held experts' grouped products, from shapes alone (``flops.py``'s rules:
the algorithm's requirements, a product of ``[m, k] @ [k, n]`` is ``2 m k n``
operations; nothing recomputed and no row of padding is credited)."""

from __future__ import annotations

from chipbench.flops import FLASH_PASSES
from chipbench.flops_qwen3next import held_gmm_call  # noqa: F401
from chipbench.reference.ling3 import layer_kinds  # noqa: F401


def layer_counts(c):
    """``(KDA layers, latent-attention layers, dense FFNs, expert blocks)``."""
    kinds = layer_kinds(c)
    dense = min(c["first_k_dense_replace"], len(kinds))
    return (kinds.count("kda"), kinds.count("attention"), dense,
            len(kinds) - dense)


def forward_flops_per_token(c, seq, held_pairs_per_token):
    """Forward pass, per token, by part.  MODEL operations: the delta rule is
    the recurrence's three rank-one passes over a ``d x d`` state a head
    (``6 d^2``), not the chunked form's products; causal attention reads on
    average ``seq / 2`` keys, its scores ``d_n + d_r`` wide and its values
    ``d_v``; the experts are those a token's pairs on HELD experts take."""
    h = c["hidden_size"]
    n_kda, n_att, n_dense, n_moe = layer_counts(c)
    nh, d = c["num_attention_heads"], c["head_dim"]
    hd = nh * d
    dn, dr, dv, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"], c["kv_lora_rank"])
    f, fs = c["moe_intermediate_size"], c[
        "moe_shared_expert_intermediate_size"]
    return {
        "kda_projections": n_kda * (
            2.0 * h * (5 * hd + nh) + 2.0 * hd * h
            + 2.0 * c["short_conv_kernel_size"] * 3 * hd),
        "kda_rule": n_kda * 6.0 * nh * d * d,
        "attention_projections": n_att * (
            2.0 * h * nh * (dn + dr) + 2.0 * h * (r + dr)
            + 2.0 * r * nh * (dn + dv) + 2.0 * h * nh + 2.0 * nh * dv * h),
        "causal_attention": n_att * 2.0 * (seq / 2.0) * nh * (dn + dr + dv),
        "dense_mlp": n_dense * 6.0 * h * c["intermediate_size"],
        "router": n_moe * 2.0 * h * c["deployment"]["num_experts"],
        "shared_expert": n_moe * 6.0 * h * fs,
        "held_experts": n_moe * held_pairs_per_token * 6.0 * h * f,
        "head": 2.0 * h * c["vocab_size"]}


def kda_chunk(chunk, dk, dv):
    """Forward operations of one chunk of one head in the chunked form: ``K
    K^T`` and ``Q K^T`` with their decays inside (``2 C^2 d_k`` each), ``P
    U`` (``2 C^2 d_v``), the unit lower triangular solve for ``V'`` and ``W``
    by substitution (``C^2 (d_k + d_v)``) and the three products with the
    state (``W S``, ``Q S``, ``K^T U``: ``2 C d_k d_v`` each).  The
    sub-chunks' extra products of the program earn nothing."""
    c = float(chunk)
    return (2 * c * c * dk * 2 + 2 * c * c * dv + c * c * (dk + dv)
            + 3 * 2 * c * dk * dv)


def kda_step(c, tokens, chunk, itemsize=2):
    """``(operations, bytes)`` of the delta rule of ONE KDA layer in a train
    step over ``tokens`` positions: forward as ``kda_chunk`` over every chunk
    and head; q, k, v read and o written in the compute type, g (a number a
    channel) and beta read in f32, one f32 ``d x d`` state a chunk and head
    written, each moved once.  The backward pass is taken as twice the
    forward, in operations and in bytes."""
    nh, d = c["num_attention_heads"], c["head_dim"]
    chunks = -(-tokens // chunk)
    ops = chunks * nh * kda_chunk(chunk, d, d)
    nbytes = (tokens * nh * 4 * d * itemsize + tokens * nh * (d + 1) * 4
              + chunks * nh * d * d * 4)
    return 3.0 * ops, 3.0 * nbytes


def flash_pass(name, rows, seq, d_qk, d_v, itemsize=2):
    """``(operations, bytes)`` of one flash-attention pass over ``rows``
    (batch x heads) sequences whose scores are ``d_qk`` wide and whose values
    ``d_v``: forward ``Q K^T`` and ``P V`` (``2 S^2 (d_qk + d_v)``); backward
    S once more, dK and dQ over ``d_qk``, dP and dV over ``d_v``; q, k (and
    dq, dk) ``d_qk`` wide, v, o (and do, dv) ``d_v``, each moved once."""
    wide, narrow = {"forward": (1, 1), "backward": (3, 2)}[name]
    assert wide + narrow == FLASH_PASSES[name]["products"]
    tensors = FLASH_PASSES[name]["tensors"] // 2
    return (2.0 * rows * seq * seq * (wide * d_qk + narrow * d_v),
            float(tensors * rows * seq * (d_qk + d_v) * itemsize))
