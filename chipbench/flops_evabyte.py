"""Operations and bytes of an EvaByte decoder's step and of EVA attention,
from shapes alone (``flops.py``'s rules: the algorithm's requirements, a
product of ``[m, k] @ [k, n]`` is ``2 m k n`` operations; nothing recomputed,
no masked pair and no row of padding is credited).

EVA's pairs a head over ``S`` positions, window ``W``, chunk ``c``
(``eva_pairs``): position ``t`` of window ``w = t // W`` attends to the ``t - W
w + 1`` keys of its window (local) and to the ``(W / c) w`` summaries of the
windows before it (remote).  A pass forms each pair's score and value product
once (forward 2 products, backward 5, as ``flops.FLASH_PASSES``).  The
summaries themselves are ``2 d`` operations a key and head for ``phi . k``,
``2 d`` for ``alpha v`` and ``d`` for the mean, forward.
"""

from __future__ import annotations

from chipbench.flops import FLASH_PASSES

#: the EVA pair's events, by pass, as ``flops.FLASH_PASSES`` names flash's
EVA_EVENTS = {"forward": "hetu_eva_fwd", "backward": "hetu_eva_bwd"}
#: a kernel pair for the summaries, should the program get one: its events
#: are the same reader's
PREP_EVENTS = {"forward": "hetu_eva_prep_fwd", "backward": "hetu_eva_prep_bwd"}


def eva_pairs(seq, window, chunk):
    """``(local, remote)`` pairs a head."""
    full, rest = divmod(seq, window)
    local = full * window * (window + 1) / 2.0 + rest * (rest + 1) / 2.0
    remote = (window // chunk) * (window * full * (full - 1) / 2.0
                                  + rest * full)
    return local, remote


def forward_flops_per_token(c, seq):
    """Forward pass, per token, by part."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    d, n = h // heads, c["num_hidden_layers"]
    local, remote = eva_pairs(seq, c["window_size"], c["chunk_size"])
    return {
        "mlp": n * 6.0 * h * c["intermediate_size"],
        "projections": n * 8.0 * h * heads * d,
        "eva_local": n * 4.0 * heads * d * local / seq,
        "eva_remote": n * 4.0 * heads * d * remote / seq,
        "summaries": n * 5.0 * heads * d,
        "heads": 2.0 * h * c["num_pred_heads"] * c["vocab_size"]}


def eva_pass(name, batch, heads, seq, dim, window, chunk, itemsize=2):
    """``(operations, bytes)`` of one pass of EVA attention: the plan's pairs,
    2 products forward and 5 backward; q, o, k and v (backward: their
    cotangents too) and the summaries ``k^``, ``v^`` of the windows that are
    read (and their cotangents), each moved once."""
    p = FLASH_PASSES[name]
    local, remote = eva_pairs(seq, window, chunk)
    summaries = (seq - 1) // window * (window // chunk)
    rows = p["tensors"] // 2 * (seq + summaries) * dim * itemsize
    return (p["products"] * 2.0 * batch * heads * (local + remote) * dim,
            float(batch * 2 * heads * rows))
