"""Plain reference of SDAR's block-diffusion training pass
(``JetLM/SDAR-30B-A3B-Chat``, ``model_type`` "sdar_moe") and its loss.
Straight ``jax.numpy`` in float32 at the highest matmul precision: attention
by blocks of query rows against all ``2L`` keys under a dense boolean mask
built from the four rules below, every held expert computed for every token
and masked by the router's weights; no kernel, no sort, no grouped product,
no recomputation.  Independent of ``hetu_tpu/models``, ``hetu_tpu/layers`` and
``hetu_tpu/ops``: it takes the weights under its own names (``WEIGHTS`` below;
matrices are ``[in, out]``, experts stacked on a leading axis) and the
configuration's published keys, and nothing else.

``L`` tokens a sequence, blocks of ``K = assumed.block_length`` (``b(i) = i //
K``), ``input_ids = [ids | noised] [B, 2L]``, the clean copy first.  ``eps``
``rms_norm_eps``, ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``::

    positions: clean token i and noised token i both turn at position i
    layer l: h = x + Attn(N(x; w_in));  x' = h + MoE(N(h; w_post))
    Attn: q = u W_q as H heads of d, k, v as KV heads of d, no bias; q and k
        normed a head (N over d with a learned [d] weight each), then
        rotate-half rotary over all d at rope_theta; query head h reads key
        head h // (H / KV); scores / sqrt(d); softmax over the keys the mask
        shows; W_o.
    the mask, query p on key r, each clean (c) or noised (n) with token
        indices i, j:  c on c  b(j) <= b(i);  n on c  b(j) < b(i);
        n on n  b(j) == b(i);  c on n never.
    MoE: s = softmax(u W_r) over ALL experts; the num_experts_per_tok largest
        (ties to the lower index); weights s_e / sum_chosen s
        (norm_topk_prob); y = sum w_e W_d,e (silu(W_g,e u) * W_u,e u).
    head: z_i = N(x[noised i]; w_f) W_head, the L noised positions alone.
    loss = 1 / (B L) sum_i [labels_i >= 0] weights_i CE(z_i, labels_i), no
        shift; ce_masked the unweighted mean over labelled positions.

Departures from the published description, each on purpose:

* ``held=(first, count)``: this chip's share of an expert-parallel layer.
  The expert weights given are those of experts ``first .. first + count -
  1`` and the sum over a token's chosen experts runs over those of them
  alone: what the experts on other chips would add is left out, as the
  program leaves it out.  The router, its choice and the normalisation (over
  all chosen, held or not) are over all experts.  ``held=None`` is the whole
  layer.
* The vocabulary may be a slice: ids, logits and the loss are over the rows
  of ``embed`` and ``lm_head`` that are given.

``matmul_inputs`` (default None: plain f32) rounds both operands of every
matrix product to that type before multiplying in f32; ``without`` changes
one piece (``CONTROLS``): the reference "at a lower precision" or "with a
piece changed", used on the chip to see which gaps each would open (the
traffic file's tolerances lie below them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .ling3 import _mm, _norm, swiglu

#: the weights ``forward`` reads: the model's, then per layer ``layers.<i>.``
WEIGHTS = ("embed", "norm", "lm_head")
LAYER_WEIGHTS = ("input_norm", "post_norm", "q", "k", "v", "o", "q_norm",
                 "k_norm", "router", "w_gate", "w_up", "w_down")

#: query rows a block of attention: [heads, 256, 2L] f32 scores at a time
QUERY_BLOCK = 256

#: what ``without`` may name, and what each changes
CONTROLS = {
    "causal": "plain causal attention over the 2L positions",
    "own_clean_block": "a noised block sees its own clean block too (<= for "
                       "<: the label leaks)",
    "noised_causal": "noised on noised causal inside the block",
    "positions": "positions 0 .. 2L - 1: the noised copy turns L further",
    "qk_norm": "no norm on a head's query and key",
    "weights": "the loss without its 1 / t a position",
}


def visible(rows, positions, block, without=()):
    """``[len(rows), positions]`` bool: which of the ``positions = 2L`` keys
    each of the queries ``rows`` (positions of the pass) sees."""
    half = positions // 2
    keys = jnp.arange(positions)
    if "causal" in without:
        return keys[None, :] <= rows[:, None]
    qn, kn = (rows >= half)[:, None], (keys >= half)[None, :]
    qi, kj = (rows % half)[:, None], (keys % half)[None, :]
    qb, kb = qi // block, kj // block
    on_clean = jnp.where(
        qn, kb <= qb if "own_clean_block" in without else kb < qb, kb <= qb)
    own = qn & (kb == qb)
    if "noised_causal" in without:
        own = own & (kj <= qi)
    return jnp.where(kn, own, on_clean)


def rotate(x, positions, theta):
    """Rotate-half rotary over all of ``x [B, S, heads, d]`` at
    ``positions [S]``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def attention(u, w, c, mm, without=()):
    """The attention sublayer on normed input ``u [B, 2L, hidden]``."""
    B, S, _ = u.shape
    d, kv, H = (c["head_dim"], c["num_key_value_heads"],
                c["num_attention_heads"])
    eps, block = c["rms_norm_eps"], c["assumed"]["block_length"]
    q = mm(u, w["q"]).reshape(B, S, H, d)
    k = mm(u, w["k"]).reshape(B, S, kv, d)
    v = mm(u, w["v"]).reshape(B, S, kv, d)
    if "qk_norm" not in without:
        q, k = _norm(q, w["q_norm"], eps), _norm(k, w["k_norm"], eps)
    at = jnp.arange(S)
    positions = at if "positions" in without else at % (S // 2)
    q = rotate(q, positions, c["rope_theta"])
    k = rotate(k, positions, c["rope_theta"])
    reads = jnp.arange(H) // (H // kv)
    k, v = k[:, :, reads], v[:, :, reads]                  # [B, S, H, d]
    rows_a_block = min(QUERY_BLOCK, S)
    assert S % rows_a_block == 0, (S, rows_a_block)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, rows_a_block, axis=1)
        s = mm(qb.transpose(0, 2, 1, 3),                   # [B, H, bq, d]
               k.transpose(0, 2, 3, 1)) / jnp.sqrt(float(d))
        seen = visible(lo + jnp.arange(rows_a_block), S, block, without)
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return mm(prob, v.transpose(0, 2, 1, 3))           # [B, H, bq, d]
    o = jax.lax.map(rows, jnp.arange(0, S, rows_a_block))  # [n, B, H, bq, d]
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, S, H * d)
    return mm(o, w["o"])


def router(h, w_r, c, matmul_inputs=None):
    """``(chosen [T, k], weight [T, E])``: each token's ``k`` experts by the
    softmax over ALL experts (ties to the lower index) and their weights,
    renormalised over the chosen (``norm_topk_prob``), laid out by expert."""
    k = c["num_experts_per_tok"]
    scores = jax.nn.softmax(_mm(h, w_r, matmul_inputs), -1)
    chosen = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]
    top = jnp.take_along_axis(scores, chosen, -1)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(chosen, scores.shape[1], dtype=h.dtype)
                     * top[..., None], 1)
    return chosen, weight


def expert_block(h, w, c, mm, held=None, matmul_inputs=None):
    """The sparse block on normed tokens ``h [T, hidden]``: ``(y, chosen)``.
    With ``held`` the sum is over the held experts."""
    chosen, weight = router(h, w["router"], c, matmul_inputs)
    if held is not None:
        weight = weight[:, held[0]:held[0] + held[1]]
    assert weight.shape[1] == w["w_gate"].shape[0], (
        weight.shape, w["w_gate"].shape)

    def expert(y, e):               # every held expert sees every token
        w_gate, w_up, w_down, weight_e = e
        return y + weight_e[:, None] * swiglu(h, w_gate, w_up, w_down,
                                              mm), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return y, chosen


def forward(params, c, input_ids, held=None, matmul_inputs=None, without=(),
            keep=None):
    """``(logits [B L, V] of the noised half, per layer chosen [2 B L, k],
    the attention sublayer's output [B, 2L, hidden] of layer ``keep``)``."""
    assert set(without) <= set(CONTROLS), without

    def mm(a, b):
        return _mm(a, b, matmul_inputs)

    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        B, S = input_ids.shape
        hidden, eps = c["hidden_size"], c["rms_norm_eps"]
        x = p["embed"][input_ids]
        routed, kept = [], None
        for l in range(c["num_hidden_layers"]):
            w = {k[len(f"layers.{l}."):]: v for k, v in p.items()
                 if k.startswith(f"layers.{l}.")}
            attended = attention(_norm(x, w["input_norm"], eps), w, c, mm,
                                 without)
            if l == keep:
                kept = attended
            x = x + attended
            h = _norm(x, w["post_norm"], eps).reshape(B * S, hidden)
            y, chosen = expert_block(h, w, c, mm, held, matmul_inputs)
            routed.append(chosen)
            x = x + y.reshape(B, S, hidden)
        x = _norm(x[:, S // 2:], p["norm"], eps).reshape(-1, hidden)
        return mm(x, p["lm_head"]), routed, kept


def loss_sums(params, c, input_ids, labels, weights, held=None,
              matmul_inputs=None, without=(), keep_logits=False, keep=None):
    """Sums over some sequences that chunks of a batch can add: ``ce`` (the
    sum of ``weights x`` cross-entropy over positions with a label >= 0),
    ``ce_masked`` (the same unweighted), ``n`` (all noised positions), ``m``
    (the labelled ones).  Also ``chosen``, per layer ``[2 B L, k]``; with
    ``keep_logits`` the logits ``[B L, V]``; with ``keep`` that layer's
    attention output."""
    logits, routed, kept = forward(params, c, input_ids, held, matmul_inputs,
                                   without, keep)
    flat = jnp.asarray(labels).reshape(-1)
    valid = flat >= 0
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, flat, 0)[:, None],
                              -1)[:, 0] * valid
    w = (jnp.ones_like(ce) if "weights" in without
         else jnp.asarray(weights, jnp.float32).reshape(-1))
    out = {"ce": jnp.sum(ce * w), "ce_masked": jnp.sum(ce),
           "n": jnp.asarray(flat.size, jnp.float32), "m": valid.sum(),
           "chosen": jnp.stack(routed)}
    if keep_logits:
        out["logits"] = logits
    if keep is not None:
        out["attention"] = kept
    return out


def loss_from_sums(sums):
    """``{"loss", "ce", "ce_masked"}`` from added-up ``loss_sums``."""
    ce = sums["ce"] / sums["n"]
    return {"loss": ce, "ce": ce,
            "ce_masked": sums["ce_masked"] / jnp.maximum(sums["m"], 1)}


def training_loss(params, c, input_ids, labels, weights, held=None):
    """The loss of one batch taken whole (what the tests differentiate)."""
    return loss_from_sums(loss_sums(params, c, input_ids, labels, weights,
                                    held))["loss"]
