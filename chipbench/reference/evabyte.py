"""Plain reference of the EvaByte decoder (``EvaByte/EvaByte``) and its
multi-byte pretraining loss.  Straight ``jax.numpy`` in float32 at the highest
matmul precision: attention by blocks of query rows against ALL keys and ALL
chunk summaries side by side under an explicit ``[rows, S + S / c]`` mask, no
kernel, no walk over tiles, no recomputation.  Written from the equations
(arXiv:2302.04542, the EVA estimator with one normaliser, and the published
configuration's keys); imports nothing of ``hetu_tpu``.  It takes the weights
under its own names (``WEIGHTS``; matrices are ``[in, out]``) and the
configuration's published keys, and nothing else.

``C`` hidden, ``H`` heads of ``d``, window ``W``, chunk ``c``, ``s = d^-1/2``::

    N(x; g) = x / sqrt(mean(x^2) + eps) * (1 + g)     (norm_add_unit_offset)
    layer:  a = x + EVA(N(x; g_in));  y = a + W_down (silu(W_gate u) * W_up u),
            u = N(a; g_post); no bias anywhere
    q, k, v = u W_q, u W_k, u W_v as H heads of d; q_t and k_t rotated over all
        d channels at position t, base rope_theta, rotate-half
    chunk j = positions c j .. c j + c - 1, head h with phi_h, mu_h in R^d:
        alpha_m = softmax over the chunk's m of (s phi_h . k_m)
        v^_j = sum_m alpha_m v_m        k^_j = (1 / c) sum_m k_m + mu_h
    query t, w = floor(t / W): E = {m : W w <= m <= t}, R = {j : j < (W / c) w}
        o_t = [sum_E exp(s q_t . k_m) v_m + sum_R exp(s q_t . k^_j) v^_j]
              / [sum_E exp(s q_t . k_m) + sum_R exp(s q_t . k^_j)]
        EVA = concat_h(o) W_o
    z = N(x; g_f);  logits_i = z W_i, i = 0 .. P - 1 (W_i the columns [V i,
        V (i + 1)) of lm_head);  head i at position t is labelled with byte
        t + 1 + i;  loss = (1 / P) sum_i CE_i over labelled positions

``matmul_inputs`` (default None: plain f32) rounds both operands of every
matrix product (the summaries' ``phi . k`` and ``alpha v`` among them) to that
type before multiplying in f32; ``without`` changes one piece (``CONTROLS``):
the reference "at a lower precision" or "with a piece changed", used to see
which gaps each would open (the traffic file's limits lie below them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the weights ``forward`` reads: the model's, then per layer ``layers.<i>.``
WEIGHTS = ("embed", "norm", "lm_head")
LAYER_WEIGHTS = ("input_norm", "post_norm", "q", "k", "v", "o", "phi", "mu",
                 "mlp_gate", "mlp_up", "mlp_down")

#: query rows a block of attention: [heads, 256, S + S / c] f32 scores
QUERY_BLOCK = 256

#: what ``without`` may name, and what each changes
CONTROLS = {
    "remote": "the remote term left out: a query sees its own window alone",
    "sliding": "a sliding window: the last W keys exactly, the chunks that "
               "end before them through their summaries",
    "window_edge": "a window one key off: the local set starts at W w + 1",
    "mu": "mu ignored: a chunk's key is the mean of its keys",
    "phi": "phi ignored: a chunk's value is the mean of its values",
    "second_rotation": "each summary key rotated once more, at its chunk's "
                       "first position",
    "summaries_bf16": "the summaries' scores, softmax and sums in bf16",
}


def _norm(x, g, c):
    offset = 1.0 if c.get("norm_add_unit_offset", True) else 0.0
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + c["rms_norm_eps"]) * (offset + g)


def _round(x, dtype):
    """``x`` rounded to ``dtype`` and back to f32 (``reduce_precision`` for
    bf16: XLA may drop a pair of ``astype``)."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    if info.nexp == jnp.finfo(jnp.float32).nexp:
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                        mantissa_bits=info.nmant)
    return x.astype(dtype).astype(jnp.float32)


def _mm(a, b, dtype=None):
    return _round(a, dtype) @ _round(b, dtype)


def rotary_tables(seq_len, d, theta):
    """``(cos, sin) [S, d]``, rotate-half: channel ``i`` and ``i + d / 2``
    turn together by ``t theta^(-2 i / d)``."""
    inv = float(theta) ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, cos, sin):
    """``x [S, H, d]`` at the positions of ``cos``, ``sin [S, d]``."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def summaries(k, v, phi, mu, chunk, dtype=None, without=()):
    """``(k^, v^) [S / c, H, d]`` of the rotated keys and the values ``[S, H,
    d]`` under ``phi``, ``mu [H, d]``."""
    s, h, d = k.shape
    n = s // chunk
    kc, vc = (x[:n * chunk].reshape(n, chunk, h, d) for x in (k, v))
    low = jnp.bfloat16 if "summaries_bf16" in without else None
    kc_, vc_, phi_ = (_round(_round(x, dtype), low) for x in (kc, vc, phi))
    scores = _round(jnp.einsum("nchd,hd->nch", kc_, phi_) * d ** -0.5, low)
    if "phi" in without:
        scores = jnp.zeros_like(scores)
    alpha = _round(jax.nn.softmax(scores, axis=1), low)
    vs = _round(jnp.einsum("nch,nchd->nhd", _round(alpha, dtype), vc_), low)
    ks = _round(jnp.mean(_round(kc, low), axis=1), low)
    if "mu" not in without:
        ks = _round(ks + mu, low)
    return ks, vs


def seen(rows, seq, window, chunk, without=()):
    """``[len(rows), S + S / c]`` bool: query row on key, then on summary."""
    t = rows[:, None]
    m, j = jnp.arange(seq)[None, :], jnp.arange(seq // chunk)[None, :]
    if "sliding" in without:
        first = jnp.maximum(t - window + 1, 0)
        local, remote = (m >= first) & (m <= t), (j + 1) * chunk <= first
    else:
        first = t // window * window
        start = (jnp.minimum(first + 1, t) if "window_edge" in without
                 else first)
        local, remote = (m >= start) & (m <= t), j * chunk < first
    if "remote" in without:
        remote = jnp.zeros_like(remote)
    return jnp.concatenate([local, remote], axis=1)


def eva(q, k, v, phi, mu, c, dtype=None, without=(), parts=False):
    """``o [S, H d]`` of rotated ``q``, ``k`` and ``v [S, H, d]``; with
    ``parts`` also ``{"local": ..., "summaries": ...}``: the output of the
    local set alone (its own softmax) and ``[k^ | v^] [S / c, 2 H d]``."""
    s, h, d = q.shape
    window, chunk = c["window_size"], c["chunk_size"]
    ks, vs = summaries(k, v, phi, mu, chunk, dtype, without)
    if "second_rotation" in without:
        cos, sin = rotary_tables(s, d, c["rope_theta"])
        ks = _rotate(ks, cos[::chunk][:len(ks)], sin[::chunk][:len(ks)])
    keys = jnp.concatenate([k, ks], 0)
    values = jnp.concatenate([v, vs], 0)
    out, local = [], []
    for lo in range(0, s, QUERY_BLOCK):
        rows = jnp.arange(lo, min(lo + QUERY_BLOCK, s))
        scores = jnp.einsum("qhd,khd->hqk", _round(q[lo:lo + QUERY_BLOCK],
                                                   dtype),
                            _round(keys, dtype)) * d ** -0.5
        mask = seen(rows, s, window, chunk, without)

        def attend(mask):
            p = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", _round(p, dtype),
                              _round(values, dtype)).reshape(len(rows), -1)
        out.append(attend(mask))
        if parts:
            local.append(attend(mask.at[:, s:].set(False)))
    out = jnp.concatenate(out, 0)
    if not parts:
        return out
    return out, {"local": jnp.concatenate(local, 0),
                 "summaries": jnp.concatenate(
                     [ks.reshape(len(ks), -1), vs.reshape(len(vs), -1)], -1)}


def layer(p, i, c, x, cos, sin, dtype=None, without=(), keep=False):
    """Decoder layer ``i`` on ``x [S, C]``; with ``keep`` also what ``eva``'s
    ``parts`` hold and ``"eva"``, the attention's own output before ``W_o``."""
    w = lambda name: p[f"layers.{i}.{name}"]
    h = c["num_attention_heads"]
    u = _norm(x, w("input_norm"), c)
    q, k, v = (_mm(u, w(n), dtype).reshape(len(x), h, -1) for n in "qkv")
    q, k = _rotate(q, cos, sin), _rotate(k, cos, sin)
    o = eva(q, k, v, w("phi"), w("mu"), c, dtype, without, parts=keep)
    kept = {}
    if keep:
        o, kept = o
        kept["eva"] = o
    a = x + _mm(o, w("o"), dtype)
    u = _norm(a, w("post_norm"), c)
    y = a + _mm(jax.nn.silu(_mm(u, w("mlp_gate"), dtype))
                * _mm(u, w("mlp_up"), dtype), w("mlp_down"), dtype)
    return y, kept


def forward(p, c, ids, matmul_inputs=None, without=(), keep_layer=None):
    """``(logits [B, S, P, V], kept)`` of ``ids [B, S]``; ``kept``: layer
    ``keep_layer``'s ``eva``, ``local`` ``[B, S, H d]`` and ``summaries``."""
    d = c["hidden_size"] // c["num_attention_heads"]
    cos, sin = rotary_tables(ids.shape[1], d, c["rope_theta"])
    logits, kept = [], {}
    for row in ids:
        x = p["embed"][row]
        for i in range(c["num_hidden_layers"]):
            x, some = layer(p, i, c, x, cos, sin, matmul_inputs, without,
                            keep=i == keep_layer)
            for name, value in some.items():
                kept.setdefault(name, []).append(value)
        z = _norm(x, p["norm"], c)
        logits.append(_mm(z, p["lm_head"], matmul_inputs).reshape(
            len(row), c["num_pred_heads"], c["vocab_size"]))
    return jnp.stack(logits), {k: jnp.stack(v) for k, v in kept.items()}


def loss_sums(p, c, ids, labels, keep_logits=False, **how):
    """``{"ce": [P], "n": [P]}``: each head's summed cross-entropy over its
    labelled positions (``labels [B, S, P] >= 0``) and their count, beside
    what ``forward`` kept and, with ``keep_logits``, ``logits [B S P, V]``."""
    logits, kept = forward(p, c, ids, **how)
    logp = jax.nn.log_softmax(logits, axis=-1)
    valid = labels >= 0
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None],
                                 axis=-1)[..., 0]
    out = dict(kept, ce=-jnp.sum(jnp.where(valid, picked, 0.0), axis=(0, 1)),
               n=jnp.sum(valid, axis=(0, 1)).astype(jnp.float32))
    if keep_logits:
        out["logits"] = logits.reshape(-1, logits.shape[-1])
    return out


def loss_from_sums(tot):
    """``{"loss", "ce", "ce_head<i>"}``: the heads' means and their mean."""
    heads = tot["ce"] / jnp.maximum(tot["n"], 1.0)
    out = {f"ce_head{i}": heads[i] for i in range(len(heads))}
    out["loss"] = out["ce"] = jnp.mean(heads)
    return out


def loss(p, c, ids, labels, **how):
    return loss_from_sums(loss_sums(p, c, ids, labels, **how))["loss"]
