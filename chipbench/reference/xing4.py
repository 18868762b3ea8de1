"""Plain reference of Xing4.0 (``XingChen-AGI/Xing4.0-29B-A4B``, ``model_type``
``xing4_0``) and its pretraining loss with one multi-token-prediction depth.
Straight ``jax.numpy`` in float32 at the highest matmul precision: the
hyper-connections one einsum a map, every held expert computed for every token
and masked by the router's weights, attention by blocks of query rows against
all keys; no sort, no grouped product, no kernel.  Independent of
``hetu_tpu/models``, ``hetu_tpu/layers`` and ``hetu_tpu/ops``: it takes the
weights under its own names (``WEIGHTS`` below; matrices are ``[in, out]``,
experts stacked on a leading axis) and the configuration's published keys.
The norm, the rounding, SwiGLU and the sigmoid-scored expert block are
``reference/ling3.py``'s (the same DeepSeek-V3 router: ``n_group 1`` takes its
ungrouped path; the published ``+ 1e-20`` in the renormalisation is below
f32's resolution of a sum of sigmoids and is left out).

``C`` hidden size, ``n = hc_mult``, ``eps = rms_norm_eps``, ``N(x; w) = x /
sqrt(mean(x^2) + eps) * w``::

    streams: X_0[s] = (e_s, .., e_s) in R^{n x C};  h_s = sum_i X_L[s, i];
        logits = N(h; w) W_head

    a hyper-connected sublayer with function F and its own phi, b, alpha:
        v = vec(X);  v' = v / sqrt(mean(v^2) + hc_eps)
        z = v' phi  in R^{2 n + n^2}  (columns: pre, post, res row by row)
        Hpre = sigmoid(a_0 z_pre + b_pre);  Hpost = 2 sigmoid(a_1 z_post + b_post)
        M_0 = exp(clip(a_2 mat(z_res) + b_res, mhc_h_res_clamp_min, _max))
        M_t = rows(cols(M_{t-1})), cols(M) = M / (1^T M + hc_eps),
            rows(M) = M / (M 1 + hc_eps), t = 1..hc_sinkhorn_iters;  Hres = M_T
        u = Hpre X;   X' = Hres X + Hpost^T F(N(u; w))

    layer i: X = HC(X; MLA);  X = HC(X; FFN), FFN the dense SwiGLU if i <
        first_k_dense_replace, else the expert block

    MLA (heads H; d_n = qk_nope_head_dim, d_r = qk_rope_head_dim, d_v =
        v_head_dim): c_q = N(x W_qa; w);  q = (c_q W_qb) viewed [.., H, d_n +
        d_r];  [c | k_r] = x W_kva, c = N(c; w);  (c W_kvb) viewed [.., H, d_n
        + d_v] -> k_n, v;  rotate-half RoPE on the LAST d_r of q and of [k_n |
        k_r], YaRN frequencies (rope_scaling: the dimensions the original
        context turns more than beta_fast times stay, fewer than beta_slow
        times are divided by factor, a linear ramp between), cos and sin times
        m(mscale) / m(mscale_all_dim), m(a) = 0.1 a ln(factor) + 1;  causal
        softmax of q k^T (d_n + d_r)^-1/2 m(mscale_all_dim)^2;  W_o.

    multi-token prediction (DeepSeek-V3 eq. 21-25), depth 1:
        h' = [N(Emb(t_{s+1}); w_e) | N(h_s; w_h)] W_eh;  the streams of h'
        through layer L (an expert layer, behind the stack), summed, N(.; w_1),
        the SAME head;  its labels t_{s+2}, the last position none.
    loss = mean CE(main; t_{s+1}) + mtp_loss_weight * mean CE(MTP; t_{s+2})

Departures, each on purpose:

* ``held=(first, count)``: this chip's share of an expert-parallel layer
  (``reference/ling3.py``): the routed sum runs over the held experts alone.
* The vocabulary may be a slice: ids, logits and both losses are over the rows
  of ``embed`` and ``lm_head`` that are given.
* A label below 0 is no label: the depth embeds row 0 at that position and
  the position before it has no MTP label.

``matmul_inputs`` rounds both operands of every matrix product to that type
before multiplying in f32; ``without`` changes one piece (``CONTROLS``): the
reference "at a lower precision" or "with an omission", used on the chip to
see which gaps each opens.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .ling3 import _mm, _norm, expert_block, swiglu

WEIGHTS = ("embed", "norm", "lm_head")
MTP_WEIGHTS = ("mtp.enorm", "mtp.hnorm", "mtp.norm", "mtp.eh")
LAYER_WEIGHTS = ("input_norm", "post_norm", "qa", "qa_norm", "qb", "kva",
                 "kv_norm", "kvb", "o", "attn_hc.phi", "attn_hc.b",
                 "attn_hc.alpha", "mlp_hc.phi", "mlp_hc.b", "mlp_hc.alpha")
DENSE_WEIGHTS = ("mlp_gate", "mlp_up", "mlp_down")
EXPERT_WEIGHTS = ("router", "router_bias", "w_gate", "w_up", "w_down",
                  "shared_gate", "shared_up", "shared_down")

#: what ``without`` may name: each a piece of the model got wrong
CONTROLS = ("sinkhorn_2", "clamp", "mscale", "mtp_shift")

#: query rows a block of attention
QUERY_BLOCK = 512


def decoder_layers(c):
    """Layers walked: the stack, then the MTP depth's one."""
    return c["num_hidden_layers"] + c["num_nextn_predict_layers"]


def is_dense(c, i):
    return i < min(c["first_k_dense_replace"], c["num_hidden_layers"])


def _m(p, a):
    return 0.1 * a * math.log(p["factor"]) + 1.0 if a else 1.0


def yarn_frequencies(c):
    """``(inv [d_r / 2], what cos and sin are multiplied by)``."""
    d, theta, p = c["qk_rope_head_dim"], float(c["rope_theta"]), c[
        "rope_scaling"]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if not p:
        return inv, 1.0
    assert p["type"] == "yarn", p
    original = p["original_max_position_embeddings"]

    def dimension(turns):
        return (d * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(dimension(p["beta_fast"])), 0)
    high = min(math.ceil(dimension(p["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return (inv * (1.0 - ramp) + inv / p["factor"] * ramp,
            _m(p, p.get("mscale", 1)) / _m(p, p.get("mscale_all_dim", 0)))


def softmax_scale(c, without=()):
    p = c["rope_scaling"] or {}
    scale = (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
    if p.get("mscale_all_dim") and "mscale" not in without:
        scale *= _m(p, p["mscale_all_dim"]) ** 2
    return scale


def _rope_last(x, c):
    """Rotate-half RoPE on the last ``d_r`` dimensions of ``[B, S, heads,
    d]``, positions from 0."""
    d, S = c["qk_rope_head_dim"], x.shape[1]
    inv, factor = yarn_frequencies(c)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :] * factor
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :] * factor
    rest, r = x[..., :-d], x[..., -d:]
    r = r * cos + jnp.concatenate([-r[..., d // 2:], r[..., :d // 2]],
                                  -1) * sin
    return jnp.concatenate([rest, r], -1)


def latent_attention(a, w, c, mm, without=()):
    """MLA with a low-rank query on normed input ``a [B, S, C]``."""
    B, S, _ = a.shape
    nh, eps = c["num_attention_heads"], c["rms_norm_eps"]
    dn, dr, dv, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"], c["kv_lora_rank"])
    q = mm(_norm(mm(a, w["qa"]), w["qa_norm"], eps),
           w["qb"]).reshape(B, S, nh, dn + dr)
    kva = mm(a, w["kva"])
    kvb = mm(_norm(kva[..., :r], w["kv_norm"], eps),
             w["kvb"]).reshape(B, S, nh, dn + dv)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(
        kva[..., None, r:], (B, S, nh, dr))], -1)
    v = kvb[..., dn:]
    q, k = _rope_last(q, c), _rope_last(k, c)
    scale = softmax_scale(c, without)
    pos = jnp.arange(S)
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1)
        s = mm(qb.transpose(0, 2, 1, 3), k.transpose(0, 2, 3, 1)) * scale
        seen = (lo + jnp.arange(block))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return mm(p, v.transpose(0, 2, 1, 3))              # [B, h, bq, dv]
    o = jax.lax.map(rows, jnp.arange(0, S, block))         # [n, B, h, bq, dv]
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, S, nh * dv)
    return mm(o, w["o"])


def sinkhorn(logits, c, without=()):
    """``[.., n, n]`` -> ``Hres``."""
    eps = c["hc_eps"]
    if "clamp" not in without:
        logits = jnp.clip(logits, c["mhc_h_res_clamp_min"],
                          c["mhc_h_res_clamp_max"])
    m = jnp.exp(logits)
    for _ in range(2 if "sinkhorn_2" in without else c["hc_sinkhorn_iters"]):
        m = m / (jnp.sum(m, -2, keepdims=True) + eps)      # columns
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)      # rows
    return m


def hyper_connection(X, w, c, mm, f, without=()):
    """One hyper-connected sublayer on ``X [B, S, n, C]`` with ``phi``,
    ``b``, ``alpha`` = ``w``; ``f`` maps ``u [B, S, C]`` to ``F(N(u))``:
    ``(X', Hres [B, S, n, n])``."""
    phi, b, alpha = w
    B, S, n, C = X.shape
    v = X.reshape(B, S, n * C)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + c["hc_eps"])
    z = mm(v, phi)
    pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + b[n:2 * n])
    res = sinkhorn((alpha[2] * z[..., 2 * n:] + b[2 * n:]).reshape(
        B, S, n, n), c, without)
    u = jnp.einsum("bsn,bsnc->bsc", pre, X)
    y = f(u)
    return (jnp.einsum("bsij,bsjc->bsic", res, X)
            + post[..., None] * y[:, :, None, :]), res


def decoder_layer(X, w, c, dense, mm, held=None, matmul_inputs=None,
                  without=()):
    """``(X', chosen [T, k] or None, the mixer's output, [Hres, Hres])``."""
    B, S, n, C = X.shape
    eps, kept = c["rms_norm_eps"], {}

    def attention(u):
        kept["mixer"] = latent_attention(_norm(u, w["input_norm"], eps), w,
                                         c, mm, without)
        return kept["mixer"]

    def ffn(u):
        h = _norm(u, w["post_norm"], eps).reshape(B * S, C)
        if dense:
            y = swiglu(h, w["mlp_gate"], w["mlp_up"], w["mlp_down"], mm)
        else:
            y, kept["chosen"] = expert_block(h, w, c, mm, held,
                                             matmul_inputs)
        return y.reshape(B, S, C)
    hc = lambda k: tuple(w[f"{k}.{p}"] for p in ("phi", "b", "alpha"))
    X, res_a = hyper_connection(X, hc("attn_hc"), c, mm, attention, without)
    X, res_f = hyper_connection(X, hc("mlp_hc"), c, mm, ffn, without)
    return X, kept.get("chosen"), kept["mixer"], [res_a, res_f]


def forward(params, c, input_ids, next_ids=None, held=None,
            matmul_inputs=None, without=(), keep_mixer=None):
    """``{"logits" [B S, V], "chosen" [per expert layer [T, k]], "hres" [per
    sublayer [B, S, n, n]]}``; with ``next_ids`` (the ids one position on)
    also ``"mtp_logits"``, and the depth's layer among ``chosen`` and
    ``hres``; with ``keep_mixer`` that layer's MLA output as ``"mixer"``."""
    def mm(a, b):
        return _mm(a, b, matmul_inputs)

    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        B, S = input_ids.shape
        C, n, eps = c["hidden_size"], c["hc_mult"], c["rms_norm_eps"]
        out = {"chosen": [], "hres": []}

        def walk(x, layers):
            X = jnp.broadcast_to(x[:, :, None, :], (B, S, n, C))
            for i in layers:
                w = {k[len(f"layers.{i}."):]: v for k, v in p.items()
                     if k.startswith(f"layers.{i}.")}
                X, chosen, mixer, res = decoder_layer(
                    X, w, c, is_dense(c, i), mm, held, matmul_inputs, without)
                if chosen is not None:
                    out["chosen"].append(chosen)
                out["hres"] += res
                if i == keep_mixer:
                    out["mixer"] = mixer
            return jnp.sum(X, 2)

        def head(h, w_norm):
            return mm(_norm(h, w_norm, eps).reshape(B * S, C), p["lm_head"])
        L = c["num_hidden_layers"]
        h = walk(p["embed"][input_ids], range(L))
        out["logits"] = head(h, p["norm"])
        if next_ids is not None and c["num_nextn_predict_layers"]:
            both = jnp.concatenate(
                [_norm(p["embed"][next_ids], p["mtp.enorm"], eps),
                 _norm(h, p["mtp.hnorm"], eps)], -1)
            h1 = walk(mm(both, p["mtp.eh"]), [L])
            out["mtp_logits"] = head(h1, p["mtp.norm"])
        return out


def mtp_labels(labels, without=()):
    """The depth's labels: those one position on, the last position none
    (``mtp_shift``: the main labels themselves, one shift too few)."""
    if "mtp_shift" in without:
        return labels
    return jnp.concatenate([labels[:, 1:],
                            jnp.full_like(labels[:, :1], -1)], 1)


def _ce_sums(logits, labels):
    flat = jnp.asarray(labels).reshape(-1)
    valid = flat >= 0
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, flat, 0)[:, None],
                              -1)[:, 0]
    return jnp.sum(ce * valid), valid.sum()


def loss_sums(params, c, input_ids, labels, held=None, matmul_inputs=None,
              without=(), keep_logits=False, keep_mixer=None):
    """Sums over some sequences that chunks of a batch can add: ``ce``, ``n``
    and ``mtp``, ``n_mtp`` (each cross-entropy's sum over its labelled
    positions and their count).  Also ``chosen`` ``[expert layers, T, k]`` and
    ``hres`` ``[sublayers, B, S, n, n]``, with ``keep_logits`` the main
    logits and with ``keep_mixer`` that layer's MLA output."""
    labels = jnp.asarray(labels)
    out = forward(params, c, input_ids, jnp.maximum(labels, 0), held,
                  matmul_inputs, without, keep_mixer)
    ce, n = _ce_sums(out["logits"], labels)
    sums = {"ce": ce, "n": n, "chosen": jnp.stack(out["chosen"]),
            "hres": jnp.stack(out["hres"])}
    if "mtp_logits" in out:
        sums["mtp"], sums["n_mtp"] = _ce_sums(out["mtp_logits"],
                                              mtp_labels(labels, without))
    if keep_logits:
        sums["logits"] = out["logits"]
    if keep_mixer is not None:
        sums["mixer"] = out["mixer"]
    return sums


def loss_from_sums(sums, weight):
    """``{"loss", "ce", "mtp"}`` from added-up ``loss_sums``; ``weight`` is
    the job's ``mtp_loss_weight``."""
    ce = sums["ce"] / jnp.maximum(sums["n"], 1)
    if "mtp" not in sums:
        return {"loss": ce, "ce": ce}
    mtp = sums["mtp"] / jnp.maximum(sums["n_mtp"], 1)
    return {"loss": ce + weight * mtp, "ce": ce, "mtp": mtp}


def pretraining_loss(params, c, input_ids, labels, weight, held=None):
    """The loss of one batch taken whole (what the tests differentiate)."""
    return loss_from_sums(loss_sums(params, c, input_ids, labels, held),
                          weight)["loss"]
