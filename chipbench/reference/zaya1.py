"""Plain reference of the ZAYA1 decoder (``Zyphra/ZAYA1-8B``) and its
pretraining loss.  Straight ``jax.numpy`` in float32 at the highest matmul
precision: the convolutions as shifted sums, attention by blocks of query rows
against all keys under an explicit causal mask, every held expert computed for
every token and masked by the router's weight; no kernel, no sort, no grouped
product, no recomputation.  Independent of ``hetu_tpu/models``,
``hetu_tpu/layers`` and ``hetu_tpu/ops``: it takes the weights under its own
names (``WEIGHTS`` below; matrices are ``[in, out]``, experts stacked on a
leading axis) and the configuration's published keys, and nothing else (the
norm, the rounding, the product and SwiGLU are ``reference/ling3.py``'s, as
the Xing4.0 reference takes them).

``C`` hidden, ``H`` query heads on ``J`` key heads of ``d``, ``g = H / J``,
``E`` experts, ``R = router_hidden_size``, ``eps = rms_norm_eps``, ``N(x; w) =
x / sqrt(mean(x^2) + eps) * w``, ``x^-_t = x_(t-1)`` with zeros before
position 0::

    sublayer:  x' = s_r (x + b_r) + s_f (F(N(x; w)) + b_f)
    CCA on u:  q~ = u W_q, k~ = u W_k, v = [u W_v1 | u^- W_v2]
               m^q_h = (q~_h + k~_(h div g)) / 2;  m^k_j = mean_g m^q
               z = [q~ | k~];  z'_t = a_0 z_(t-1) + a_1 z_t + b
               z''_t[h] = z'_(t-1)[h] A_0^h + z'_t[h] A_1^h + b'[h]
               q = z''_q + m^q;  k = z''_k + m^k
               q^_h = sqrt(d) q_h / |q_h|;  k^_j = exp(t_j) sqrt(d) k_j / |k_j|
               rotary (half-split pairs) on the first d * partial_rotary_factor
               lanes of a head, base rope_parameters.hybrid.rope_theta
               o_h = softmax_causal(q^_h k^_(h div g)^T / sqrt(d)) v_(h div g)
               y = [o_0 .. o_(H-1)] W_o
    experts on u, layer l:
               r_l = u W_d + b_d  (+ gamma_l r_(l-1) for l >= 1)
               p = softmax(W_3 gelu(W_2 gelu(W_1 N(r_l; w_n) + b_1) + b_2))
               e = argmax(p + beta) over E + 1 choices (ties to the lower);
               w = p_e;  y = w SwiGLU_e(u) if e < E, else 0
    head:      N(x_L; w) Emb^T (tied), mean cross-entropy on the next id

Departures from the published description, each on purpose:

* ``held=(first, count)``: this chip's share of an expert-parallel layer.  The
  expert weights given are those of experts ``first .. first + count - 1`` and
  a token whose choice is another expert gets nothing from this share, as the
  program leaves it out; the router, the softmax and the choice are over all
  ``E + 1``.  ``held=None`` is the whole layer.
* The vocabulary may be a slice: ids, logits and the loss are over the rows of
  ``embed`` that are given.

``matmul_inputs`` (default None: plain f32) rounds both operands of every
matrix product to that type before multiplying in f32; ``without`` changes one
piece (``CONTROLS``): the reference "at a lower precision" or "with a piece
changed", used on the chip to see which gaps each opens (the traffic file's
limits lie below them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .ling3 import _mm, _norm, _round, swiglu

#: the weights ``forward`` reads: the model's, then per layer ``layers.<i>.``
WEIGHTS = ("embed", "norm")
MERGE = ("s_r", "b_r", "s_f", "b_f")
LAYER_WEIGHTS = (
    ("input_norm", "post_norm", "qk", "v", "o", "taps", "tap_bias", "mix",
     "mix_bias", "temp", "w_gate", "w_up", "w_down")
    + tuple(f"{m}.{n}" for m in ("attn_merge", "mlp_merge") for n in MERGE)
    + tuple(f"router.{n}" for n in ("down", "down_bias", "gamma", "norm",
                                    "w1", "b1", "w2", "b2", "w3", "bias")))

#: query rows a block of attention: [heads, 256, S] f32 scores at a time
QUERY_BLOCK = 256

#: what ``without`` may name, and what each changes
CONTROLS = {
    "taps_in_time": "the depthwise taps swapped in time (a_0 on the current "
                    "position, a_1 on the previous)",
    "head_mix": "the head-mixing taps left out (z'' = z')",
    "qk_mean": "the q-k mean left out",
    "value_shift": "W_v2 fed the current token",
    "temperature": "the keys' temperature left out",
    "rotary_all": "rotary over all of a head's dimensions",
    "eda": "the router state of the layer above left out",
    "skip_choice": "a token that chose no expert sent to the share's first "
                   "expert",
    "residual_scale": "the residual's s_r and b_r left out",
}


def _before(x):
    """``x^-``: ``x [B, S, ..]`` one position on, zeros at position 0."""
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], 1)


def rotate(x, turned, theta):
    """Half-split rotary on the first ``turned`` dimensions of ``x [B, S,
    heads, d]``, positions from 0; the rest pass through."""
    S = x.shape[1]
    inv = theta ** (-2.0 * jnp.arange(turned // 2, dtype=jnp.float32)
                    / turned)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    t, rest = x[..., :turned], x[..., turned:]
    t1, t2 = t[..., : turned // 2], t[..., turned // 2:]
    t = t * jnp.cos(ang) + jnp.concatenate([-t2, t1], -1) * jnp.sin(ang)
    return jnp.concatenate([t, rest], -1)


def cca_qk(u, w, c, mm, without=()):
    """``(q^ [B, S, H, d], k^ [B, S, J, d])`` behind the mixing, before the
    rotary."""
    B, S, _ = u.shape
    H, J, d = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    g = H // J
    z = mm(u, w["qk"])                                       # [B, S, (H+J) d]
    a_old, a_new = ((w["taps"][1], w["taps"][0]) if "taps_in_time" in without
                    else (w["taps"][0], w["taps"][1]))
    z1 = a_old * _before(z) + a_new * z + w["tap_bias"]
    z1 = z1.reshape(B, S, H + J, d)
    if "head_mix" in without:
        z2 = z1
    else:
        def taps(x, a):
            if mm.dtype is not None:
                x, a = _round(x, mm.dtype), _round(a, mm.dtype)
            return jnp.einsum("bsnd,nde->bsne", x, a)
        z2 = (taps(_before(z1), w["mix"][0]) + taps(z1, w["mix"][1])
              + w["mix_bias"].reshape(H + J, d))
    q, k = z2[:, :, :H], z2[:, :, H:]
    if "qk_mean" not in without:
        zq = z[..., :H * d].reshape(B, S, J, g, d)
        zk = z[..., H * d:].reshape(B, S, J, 1, d)
        mq = (zq + zk) / 2
        q = q + mq.reshape(B, S, H, d)
        k = k + mq.mean(3)

    def unit(x):
        return jnp.sqrt(float(d)) * x / jnp.linalg.norm(x, axis=-1,
                                                        keepdims=True)
    q, k = unit(q), unit(k)
    if "temperature" not in without:
        k = k * jnp.exp(w["temp"])[:, None]
    return q, k


def cca(u, w, c, mm, without=()):
    """``(y [B, S, C], q^, k^)``: the attention sublayer on normed ``u``."""
    B, S, _ = u.shape
    H, J, d = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    half = J * d // 2
    q, k = cca_qk(u, w, c, mm, without)
    shifted = u if "value_shift" in without else _before(u)
    v = jnp.concatenate([mm(u, w["v"][:, :half]),
                         mm(shifted, w["v"][:, half:])], -1)
    v = v.reshape(B, S, J, d)
    rope = c["rope_parameters"]["hybrid"]
    turned = d if "rotary_all" in without else int(
        d * rope.get("partial_rotary_factor", c["partial_rotary_factor"]))
    qr, kr = (rotate(x, turned, float(rope["rope_theta"])) for x in (q, k))
    reads = jnp.arange(H) // (H // J)
    kr, v = kr[:, :, reads], v[:, :, reads]                   # [B, S, H, d]
    pos = jnp.arange(S)
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(qr, lo, block, axis=1)
        s = mm(qb.transpose(0, 2, 1, 3),                     # [B, H, bq, d]
               kr.transpose(0, 2, 3, 1)) / jnp.sqrt(float(d))
        seen = (lo + jnp.arange(block))[:, None] >= pos[None, :]
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return mm(prob, v.transpose(0, 2, 1, 3))             # [B, H, bq, d]
    o = jax.lax.map(rows, jnp.arange(0, S, block))           # [n, B, H, bq, d]
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, S, H * d)
    return mm(o, w["o"]), q, k


def router(u, w, prev, c, mm, without=()):
    """``(chosen [T], weight [T, E + 1], state [B, S, R])``: the state with
    the layer above's added, each token's ONE choice of ``E + 1`` by ``p +
    beta`` (ties to the lower index) and its ``p`` laid out by choice."""
    eps = c["rms_norm_eps"]
    r = mm(u, w["router.down"]) + w["router.down_bias"]
    if prev is not None and "eda" not in without:
        r = r + w["router.gamma"] * prev
    h = _norm(r, w["router.norm"], eps)
    h = jax.nn.gelu(mm(h, w["router.w1"]) + w["router.b1"], approximate=False)
    h = jax.nn.gelu(mm(h, w["router.w2"]) + w["router.b2"], approximate=False)
    p = jax.nn.softmax(mm(h, w["router.w3"]), -1)
    p = p.reshape(-1, p.shape[-1])
    chosen = jnp.argmax(p + w["router.bias"], -1)
    weight = jax.nn.one_hot(chosen, p.shape[-1], dtype=p.dtype) * p
    return chosen, weight, r


def experts(u, w, prev, c, mm, held=None, without=()):
    """``(y [B, S, C], chosen [T], state)``: the expert sublayer on normed
    ``u``; with ``held`` the held experts' part alone."""
    chosen, weight, state = router(u, w, prev, c, mm, without)
    E = weight.shape[1] - 1                     # the router's last is no expert
    if "skip_choice" in without:
        weight = weight.at[:, held[0] if held else 0].add(weight[:, E])
    weight = weight[:, :E]                      # the last choice computes nothing
    if held is not None:
        weight = weight[:, held[0]:held[0] + held[1]]
    h = u.reshape(-1, u.shape[-1])

    def expert(y, e):               # every held expert sees every token
        w_gate, w_up, w_down, weight_e = e
        return y + weight_e[:, None] * swiglu(h, w_gate, w_up, w_down,
                                              mm), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return y.reshape(u.shape), chosen, state


def merge(x, y, w, which, without=()):
    s_r, b_r, s_f, b_f = (w[f"{which}.{n}"] for n in MERGE)
    if "residual_scale" in without:
        return x + s_f * (y + b_f)
    return s_r * (x + b_r) + s_f * (y + b_f)


class _Products:
    """``mm(a, b)``: a matrix product, both operands rounded to ``dtype``
    first where one is given."""

    def __init__(self, dtype):
        self.dtype = dtype

    def __call__(self, a, b):
        return _mm(a, b, self.dtype)


def forward(params, c, input_ids, held=None, matmul_inputs=None, without=(),
            keep=None):
    """``(logits [B S, V], chosen [layers, T], kept)``; with ``keep`` (a
    layer's index) ``kept`` holds what the comparison looks at beside the
    logits: ``attention``, layer ``keep``'s CCA output ``[B, S, C]``, ``qk``
    its ``[q^ | k^] [B, S, (H + J) d]``, ``experts`` its expert sublayer's
    output before the merge; and always ``state``, the LAST
    layer's router state ``[B, S, R]``, and ``skipped``, the (token, layer)
    pairs that chose no expert."""
    assert set(without) <= set(CONTROLS), without
    mm = _Products(matmul_inputs)
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        B, S = input_ids.shape
        eps = c["rms_norm_eps"]
        x = p["embed"][input_ids]
        routed, kept, state = [], {"skipped": 0}, None
        for l in range(c["num_hidden_layers"]):
            w = {k[len(f"layers.{l}."):]: v for k, v in p.items()
                 if k.startswith(f"layers.{l}.")}
            y, q, k = cca(_norm(x, w["input_norm"], eps), w, c, mm, without)
            if l == keep:
                kept["attention"] = y
                kept["qk"] = jnp.concatenate(
                    [q.reshape(B, S, -1), k.reshape(B, S, -1)], -1)
            x = merge(x, y, w, "attn_merge", without)
            y, chosen, state = experts(_norm(x, w["post_norm"], eps), w,
                                       state, c, mm, held, without)
            if l == keep:
                kept["experts"] = y
            routed.append(chosen)
            kept["skipped"] += jnp.sum(
                chosen == w["router.w3"].shape[1] - 1)
            x = merge(x, y, w, "mlp_merge", without)
        kept["state"] = state
        x = _norm(x, p["norm"], eps).reshape(B * S, -1)
        return mm(x, p["embed"].T), jnp.stack(routed), kept


def loss_sums(params, c, input_ids, labels, held=None, matmul_inputs=None,
              without=(), keep_logits=False, keep=None):
    """Sums over some sequences that chunks of a batch can add: ``ce`` (sum
    of the cross-entropy over positions with a label >= 0), ``n`` (their
    count), ``skipped`` (the (token, layer) pairs that chose no expert).  Also
    ``chosen [layers, T]`` and ``state`` for the comparison, with
    ``keep_logits`` the logits ``[B S, V]`` and with ``keep`` that layer's
    ``attention``, ``qk`` and ``experts``."""
    logits, chosen, kept = forward(params, c, input_ids, held, matmul_inputs,
                                   without, keep)
    flat = jnp.asarray(labels).reshape(-1)
    valid = flat >= 0
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, flat, 0)[:, None],
                              -1)[:, 0]
    out = dict(kept, ce=jnp.sum(ce * valid), n=valid.sum(), chosen=chosen)
    if keep_logits:
        out["logits"] = logits
    return out


def loss_from_sums(sums):
    """``{"loss", "ce"}`` from added-up ``loss_sums``."""
    ce = sums["ce"] / jnp.maximum(sums["n"], 1)
    return {"loss": ce, "ce": ce}


def pretraining_loss(params, c, input_ids, labels, held=None):
    """The loss of one batch taken whole (what the tests differentiate)."""
    return loss_from_sums(loss_sums(params, c, input_ids, labels,
                                    held))["loss"]
