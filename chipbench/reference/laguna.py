"""Plain reference of the Laguna decoder (``poolside/Laguna-XS.2``) and its
pretraining loss.  Straight ``jax.numpy`` in float32 at the highest matmul
precision: attention by blocks of query rows against all keys under an
explicit mask built from ``0 <= i - j < w``, every held expert computed for
every token and masked by the router's weights; no kernel, no sort, no
grouped product, no recomputation.  Independent of ``hetu_tpu/models``,
``hetu_tpu/layers`` and ``hetu_tpu/ops`` (it computes YaRN's table itself):
it takes the weights under its own names (``WEIGHTS`` below; matrices are
``[in, out]``, experts stacked on a leading axis) and the configuration's
published keys, and nothing else.

``eps`` ``rms_norm_eps``, ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``::

    layer l: a = x + Attn_l(N(x; w_in));  y = a + F_l(N(a; w_post))
    final N, untied head, no bias anywhere

    Attn_l: H_l = num_attention_heads_per_layer[l] query heads on
        num_key_value_heads key heads of d = head_dim; q = u W_q, k = u W_k,
        v = u W_v; query head h reads key head h // (H_l / KV); rotary on q
        and k (below); scores / sqrt(d), position i sees j with 0 <= i - j <
        w_l, w_l = sliding_window where layer_types[l] is sliding_attention
        and unbounded where full_attention; out = W_o [g_h ctx_h]_h with
        g = sigmoid(u W_g), one number a head (gating).
    rotary: the first r = d * partial_rotary_factor dimensions of a head,
        half-split pairs, inv_i = b^(-2i/r); with rope_type "yarn":
        corr(n) = r ln(L0 / (2 pi n)) / (2 ln b), low = max(floor(corr(
        beta_fast)), 0), high = min(ceil(corr(beta_slow)), r - 1), ramp_i =
        clip((i - low) / (high - low), 0, 1), inv_i = (1 - ramp_i) b^(-2i/r)
        + ramp_i b^(-2i/r) / factor, and cos, sin times attention_factor.
    F_l: the dense SwiGLU where mlp_layer_types[l] is dense, else the expert
        block: s = sigmoid(u W_r) over ALL routed experts; the
        num_experts_per_tok largest (ties to the lower index); weights
        moe_routed_scaling_factor * s_e / sum_chosen s; E(x) = W_d (silu(W_g
        x) * W_u x); y = sum w_e E_e(x) + E_shared(x).
    loss: mean cross-entropy over labelled positions.

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

import math

import jax
import jax.numpy as jnp

#: the weights ``forward`` reads: the model's, then per layer ``layers.<i>.``
#: the norms and those of the layer's attention and FFN
WEIGHTS = ("embed", "norm", "lm_head")
LAYER_WEIGHTS = ("input_norm", "post_norm", "q", "k", "v", "o", "gate")
DENSE_WEIGHTS = ("mlp_gate", "mlp_up", "mlp_down")
EXPERT_WEIGHTS = ("router", "w_gate", "w_up", "w_down", "shared_gate",
                  "shared_up", "shared_down")

#: query rows a block of attention: [heads, 256, S] f32 scores at a time
QUERY_BLOCK = 256

#: what ``without`` may name, and what each changes
CONTROLS = {
    "window": "window layers see every earlier key",
    "window_511": "the window is 511 keys",
    "window_513": "the window is 513 keys",
    "yarn": "full layers turn by plain frequencies at their base (no blend, "
            "no attention factor)",
    "attention_factor": "YaRN's blend without its factor on cos and sin",
    "partial_full": "full layers turn all of a head's dimensions",
    "partial_window": "window layers turn half of a head's dimensions",
    "grouping": "a window layer's query head h reads key head h // (the FULL "
                "layers' heads / key heads), 48 / 8 (the last heads the last "
                "key head)",
    "head_gate": "no gate on the heads' contexts",
    "scaling_factor": "the experts' weights without moe_routed_scaling_factor",
    "norm_topk": "the chosen experts' scores not normalised",
    "shared_expert": "no shared expert",
    "all_experts": "every routed pair computed, not the held experts' alone: "
                   "an absent expert e stands in with the weights of held "
                   "expert e mod the count held",
}


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _round(x, dtype):
    """``x`` rounded to ``dtype`` and back to f32 (``reduce_precision`` for
    bf16: XLA may drop a pair of ``astype``)."""
    info = jnp.finfo(dtype)
    if info.nexp == jnp.finfo(jnp.float32).nexp:
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                        mantissa_bits=info.nmant)
    return x.astype(dtype).astype(jnp.float32)


def _mm(a, b, dtype=None):
    if dtype is not None:
        a, b = _round(a, dtype), _round(b, dtype)
    return a @ b


def rotary_tables(seq_len, turned, p, without=()):
    """``(cos, sin) [S, turned]`` of one ``rope_parameters`` group over the
    ``turned`` dimensions of a head that turn."""
    b = float(p["rope_theta"])
    i = jnp.arange(turned // 2, dtype=jnp.float32)
    inv = b ** (-2.0 * i / turned)
    factor = 1.0
    if p.get("rope_type", "default") == "yarn" and "yarn" not in without:
        def corr(turns):
            return (turned * math.log(p["original_max_position_embeddings"]
                                      / (turns * 2 * math.pi))
                    / (2 * math.log(b)))
        low = max(math.floor(corr(p["beta_fast"])), 0)
        high = min(math.ceil(corr(p["beta_slow"])), turned - 1)
        ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
        inv = (1.0 - ramp) * inv + ramp * inv / p["factor"]
        if "attention_factor" not in without:
            factor = p.get("attention_factor",
                           0.1 * math.log(p["factor"]) + 1.0)
    ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def rotate(x, cos, sin):
    """Half-split rotary on the first ``cos.shape[-1]`` dimensions of ``x [B,
    S, heads, d]``, positions from 0; the rest pass through."""
    r = cos.shape[-1]
    t, rest = x[..., :r], x[..., r:]
    t1, t2 = t[..., : r // 2], t[..., r // 2:]
    t = (t * cos[:, None, :]
         + jnp.concatenate([-t2, t1], -1) * sin[:, None, :])
    return jnp.concatenate([t, rest], -1)


def attention(u, w, c, l, mm, without=(), widen=0):
    """The attention sublayer of layer ``l`` on normed input ``u [B, S,
    hidden]``; ``widen`` more keys (fewer, if negative) in a window layer's
    window."""
    B, S, _ = u.shape
    d, kv = c["head_dim"], c["num_key_value_heads"]
    H = c["num_attention_heads_per_layer"][l]
    kind = c["layer_types"][l]
    windowed = kind == "sliding_attention"
    q = mm(u, w["q"]).reshape(B, S, H, d)
    k = mm(u, w["k"]).reshape(B, S, kv, d)
    v = mm(u, w["v"]).reshape(B, S, kv, d)
    p = c["rope_parameters"][kind]
    share = p.get("partial_rotary_factor", 1)
    if "partial_full" in without and not windowed:
        share = 1
    if "partial_window" in without and windowed:
        share = 0.5
    cos, sin = rotary_tables(S, int(d * share), p, without)
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    # query head h reads key head h // (H / kv)
    group = H // kv
    if "grouping" in without and windowed:
        group = next(h for h, t in zip(c["num_attention_heads_per_layer"],
                                       c["layer_types"])
                     if t == "full_attention") // kv
    reads = jnp.minimum(jnp.arange(H) // group, kv - 1)
    k, v = k[:, :, reads], v[:, :, reads]                  # [B, S, H, d]
    window = None
    if windowed and "window" not in without:
        # HF's convention: the window counts the query's own position
        window = c["sliding_window"] + widen + (
            -1 if "window_511" in without else
            1 if "window_513" in without else 0)
    pos = jnp.arange(S)
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1)
        s = mm(qb.transpose(0, 2, 1, 3),                   # [B, H, bq, d]
               k.transpose(0, 2, 3, 1)) / jnp.sqrt(float(d))
        gap = (lo + jnp.arange(block))[:, None] - pos[None, :]     # i - j
        seen = gap >= 0
        if window is not None:
            seen = seen & (gap < window)
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return mm(prob, v.transpose(0, 2, 1, 3))           # [B, H, bq, d]
    o = jax.lax.map(rows, jnp.arange(0, S, block))         # [n, B, H, bq, d]
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, S, H, d)
    if c["gating"] and "head_gate" not in without:
        # one number a head and a token (not a head's 128: the published
        # 33.4 G parameters leave no room for an elementwise gate)
        o = o * jax.nn.sigmoid(mm(u, w["gate"]))[..., None]
    return mm(o.reshape(B, S, H * d), w["o"])


def swiglu(h, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def router(h, w_r, c, matmul_inputs=None, without=()):
    """``(chosen [T, k], weight [T, E])``: each token's ``k`` experts by the
    sigmoid scores over ALL experts (ties to the lower index) and their
    scores normalised over the chosen and scaled, laid out by expert."""
    k = c["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_mm(h, w_r, matmul_inputs))
    chosen = jnp.argsort(-scores, axis=-1, stable=True)[:, :k]
    top = jnp.take_along_axis(scores, chosen, -1)
    if "norm_topk" not in without:
        top = top / jnp.sum(top, -1, keepdims=True)
    if "scaling_factor" not in without:
        top = top * c["moe_routed_scaling_factor"]
    weight = jnp.sum(jax.nn.one_hot(chosen, scores.shape[1], dtype=h.dtype)
                     * top[..., None], 1)
    return chosen, weight


def expert_block(h, w, c, mm, held=None, matmul_inputs=None, without=(),
                 shared=True):
    """The sparse block on normed tokens ``h [T, hidden]``: ``(y, chosen,
    routed)``, ``routed`` the routed experts' weighted sum before the shared
    expert is added.  With ``held`` the routed sum is over the held experts;
    ``shared=False`` leaves the shared expert out (a share that is not the
    one to count it)."""
    chosen, weight = router(h, w["router"], c, matmul_inputs, without)
    if held is not None and "all_experts" in without:
        weight = weight.reshape(weight.shape[0], -1, held[1]).sum(1)
    elif held is not None:
        weight = weight[:, held[0]:held[0] + held[1]]
    assert weight.shape[1] == w["w_gate"].shape[0], (
        weight.shape, w["w_gate"].shape)

    def expert(y, e):               # every held expert sees every token
        w_gate, w_up, w_down, weight_e = e
        return y + weight_e[:, None] * swiglu(h, w_gate, w_up, w_down,
                                              mm), None
    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        w["w_gate"], w["w_up"], w["w_down"], weight.T))
    y = routed
    if shared and "shared_expert" not in without:
        # added as it is: the row names no gate on the shared expert
        y = y + swiglu(h, w["shared_gate"], w["shared_up"],
                       w["shared_down"], mm)
    return y, chosen, routed


def forward(params, c, input_ids, held=None, matmul_inputs=None, without=(),
            keep_attention=(), layers=None, keep_edges=False):
    """``(logits [B S, V], per expert layer chosen [T, k], kept)``; ``kept``
    holds what the comparison looks at beside the logits: ``attention``, the
    attention sublayers' outputs ``[B, S, hidden]`` of the layers
    ``keep_attention`` names; ``routed``, the first expert layer's routed sum
    ``[T, hidden]`` without its shared expert; and with ``keep_edges``
    ``edges``, the first of those layers' output again with one key fewer and
    one key more in its window.  ``layers`` walks the first so many layers
    alone: there are no logits then (None)."""
    assert set(without) <= set(CONTROLS), without

    def mm(a, b):
        return _mm(a, b, matmul_inputs)

    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        B, S = input_ids.shape
        hidden, eps = c["hidden_size"], c["rms_norm_eps"]
        x = p["embed"][input_ids]
        routed, kept = [], {"attention": []}
        for l in range(c["num_hidden_layers"] if layers is None else layers):
            w = {k[len(f"layers.{l}."):]: v for k, v in p.items()
                 if k.startswith(f"layers.{l}.")}
            u = _norm(x, w["input_norm"], eps)
            attended = attention(u, w, c, l, mm, without)
            if l in keep_attention:
                kept["attention"].append(attended)
            if keep_edges and l == keep_attention[0]:
                kept["edges"] = [attention(u, w, c, l, mm, without, widen)
                                 for widen in (-1, 1)]
            x = x + attended
            h = _norm(x, w["post_norm"], eps).reshape(B * S, hidden)
            if c["mlp_layer_types"][l] == "dense":
                y = swiglu(h, w["mlp_gate"], w["mlp_up"], w["mlp_down"], mm)
            else:
                y, chosen, sum_routed = expert_block(
                    h, w, c, mm, held, matmul_inputs, without)
                kept.setdefault("routed", sum_routed)
                routed.append(chosen)
            x = x + y.reshape(B, S, hidden)
        if layers is not None:
            return None, routed, kept
        x = _norm(x, p["norm"], eps).reshape(B * S, hidden)
        return mm(x, p["lm_head"]), routed, kept


def window_layer(params, c, input_ids, layer, held=None, matmul_inputs=None,
                 without=(), edges=False):
    """What the comparison looks at in window layer ``layer``, from a walk
    of the layers up to it alone (a second look, so that what it keeps does
    not lie on the device beside the logits): ``window``, its attention
    sublayer's output ``[B, S, hidden]``; ``routed``, its expert block's
    routed sum ``[T, hidden]`` before the shared expert is added; with
    ``edges`` also ``edges [2, B, S, hidden]``, the sublayer's output with one
    key fewer and one key more in the window (which of the three windows a
    program's output lies nearest says where its window ends)."""
    assert c["layer_types"][layer] == "sliding_attention", layer
    assert list(c["mlp_layer_types"]).index("sparse") == layer, (
        "the routed sum is the model's first expert layer's")
    _, _, kept = forward(params, c, input_ids, held, matmul_inputs, without,
                         keep_attention=(layer,), layers=layer + 1,
                         keep_edges=edges)
    out = {"window": kept["attention"][0], "routed": kept["routed"]}
    if edges:
        out["edges"] = jnp.stack(kept["edges"])
    return out


def loss_sums(params, c, input_ids, labels, held=None, matmul_inputs=None,
              without=(), keep_logits=False, keep_attention=()):
    """Sums over some sequences that chunks of a batch can add: ``ce`` (sum
    of the cross-entropy over positions with a label >= 0), ``n`` (their
    count).  Also ``chosen``, per expert layer ``[T, k]``, for the comparison
    of routing, with ``keep_logits`` the logits ``[B S, V]`` and with
    ``keep_attention`` those layers' attention outputs, stacked."""
    logits, routed, kept = forward(params, c, input_ids, held, matmul_inputs,
                                   without, keep_attention)
    flat = jnp.asarray(labels).reshape(-1)
    valid = flat >= 0
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, flat, 0)[:, None],
                              -1)[:, 0]
    out = {"ce": jnp.sum(ce * valid), "n": valid.sum(),
           "chosen": jnp.stack(routed)}
    if keep_logits:
        out["logits"] = logits
    if keep_attention:
        out["attention"] = jnp.stack(kept["attention"])
    return out


def loss_from_sums(sums):
    """``{"loss", "ce"}`` from added-up ``loss_sums``."""
    ce = sums["ce"] / jnp.maximum(sums["n"], 1)
    return {"loss": ce, "ce": ce}


def pretraining_loss(params, c, input_ids, labels, held=None):
    """The loss of one batch taken whole (what the tests differentiate)."""
    return loss_from_sums(loss_sums(params, c, input_ids, labels,
                                    held))["loss"]
