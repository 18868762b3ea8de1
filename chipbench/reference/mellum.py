"""Plain reference of the Mellum 2 decoder
(``JetBrains/Mellum2-12B-A2.5B-Instruct``) and its pretraining loss: the UNCUT
layers, all experts and the whole vocabulary, on one device.  Straight
``jax.numpy`` in float32 at the highest matmul precision: attention by blocks
of query rows against all keys under an explicit mask built from ``0 <= i - j
< w``, every expert computed for every token and masked by the router's
weights, the head a block of rows at a time; no kernel, no mesh, no exchange,
no sort, no grouped product, no recomputation.  Independent of
``hetu_tpu/models``, ``hetu_tpu/layers`` and ``hetu_tpu/ops`` (it computes
YaRN's table itself).

It walks ONE LAYER AND ONE SEQUENCE AT A TIME so that it fits a chip beside a
program's state: ``weights(prefix)`` hands it the f32 weights of one group
under its own names (``LAYER_WEIGHTS`` for ``layers.<l>.``, ``HEAD_WEIGHTS``
for the final norm and the head, ``"embed"``; matrices are ``[in, out]``,
experts stacked on a leading axis) and may gather them from wherever they lie;
the group is dropped before the next is asked for.

``eps`` ``rms_norm_eps``, ``N(x; w) = x / sqrt(mean(x^2) + eps) * w``::

    layer l: a = x + Attn_l(N(x; w_in));  y = a + MoE(N(a; w_post))
    final N, untied head, no bias anywhere

    Attn_l: num_attention_heads query heads on num_key_value_heads key heads
        of d = head_dim; q = u W_q, k = u W_k, v = u W_v; query head h reads
        key head h // (H / KV); rotary on all d dimensions of q and k
        (below); scores / sqrt(d), position i sees j with 0 <= i - j < w_l,
        w_l = sliding_window where layer_types[l] is sliding_attention and
        unbounded where full_attention; out = ctx W_o.
    rotary: half-split pairs, inv_i = b^(-2i/d); with rope_type "yarn":
        corr(n) = d ln(L0 / (2 pi n)) / (2 ln b), low = max(floor(corr(
        beta_fast)), 0), high = min(ceil(corr(beta_slow)), d - 1), ramp_i =
        clip((i - low) / (high - low), 0, 1), inv_i = (1 - ramp_i) b^(-2i/d)
        + ramp_i b^(-2i/d) / factor, and cos, sin times attention_factor.
    MoE: p = softmax(u W_r) over ALL experts; the num_experts_per_tok largest
        (ties to the lower index); weights p_e / sum_chosen p
        (norm_topk_prob); E(x) = W_d (silu(W_g x) * W_u x); y = sum w_e
        E_e(x); no shared expert.
    loss: mean cross-entropy over labelled positions.

Departures from the published description: none in the equations.  The model
card says "MTP head"; ``config.json`` has no key for one and the parameter
count closes without it, so none is computed.  The window counts the query's
own position (HF's convention) and q and k carry no norm: no key says
otherwise (the configuration file's ``assumed``).

``matmul_inputs`` (default None: plain f32) rounds both operands of every
matrix product to that type before multiplying in f32; ``without`` changes one
piece (``CONTROLS``): the reference "at a lower precision" or "with a piece
changed", used on the chip to see which gaps each would open (the traffic
file's limits lie below them).  Three of the pieces are what an exchange of
experts over ``ranks`` devices gets wrong when it is wrong; the reference has
no exchange and states each as what it does to the sum.
"""

from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

LAYER_WEIGHTS = ("input_norm", "post_norm", "q", "k", "v", "o", "router",
                 "w_gate", "w_up", "w_down")
HEAD_WEIGHTS = ("norm", "lm_head")

#: query rows a block of attention ([heads, 256, S] f32 scores at a time) and
#: rows a block of the head ([1024, V] f32 logits at a time)
QUERY_BLOCK = 256
HEAD_BLOCK = 1024

#: what ``without`` may name, and what each changes
CONTROLS = {
    "window": "window layers see every earlier key",
    "window_less": "the window is one key shorter",
    "window_more": "the window is one key longer",
    "yarn": "full layers turn by plain frequencies at their base (no blend, "
            "no attention factor)",
    "attention_factor": "YaRN's blend without its factor on cos and sin",
    "norm_topk": "the chosen experts' probabilities not normalised",
    "rank_offset": "the experts of rank 1 stand at rank 2's offset and rank "
                   "2's at rank 1's: a pair routed to one is computed by the "
                   "other's weights",
    "returned_order": "each sequence gets the routed sum of the next rank's "
                      "sequence: the parts summed, returned in another order",
    "part_left_out": "the last rank's experts add nothing to the sum",
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


def rotary_tables(seq_len, d, p, without=()):
    """``(cos, sin) [S, d]`` of one ``rope_parameters`` group."""
    b = float(p["rope_theta"])
    i = jnp.arange(d // 2, dtype=jnp.float32)
    inv = b ** (-2.0 * i / d)
    factor = 1.0
    if p.get("rope_type", "default") == "yarn" and "yarn" not in without:
        def corr(turns):
            return (d * math.log(p["original_max_position_embeddings"]
                                 / (turns * 2 * math.pi)) / (2 * math.log(b)))
        low = max(math.floor(corr(p["beta_fast"])), 0)
        high = min(math.ceil(corr(p["beta_slow"])), d - 1)
        ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
        inv = (1.0 - ramp) * inv + ramp * inv / p["factor"]
        if "attention_factor" not in without:
            factor = p.get("attention_factor",
                           0.1 * math.log(p["factor"]) + 1.0)
    ang = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)
    return jnp.cos(ang) * factor, jnp.sin(ang) * factor


def rotate(x, cos, sin):
    """Half-split rotary on ``x [S, heads, d]``, positions from 0."""
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return (x * cos[:, None, :]
            + jnp.concatenate([-x2, x1], -1) * sin[:, None, :])


def attention(u, w, c, kind, mm, without=(), widen=0):
    """The attention sublayer of a layer of ``kind`` (its ``layer_types``
    entry) on one sequence's normed input ``u [S, hidden]``; ``widen`` more
    keys (fewer, if negative) in a window layer's window."""
    S = u.shape[0]
    d, kv, H = (c["head_dim"], c["num_key_value_heads"],
                c["num_attention_heads"])
    q = mm(u, w["q"]).reshape(S, H, d)
    k = mm(u, w["k"]).reshape(S, kv, d)
    v = mm(u, w["v"]).reshape(S, kv, d)
    cos, sin = rotary_tables(S, d, c["rope_parameters"][kind], without)
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    reads = jnp.arange(H) // (H // kv)      # query head h reads key head
    k, v = k[:, reads], v[:, reads]                         # [S, H, d]
    window = None
    if kind == "sliding_attention" and "window" not in without:
        # HF's convention: the window counts the query's own position
        window = c["sliding_window"] + widen + (
            -1 if "window_less" in without else
            1 if "window_more" in without else 0)
    pos = jnp.arange(S)
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=0)
        s = mm(qb.transpose(1, 0, 2),                       # [H, bq, d]
               k.transpose(1, 2, 0)) / jnp.sqrt(float(d))
        gap = (lo + jnp.arange(block))[:, None] - pos[None, :]     # i - j
        seen = gap >= 0
        if window is not None:
            seen = seen & (gap < window)
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return mm(prob, v.transpose(1, 0, 2))               # [H, bq, d]
    o = jax.lax.map(rows, jnp.arange(0, S, block))          # [n, H, bq, d]
    return mm(o.transpose(0, 2, 1, 3).reshape(S, H * d), w["o"])


def router(h, w_r, c, matmul_inputs=None, without=()):
    """``(chosen [T, k], weight [T, E])``: each token's ``k`` experts by the
    softmax over ALL experts (ties to the lower index) and their
    probabilities normalised over the chosen, laid out by expert."""
    k = c["num_experts_per_tok"]
    p = jax.nn.softmax(_mm(h, w_r, matmul_inputs), -1)
    chosen = jnp.argsort(-p, axis=-1, stable=True)[:, :k]
    top = jnp.take_along_axis(p, chosen, -1)
    if "norm_topk" not in without:
        top = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.sum(jax.nn.one_hot(chosen, p.shape[1], dtype=h.dtype)
                     * top[..., None], 1)
    return chosen, weight


def experts(h, w, c, mm, matmul_inputs=None, without=(), ranks=1):
    """The expert block on one sequence's normed tokens ``h [T, hidden]``:
    ``(routed [T, hidden], chosen [T, k])``; every expert sees every token."""
    chosen, weight = router(h, w["router"], c, matmul_inputs, without)
    E = weight.shape[1]
    a_rank = E // ranks
    if "rank_offset" in without and ranks > 2:
        by_rank = weight.reshape(-1, ranks, a_rank)
        weight = by_rank[:, jnp.asarray(
            [0, 2, 1] + list(range(3, ranks)))].reshape(weight.shape)
    if "part_left_out" in without and ranks > 1:
        weight = weight.at[:, E - a_rank:].set(0.0)

    def expert(y, e):
        w_gate, w_up, w_down, weight_e = e
        out = mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)
        return y + weight_e[:, None] * out, None
    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        w["w_gate"], w["w_up"], w["w_down"], weight.T))
    return routed, chosen


def layer(w, c, kind, x, matmul_inputs=None, without=(), ranks=1,
          edges=False):
    """A layer of ``kind`` on one sequence ``x [S, hidden]`` up to the sum with
    its experts, which is the caller's (``walk``): ``{"a": x + Attn, "attention":
    Attn's output, "routed": the experts' weighted sum, "chosen" [S, k]}``
    and, with ``edges``, ``"edges" [2, S, hidden]``: the attention sublayer's
    output with one key fewer and one key more in its window."""
    def mm(a, b):
        return _mm(a, b, matmul_inputs)
    with jax.default_matmul_precision("highest"):
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
        eps = c["rms_norm_eps"]
        u = _norm(x, w["input_norm"], eps)
        attended = attention(u, w, c, kind, mm, without)
        out = {"attention": attended, "a": x + attended}
        if edges:
            out["edges"] = jnp.stack([attention(u, w, c, kind, mm, without, by)
                                      for by in (-1, 1)])
        out["routed"], out["chosen"] = experts(
            _norm(out["a"], w["post_norm"], eps), w, c, mm, matmul_inputs,
            without, ranks)
        return out


def head(w, c, x, labels, matmul_inputs=None, keep_logits=0):
    """The final norm, the head and the loss's sums on one sequence ``x [S,
    hidden]``, ``HEAD_BLOCK`` rows at a time: ``{"ce": the sum of the
    cross-entropy over positions with a label >= 0, "n": their count}`` and,
    with ``keep_logits = n``, ``"logits" [S / n, V]``: every ``n``-th row,
    from row 0."""
    with jax.default_matmul_precision("highest"):
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
        S = x.shape[0]
        block = min(HEAD_BLOCK, S)
        assert S % block == 0, (S, block)
        x = _norm(x, w["norm"], c["rms_norm_eps"])

        def rows(args):
            xb, lb = args
            logits = _mm(xb, w["lm_head"], matmul_inputs)
            valid = lb >= 0
            logp = jax.nn.log_softmax(logits, -1)
            ce = -jnp.take_along_axis(logp, jnp.where(valid, lb, 0)[:, None],
                                      -1)[:, 0]
            return (jnp.sum(ce * valid), valid.sum(),
                    logits[::keep_logits] if keep_logits else jnp.zeros(()))
        ce, n, logits = jax.lax.map(rows, (
            x.reshape(S // block, block, -1),
            jnp.asarray(labels).reshape(S // block, block)))
        out = {"ce": ce.sum(), "n": n.sum()}
        if keep_logits:
            assert block % keep_logits == 0, (block, keep_logits)
            out["logits"] = logits.reshape(S // keep_logits, -1)
        return out


_layer_jit = jax.jit(layer, static_argnames=(
    "c", "kind", "matmul_inputs", "without", "ranks", "edges"))
_head_jit = jax.jit(head, static_argnames=("c", "matmul_inputs",
                                           "keep_logits"))


def walk(weights, c, input_ids, labels, matmul_inputs=None, without=(),
         ranks=1, keep=(), keep_attention=(), edges_of=None, logits_every=1):
    """The model on ``input_ids [B, S]`` a layer and a sequence at a time.
    ``weights(prefix)`` hands over one group's weights (module docstring).
    Returns ``(sums, kept)``: ``sums`` the loss's ``{"ce", "n"}`` over all
    sequences; ``kept`` numpy arrays of the sequences ``keep`` names, in that
    order: ``logits [n S / logits_every, V]`` (every so many-th row of a
    sequence), ``attention`` (layer -> ``[n, S, hidden]`` of
    the layers ``keep_attention`` names), ``edges [2, n, S, hidden]`` of layer
    ``edges_of``, ``routed [n S, hidden]`` the first layer's experts' sum, and
    of ALL sequences ``chosen [layers, B S, k]``."""
    assert set(without) <= set(CONTROLS), without
    ids = np.asarray(input_ids)
    B = len(ids)
    embed = weights("embed")
    xs = [jnp.asarray(np.asarray(embed)[ids[b]], jnp.float32)
          for b in range(B)]
    del embed
    kept = {"attention": {}, "chosen": []}
    one, frozen = _layer_jit, _Frozen(c)
    for l in range(c["num_hidden_layers"]):
        # once on the device for all sequences, and gone before the next
        w = jax.device_put(weights(f"layers.{l}."))
        outs = [one(w, frozen, c["layer_types"][l], x, matmul_inputs,
                    tuple(without), ranks,
                    edges=l == edges_of and b in keep)
                for b, x in enumerate(xs)]
        del w
        # the experts' sum goes back to its own sequence; the control hands
        # each the next one's
        turn = 1 if "returned_order" in without else 0
        xs = [o["a"] + outs[(b + turn) % B]["routed"]
              for b, o in enumerate(outs)]
        kept["chosen"].append(np.concatenate(
            [np.asarray(o["chosen"]) for o in outs]))
        if l in keep_attention:
            kept["attention"][l] = np.stack(
                [np.asarray(outs[b]["attention"]) for b in keep])
        if l == edges_of:
            kept["edges"] = np.stack(
                [np.asarray(outs[b]["edges"]) for b in keep], axis=1)
        if l == 0 and keep:
            kept["routed"] = np.concatenate(
                [np.asarray(outs[(b + turn) % B]["routed"]) for b in keep])
        jax.block_until_ready(xs)
        del outs
    w = jax.device_put(weights("head."))
    last = _head_jit
    sums, logits = {"ce": 0.0, "n": 0}, []
    labels = np.asarray(labels)
    for b, x in enumerate(xs):
        out = last(w, frozen, x, labels[b], matmul_inputs,
                   logits_every if b in keep else 0)
        sums = {k: sums[k] + float(out[k]) for k in sums}
        if b in keep:
            logits.append(np.asarray(out["logits"]))
    if logits:
        kept["logits"] = np.concatenate(logits)
    kept["chosen"] = np.stack(kept["chosen"])
    return sums, kept


class _Frozen(dict):
    """A configuration as a static argument of ``jax.jit``, hashed and
    compared by its content, so that every ``walk`` of one configuration
    compiles a kind of layer once."""

    def _text(self):
        return json.dumps(self, sort_keys=True, default=str)

    def __hash__(self):
        return hash(self._text())

    def __eq__(self, other):
        return isinstance(other, _Frozen) and self._text() == other._text()


def loss_from_sums(sums):
    """``{"loss", "ce"}`` from added-up sums."""
    ce = sums["ce"] / max(sums["n"], 1)
    return {"loss": ce, "ce": ce}


def pretraining_loss(params, c, input_ids, labels):
    """The loss of one batch as ONE differentiable function of ``params``
    (``"embed"``, ``"layers.<l>.<name>"``, ``"norm"``, ``"lm_head"``): what
    the tests differentiate."""
    ce = n = 0.0
    for b in range(input_ids.shape[0]):
        x = jnp.asarray(params["embed"], jnp.float32)[input_ids[b]]
        for l in range(c["num_hidden_layers"]):
            w = {k: params[f"layers.{l}.{k}"] for k in LAYER_WEIGHTS}
            out = layer(w, c, c["layer_types"][l], x)
            x = out["a"] + out["routed"]
        out = head({k: params[k] for k in HEAD_WEIGHTS}, c, x, labels[b])
        ce, n = ce + out["ce"], n + out["n"]
    return ce / jnp.maximum(n, 1)
