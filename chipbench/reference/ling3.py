"""Plain reference of the Ling-3.0 language model (the decoder of
``inclusionAI/Ling-3.0-flash-VL``; text-only, no vision tower) and its
pretraining loss.  Straight ``jax.numpy`` in float32 at the highest matmul
precision: the delta rule one position at a time, every held expert computed
for every token and masked by the router's weights, attention by blocks of
query rows against all keys; no chunked scan, no sort, no grouped product, no
kernel.  Independent of ``hetu_tpu/models``, ``hetu_tpu/layers`` and
``hetu_tpu/ops``: it takes the weights under its own names (``WEIGHTS`` below;
matrices are ``[in, out]``, experts stacked on a leading axis) and the
configuration's published keys, and nothing else.

``H`` hidden size, ``eps`` ``rms_norm_eps``, ``N(x; w) = x / sqrt(mean(x^2) +
eps) * w`` in f32::

    layer i (0-based): latent attention if (i + 1) % layer_group_size == 0,
        else KDA;  its FFN the dense SwiGLU if i < first_k_dense_replace,
        else the expert block:
        x = x + mixer(N(x; w_in));  x = x + ffn(N(x; w_post))
    final N, untied head, no bias anywhere

    KDA (heads of d = head_dim): [q~ | k~ | v~ | f | z] = x W_in, five blocks
        of heads x d.  [q~ | k~ | v~] -> depthwise causal convolution of
        width short_conv_kernel_size (left padding, no bias) -> SiLU.
        q = unit(q~) / sqrt(d), k = unit(k~)  (x * rsqrt(sum x^2 + 1e-6) a
        head), v = v~;  g = kda_lower_bound * sigmoid(exp(A_log_h) * (f +
        dt_bias)) a channel;  beta = sigmoid(x w_beta) a head.  Per head,
        S_0 = 0, for t = 1..T:
            S = Diag(exp(g_t)) S;  u = beta_t (v_t - S^T k_t);
            S = S + k_t u^T;  o_t = S^T q_t
        o = o / sqrt(mean(o^2) + eps) * w_n * sigmoid(z) a head; W_out.
    latent attention (heads; d_n = qk_nope_head_dim, d_r = qk_rope_head_dim,
        d_v = v_head_dim, r = kv_lora_rank): q = (x W_q) viewed [.., heads,
        d_n + d_r];  [c | k_r] = x W_kva, c = N(c; w_c) over r;  (c W_kvb)
        viewed [.., heads, d_n + d_v] -> k_n, v.  q = N(q; w_q), k = N([k_n |
        k_r]; w_k) over d_n + d_r (use_qk_norm), rotate-half RoPE
        (rope_theta) on the LAST d_r of both.  Causal softmax attention
        scaled by (d_n + d_r)^-1/2.  out = W_o (o_h * sigmoid(x w_gate)_h).
    expert block: s = sigmoid(x W_r) over ALL routed experts in f32; chosen
        by s + b (b the selection bias): the experts in n_group groups of
        neighbours, a group's score the sum of its two largest, the
        topk_group largest groups kept (ties to the lower index), among
        their experts the num_experts_per_tok largest; weights
        routed_scaling_factor * s_i / sum_chosen s_j (norm_topk_prob);
        E(x) = W_d (silu(W_g x) * W_u x);  y = sum w_i E_i(x) + E_shared(x).
    loss: mean cross-entropy over labelled positions.

Departures, each on purpose:

* ``held=(first, count)``: this chip's share of an expert-parallel layer.
  The expert weights given are those of experts ``first .. first + count -
  1`` and the sum over a token's chosen experts runs over those of them
  alone: what the experts on other chips would add is left out, as the
  program leaves it out.  The router, its groups, its choice and the
  renormalisation (over all chosen, held or not) are over all experts.
  ``held=None`` is the whole layer.
* The vocabulary may be a slice: ids, logits and the loss are over the rows
  of ``embed`` and ``lm_head`` that are given.
* Not here, as not in the program: the vision tower, multi-token
  prediction, the clamp of ``expert_swiglu_limit_list`` (0 for every layer
  that is built; ``forward`` refuses another value).

``matmul_inputs`` (default None: plain f32) rounds both operands of every
matrix product to that type before multiplying in f32, ``state_dtype``
carries the KDA state in that type from position to position, and
``without`` leaves a mechanism out (``"head_gate"``: the latent layer's
output gate; ``"vector_decay"``: every channel of a head decays by the mean
of the head's; ``"groups"``: the choice over all experts at once): the
reference "at a lower precision" or "with an omission", used on the chip to
see which gaps each would open (the traffic file's tolerances lie below
them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the weights ``forward`` reads: the model's, then per layer ``layers.<i>.``
#: the norms and those of the layer's mixer and FFN
WEIGHTS = ("embed", "norm", "lm_head")
LAYER_WEIGHTS = ("input_norm", "post_norm")
KDA_WEIGHTS = ("kda_in", "kda_beta", "conv", "a_log", "dt_bias", "kda_norm",
               "kda_out")
ATTENTION_WEIGHTS = ("q", "kva", "kv_norm", "kvb", "q_norm", "k_norm",
                     "gate", "o")
DENSE_WEIGHTS = ("mlp_gate", "mlp_up", "mlp_down")
EXPERT_WEIGHTS = ("router", "router_bias", "w_gate", "w_up", "w_down",
                  "shared_gate", "shared_up", "shared_down")

#: query rows a block of attention: [heads, 512, S] f32 scores at a time
QUERY_BLOCK = 512


def layer_kinds(c):
    n = c["layer_group_size"]
    return ["attention" if (i + 1) % n == 0 else "kda"
            for i in range(c["num_hidden_layers"])]


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


def _rope_last(x, theta, d_rope):
    """Rotate-half RoPE on the last ``d_rope`` dimensions of ``[B, S, heads,
    d]``, positions from 0; the other dimensions pass through."""
    S = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, d_rope, 2, dtype=jnp.float32)
                          / d_rope)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rest, r = x[..., :-d_rope], x[..., -d_rope:]
    r1, r2 = r[..., : d_rope // 2], r[..., d_rope // 2:]
    r = r * cos + jnp.concatenate([-r2, r1], -1) * sin
    return jnp.concatenate([rest, r], -1)


def _unit(t):
    """L2 normalisation over a head."""
    return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)


def latent_attention(a, w, c, mm, without=()):
    """The latent-attention mixer on normed input ``a [B, S, H]``."""
    B, S, _ = a.shape
    nh, eps = c["num_attention_heads"], c["rms_norm_eps"]
    dn, dr, dv, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"], c["kv_lora_rank"])
    q = mm(a, w["q"]).reshape(B, S, nh, dn + dr)
    kva = mm(a, w["kva"])
    latent = _norm(kva[..., :r], w["kv_norm"], eps)
    kvb = mm(latent, w["kvb"]).reshape(B, S, nh, dn + dv)
    k = jnp.concatenate([kvb[..., :dn], jnp.broadcast_to(
        kva[..., None, r:], (B, S, nh, dr))], -1)
    v = kvb[..., dn:]
    if c["use_qk_norm"]:
        q, k = _norm(q, w["q_norm"], eps), _norm(k, w["k_norm"], eps)
    q = _rope_last(q, c["rope_theta"], dr)
    k = _rope_last(k, c["rope_theta"], dr)
    pos = jnp.arange(S)
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1)
        s = mm(qb.transpose(0, 2, 1, 3),                   # [B, h, bq, d]
               k.transpose(0, 2, 3, 1)) / jnp.sqrt(float(dn + dr))
        seen = (lo + jnp.arange(block))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return mm(p, v.transpose(0, 2, 1, 3))              # [B, h, bq, dv]
    o = jax.lax.map(rows, jnp.arange(0, S, block))         # [n, B, h, bq, dv]
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, S, nh, dv)
    if "head_gate" not in without:
        o = o * jax.nn.sigmoid(mm(a, w["gate"]))[..., None]
    return mm(o.reshape(B, S, nh * dv), w["o"])


def causal_conv(x, w):
    """Depthwise causal convolution over the sequence, then SiLU: ``x [B, S,
    C]``, ``w [K, C]``: ``y_t = sum_j w[j] x[t - (K - 1) + j]``, zeros before
    the first position."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, j:j + S] * w[j] for j in range(K)))


def kda_recurrence(q, k, v, g, beta, state_dtype=None):
    """The delta rule with a decay a channel, one position at a time: ``q, k,
    g [B, S, heads, d_k]``, ``v [B, S, heads, d_v]``, ``beta [B, S, heads]``
    -> ``([B, S, heads, d_v], the last state [B, heads, d_k, d_v])``.  The
    state is f32 (``state_dtype``: the type it is rounded to after every
    position); the decay is added as ``S expm1(g)``, which a chip's ``exp``
    near one does not let drift."""
    B, S, nh, dk = q.shape

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state + state * jnp.expm1(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        if state_dtype is not None:
            state = _round(state, state_dtype)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    last, o = jax.lax.scan(step, jnp.zeros((B, nh, dk, v.shape[-1]),
                                           jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), last


def kda_gate(f, a_log, dt_bias, lower_bound):
    """``[.., heads, d]`` in ``[lower_bound, 0)``."""
    nh = a_log.shape[0]
    return lower_bound * jax.nn.sigmoid(
        jnp.exp(a_log)[:, None] * (f + dt_bias.reshape(nh, -1)))


def kda(a, w, c, mm, state_dtype=None, without=()):
    """The KDA mixer on normed input ``a [B, S, H]``."""
    B, S, _ = a.shape
    nh, d = c["num_attention_heads"], c["head_dim"]
    hd = nh * d
    x = mm(a, w["kda_in"])
    mixed = causal_conv(x[..., :3 * hd], w["conv"])
    heads = lambda t: t.reshape(B, S, nh, d)
    q, k, v = (heads(mixed[..., i * hd:(i + 1) * hd]) for i in range(3))
    g = kda_gate(heads(x[..., 3 * hd:4 * hd]), w["a_log"], w["dt_bias"],
                 float(c["kda_lower_bound"]))
    if "vector_decay" in without:
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    z = heads(x[..., 4 * hd:])
    beta = jax.nn.sigmoid(mm(a, w["kda_beta"]))
    o, _ = kda_recurrence(_unit(q) / jnp.sqrt(float(d)), _unit(k), v, g,
                          beta, state_dtype)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + c["rms_norm_eps"]) * w["kda_norm"]
    o = o * jax.nn.sigmoid(z)
    return mm(o.reshape(B, S, hd), w["kda_out"])


def swiglu(h, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def router(h, w_r, bias, c, matmul_inputs=None, without=()):
    """``(scores [T, E], chosen [T, k], weight [T, E])``: the sigmoid scores
    over all experts, each token's ``k`` experts (the best groups first,
    then the largest ``s + b`` among theirs, ties to the lower index) and
    their scores renormalised and scaled, laid out by expert."""
    k = c["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_mm(h, w_r, matmul_inputs))
    by = scores + bias
    T, E = by.shape
    n_group, topk_group = c["n_group"], c["topk_group"]
    if n_group > 1 and "groups" not in without:
        grouped = by.reshape(T, n_group, E // n_group)
        top2 = -jnp.sort(-grouped, axis=-1)[..., :2]
        best = jnp.argsort(-top2.sum(-1), axis=-1, stable=True)[:, :topk_group]
        kept = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None],
                       axis=1)                              # [T, n_group]
        by = jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(T, E)
    chosen = jnp.argsort(-by, axis=-1, stable=True)[:, :k]
    top = jnp.take_along_axis(scores, chosen, -1)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    top = top * c["routed_scaling_factor"]
    weight = jnp.sum(jax.nn.one_hot(chosen, E, dtype=h.dtype)
                     * top[..., None], 1)
    return scores, chosen, weight


def expert_block(h, w, c, mm, held=None, matmul_inputs=None, without=(),
                 shared=True):
    """The sparse block on normed tokens ``h [T, H]``: ``(y, chosen)``.  With
    ``held`` the routed sum is over the held experts; ``shared=False`` leaves
    the shared expert out (a share that is not the one to count it)."""
    _, chosen, weight = router(h, w["router"], w["router_bias"], c,
                               matmul_inputs, without)
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
    if shared:
        y = y + swiglu(h, w["shared_gate"], w["shared_up"],
                       w["shared_down"], mm)
    return y, chosen


def forward(params, c, input_ids, held=None, matmul_inputs=None,
            state_dtype=None, without=(), keep_mixer=None):
    """``(logits [B S, V], per expert layer chosen [T, k])``, and with
    ``keep_mixer`` the output ``[B, S, H]`` of that layer's mixer (before the
    residual sum) as a third."""
    def mm(a, b):
        return _mm(a, b, matmul_inputs)

    n = c["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert not any(list(c.get(key) or ())[:n]), (
            f"{key}: the clamp on SwiGLU is not modelled")
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        B, S = input_ids.shape
        H, eps = c["hidden_size"], c["rms_norm_eps"]
        x = p["embed"][input_ids]
        routed = []
        for i, kind in enumerate(layer_kinds(c)):
            w = {k[len(f"layers.{i}."):]: v for k, v in p.items()
                 if k.startswith(f"layers.{i}.")}
            a = _norm(x, w["input_norm"], eps)
            mixed = (latent_attention(a, w, c, mm, without)
                     if kind == "attention"
                     else kda(a, w, c, mm, state_dtype, without))
            if i == keep_mixer:
                kept = mixed
            x = x + mixed
            h = _norm(x, w["post_norm"], eps).reshape(B * S, H)
            if i < c["first_k_dense_replace"]:
                y = swiglu(h, w["mlp_gate"], w["mlp_up"], w["mlp_down"], mm)
            else:
                y, chosen = expert_block(h, w, c, mm, held, matmul_inputs,
                                         without)
                routed.append(chosen)
            x = x + y.reshape(B, S, H)
        x = _norm(x, p["norm"], eps).reshape(B * S, H)
        if keep_mixer is not None:
            return mm(x, p["lm_head"]), routed, kept
        return mm(x, p["lm_head"]), routed


def loss_sums(params, c, input_ids, labels, held=None, matmul_inputs=None,
              state_dtype=None, without=(), keep_logits=False,
              keep_mixer=None):
    """Sums over some sequences that chunks of a batch can add: ``ce`` (sum
    of the cross-entropy over positions with a label >= 0), ``n`` (their
    count).  Also ``chosen``, per expert layer ``[T, k]``, for the comparison
    of routing, with ``keep_logits`` the logits ``[B S, V]`` and with
    ``keep_mixer`` that layer's mixer output."""
    logits, routed, *mixer = forward(params, c, input_ids, held,
                                     matmul_inputs, state_dtype, without,
                                     keep_mixer)
    flat = jnp.asarray(labels).reshape(-1)
    valid = flat >= 0
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, flat, 0)[:, None],
                              -1)[:, 0]
    out = {"ce": jnp.sum(ce * valid), "n": valid.sum(),
           "chosen": jnp.stack(routed)}
    if keep_logits:
        out["logits"] = logits
    if mixer:
        out["mixer"] = mixer[0]
    return out


def loss_from_sums(sums):
    """``{"loss", "ce"}`` from added-up ``loss_sums``."""
    ce = sums["ce"] / jnp.maximum(sums["n"], 1)
    return {"loss": ce, "ce": ce}


def pretraining_loss(params, c, input_ids, labels, held=None):
    """The loss of one batch taken whole (what the tests differentiate)."""
    return loss_from_sums(loss_sums(params, c, input_ids, labels,
                                    held))["loss"]
