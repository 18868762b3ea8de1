"""Plain reference of the Qwen3-Next decoder (HF ``model_type``
``qwen3_next``; ``Qwen/Qwen3-Next-80B-A3B``, ``modeling_qwen3_next.py``) and
its pretraining loss.  Straight ``jax.numpy`` in float32 at the highest
matmul precision: the delta rule one position at a time, every held expert
computed for every token and masked by the top-k weights, attention by
blocks of query rows against all keys; no chunked scan, no sort, no grouped
product, no kernel.  Independent of ``hetu_tpu/models``, ``hetu_tpu/layers``
and ``hetu_tpu/ops``: it takes the weights under its own names (``WEIGHTS``
below; matrices are ``[in, out]``, experts stacked on a leading axis) and
the configuration's published keys, and nothing else.

``H`` hidden size, ``eps`` ``rms_norm_eps``::

    N(x; w) = x / sqrt(mean(x^2) + eps) * (1 + w)           in f32
    layer i (0-based): full attention if (i + 1) % full_attention_interval
        == 0, else Gated DeltaNet:
        x = x + mixer(N(x; w_in));  x = x + moe(N(x; w_post))
    final N, untied head, no bias anywhere

    full attention: q_proj: H -> heads * 2 d viewed [.., heads, 2 d]: a
        head's first d are its query, its last d its gate; k_proj, v_proj:
        H -> kv_heads * d.  q = N(q; w_q), k = N(k; w_k) over each head's d.
        RoPE (rotate-half, rope_theta) on the first d * partial_rotary_factor
        dimensions of each head of q and k, the rest pass through.  Causal
        softmax attention scaled by d^-1/2, each KV head serving heads /
        kv_heads query heads.  out = o_proj(attn.reshape(.., heads d)
        * sigmoid(gate)).
    Gated DeltaNet (key heads of d_k, value heads of d_v, r = value heads /
        key heads): in_proj_qkvz: H -> 2 key_dim + 2 value_dim viewed [..,
        key heads, d_k + d_k + r d_v + r d_v] -> q, k, v, z per key head;
        in_proj_ba: H -> 2 value heads viewed [.., key heads, r + r] -> b, a.
        [q | k | v] flattened -> depthwise causal convolution of width
        linear_conv_kernel_dim (left padding, no bias) -> SiLU -> split back.
        beta = sigmoid(b); g = -exp(A_log) * softplus(a + dt_bias).  q and k
        L2-normalised over d_k (x * rsqrt(sum x^2 + 1e-6)), each key head
        repeated for its r value heads, q scaled by d_k^-1/2.  Per value
        head, S_0 = 0, for t = 1..T:
            S = exp(g_t) S;  u = beta_t (v_t - S^T k_t);  S = S + k_t u^T;
            o_t = S^T q_t
        o = o / sqrt(mean(o^2) + eps) * w_n * silu(z) per head (w_n not
        zero-centred); out_proj: value_dim -> H.
    MoE: p = softmax(x W_r) over ALL routed experts in f32; the
        num_experts_per_tok largest (ties to the lower index), renormalised
        to sum to 1 (norm_topk_prob); E(x) = W_d (silu(W_g x) * W_u x);
        y = sum_{e in top-k} p_e E_e(x) + sigmoid(x w_sg) E_shared(x).
    loss: mean cross-entropy over labelled positions + lbl_weight *
        sum_layers LBL, LBL = E sum_i (n_i / T) mean_t p_t,i over all E
        routed experts, n_i the (token, choice) pairs at expert i.

Departures, each on purpose:

* ``held=(first, count)``: this chip's share of an expert-parallel layer.
  The expert weights given are those of experts ``first .. first + count -
  1`` and the sum over a token's top-k runs over those of them alone: what
  the experts on other chips would add is left out, as the program leaves
  it out, and that partial result goes on to the next layer.  The router,
  its top-k, the renormalisation (over all k chosen, held or not) and LBL
  are over all experts.  ``held=None`` is the whole layer.
* The vocabulary may be a slice: ids, logits and the loss are over the rows
  of ``embed`` and ``lm_head`` that are given.
* LBL is summed over layers, each over its own tokens; HF concatenates the
  layers' router outputs and takes one mean, which is this sum divided by
  the number of layers.  ``lbl_weight`` (0.001, the family's
  ``router_aux_loss_coef`` default) is not in the published config; the
  configuration file lists it under ``assumed``.
* The multi-token-prediction module of the released checkpoints has no key
  in the published config and is not here; nor is a router z-loss.

``matmul_inputs`` (default None: plain f32) rounds both operands of every
matrix product to that type before multiplying in f32, and ``state_dtype``
carries the DeltaNet state in that type from position to position: the
reference "at a lower precision", used on the chip to see which gaps a lower
precision than the configuration's would open (the traffic file's tolerances
lie below them).

LBL is a statistic of the whole batch, so ``loss_sums`` returns sums that
chunks of sequences can add and ``loss_from_sums`` finishes them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the weights ``forward`` reads: the model's, then per layer ``layers.<i>.``
#: the common ones and those of the layer's kind
WEIGHTS = ("embed", "norm", "lm_head")
LAYER_WEIGHTS = ("input_norm", "post_norm", "router", "w_gate", "w_up",
                 "w_down", "shared_gate", "shared_up", "shared_down",
                 "shared_sigmoid")
ATTENTION_WEIGHTS = ("q", "k", "v", "o", "q_norm", "k_norm")
DELTANET_WEIGHTS = ("qkvz", "ba", "conv", "a_log", "dt_bias", "gdn_norm",
                    "gdn_out")

#: query rows a block of attention: [heads, 512, S] f32 scores at a time
QUERY_BLOCK = 512


def layer_kinds(c):
    n = c["full_attention_interval"]
    return ["full_attention" if (i + 1) % n == 0 else "linear_attention"
            for i in range(c["num_hidden_layers"])]


def _norm(x, w, eps):
    """Zero-centred RMSNorm: the weight is stored about zero."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def _round(x, dtype):
    """``x`` rounded to ``dtype`` and back to f32.  To bf16 by
    ``reduce_precision`` and not by a pair of ``astype``: XLA may drop a
    conversion to bf16 and back (``xla_allow_excess_precision``; on the chip
    it dropped the state's, and the "bf16 state" read the f32 result to the
    last bit).  The fp8 types have another exponent range and subnormals of
    their own, so they go through the type itself."""
    info = jnp.finfo(dtype)
    if info.nexp == jnp.finfo(jnp.float32).nexp:
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                        mantissa_bits=info.nmant)
    return x.astype(dtype).astype(jnp.float32)


def _mm(a, b, dtype=None):
    if dtype is not None:
        a, b = _round(a, dtype), _round(b, dtype)
    return a @ b


def _rope(x, theta, rotary_dim):
    """Rotate-half RoPE on the first ``rotary_dim`` dimensions of ``[B, S,
    heads, d]``, positions from 0; the other dimensions pass through."""
    S = x.shape[1]
    inv = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                          / rotary_dim)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    r, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    r1, r2 = r[..., : rotary_dim // 2], r[..., rotary_dim // 2:]
    r = r * cos + jnp.concatenate([-r2, r1], -1) * sin
    return jnp.concatenate([r, rest], -1)


def _query_and_gate(qg, d):
    """``[.., heads, 2 d]``: a head's first ``d`` are its query, its last
    ``d`` its gate (not two halves of the whole projection)."""
    return qg[..., :d], qg[..., d:]


def _unit(t):
    """L2 normalisation over a head."""
    return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)


def _shared_scale(h, w_sg, mm):
    """The shared expert's token-by-token scale."""
    return jax.nn.sigmoid(mm(h, w_sg))


def _renormalise(top):
    """``norm_topk_prob``: a token's chosen weights sum to 1."""
    return top / jnp.sum(top, -1, keepdims=True)


def attention(a, w, c, mm):
    """The gated full-attention mixer on normed input ``a [B, S, H]``."""
    B, S, _ = a.shape
    nh, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps = c["rms_norm_eps"]
    q, gate = _query_and_gate(mm(a, w["q"]).reshape(B, S, nh, 2 * d), d)
    gate = gate.reshape(B, S, nh * d)
    k = mm(a, w["k"]).reshape(B, S, nkv, d)
    v = mm(a, w["v"]).reshape(B, S, nkv, d)
    rot = int(d * c["partial_rotary_factor"])
    q = _rope(_norm(q, w["q_norm"], eps), c["rope_theta"], rot)
    k = _rope(_norm(k, w["k_norm"], eps), c["rope_theta"], rot)
    # query head h reads KV head h // (nh / nkv)
    q = q.reshape(B, S, nkv, nh // nkv, d)
    pos = jnp.arange(S)
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1)
        s = mm(qb.transpose(0, 2, 3, 1, 4),                # [B,kv,g,bq,d]
               k.transpose(0, 2, 3, 1)[:, :, None]) / jnp.sqrt(float(d))
        seen = (lo + jnp.arange(block))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return mm(p, v.transpose(0, 2, 1, 3)[:, :, None])  # [B,kv,g,bq,d]
    o = jax.lax.map(rows, jnp.arange(0, S, block))         # [n,B,kv,g,bq,d]
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, nh * d)
    return mm(o * jax.nn.sigmoid(gate), w["o"])


def causal_conv(x, w):
    """Depthwise causal convolution over the sequence, then SiLU: ``x [B, S,
    C]``, ``w [K, C]``: ``y_t = sum_j w[j] x[t - (K - 1) + j]``, zeros before
    the first position (HF's Conv1d weight ``[C, 1, K]`` transposed)."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, j:j + S] * w[j] for j in range(K)))


def delta_rule(q, k, v, g, beta, state_dtype=None):
    """The gated delta rule one position at a time: ``q, k [B, S, heads,
    d_k]``, ``v [B, S, heads, d_v]``, ``g, beta [B, S, heads]`` ->
    ``([B, S, heads, d_v], the last state [B, heads, d_k, d_v])``.  The state
    is f32 (``state_dtype``: the type it is rounded to after every
    position)."""
    B, S, nv, dk = q.shape

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u[..., None, :]
        if state_dtype is not None:
            state = _round(state, state_dtype)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    last, o = jax.lax.scan(step, jnp.zeros((B, nv, dk, v.shape[-1]),
                                           jnp.float32), xs)
    return jnp.moveaxis(o, 0, 1), last


def deltanet(a, w, c, mm, state_dtype=None):
    """The Gated DeltaNet mixer on normed input ``a [B, S, H]``."""
    B, S, _ = a.shape
    nk, nv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    r = nv // nk
    x = mm(a, w["qkvz"]).reshape(B, S, nk, 2 * dk + 2 * r * dv)
    q, k = x[..., :dk], x[..., dk:2 * dk]
    v = x[..., 2 * dk:2 * dk + r * dv]
    z = x[..., 2 * dk + r * dv:].reshape(B, S, nv, dv)
    ba = mm(a, w["ba"]).reshape(B, S, nk, 2 * r)
    b, aa = ba[..., :r].reshape(B, S, nv), ba[..., r:].reshape(B, S, nv)
    mixed = jnp.concatenate([t.reshape(B, S, -1) for t in (q, k, v)], -1)
    mixed = causal_conv(mixed, w["conv"])
    q = mixed[..., :nk * dk].reshape(B, S, nk, dk)
    k = mixed[..., nk * dk:2 * nk * dk].reshape(B, S, nk, dk)
    v = mixed[..., 2 * nk * dk:].reshape(B, S, nv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(w["a_log"]) * jax.nn.softplus(aa + w["dt_bias"])

    def unit(t):                # one copy of a key head a value head
        return jnp.repeat(_unit(t), r, axis=2)
    o, _ = delta_rule(unit(q) / jnp.sqrt(float(dk)), unit(k), v, g, beta,
                      state_dtype)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + c["rms_norm_eps"]) * w["gdn_norm"]
    o = o * jax.nn.silu(z)
    return mm(o.reshape(B, S, nv * dv), w["gdn_out"])


def router(h, w_r, k, matmul_inputs=None):
    """``(logits, probs, chosen [T, k], weight [T, E])``: the softmax over
    all experts, each token's ``k`` largest (ties to the lower index) and
    their probabilities renormalised to sum to 1, laid out by expert."""
    logits = _mm(h, w_r, matmul_inputs)
    probs = jax.nn.softmax(logits, -1)
    chosen = jnp.argsort(-probs, axis=-1, stable=True)[:, :k]
    top = _renormalise(jnp.take_along_axis(probs, chosen, -1))
    weight = jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1], dtype=h.dtype)
                     * top[..., None], 1)
    return logits, probs, chosen, weight


def moe(h, w, c, mm, held=None, matmul_inputs=None):
    """The sparse block on normed tokens ``h [T, H]``: ``(y, (probs,
    chosen))``.  With ``held`` the routed sum is over the held experts."""
    _, probs, chosen, weight = router(h, w["router"],
                                      c["num_experts_per_tok"],
                                      matmul_inputs)
    if held is not None:
        weight = weight[:, held[0]:held[0] + held[1]]
    assert weight.shape[1] == w["w_gate"].shape[0], (
        weight.shape, w["w_gate"].shape)

    def swiglu(w_gate, w_up, w_down):
        return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)

    def expert(y, e):               # every held expert sees every token
        w_gate, w_up, w_down, weight_e = e
        return y + weight_e[:, None] * swiglu(w_gate, w_up, w_down), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
        w["w_gate"], w["w_up"], w["w_down"], weight.T))
    shared = swiglu(w["shared_gate"], w["shared_up"], w["shared_down"])
    y = y + _shared_scale(h, w["shared_sigmoid"], mm) * shared
    return y, (probs, chosen)


def forward(params, c, input_ids, held=None, matmul_inputs=None,
            state_dtype=None):
    """``(logits [B S, V], per layer (probs [T, E], chosen [T, k]))``."""
    def mm(a, b):
        return _mm(a, b, matmul_inputs)

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
            if kind == "full_attention":
                x = x + attention(a, w, c, mm)
            else:
                x = x + deltanet(a, w, c, mm, state_dtype)
            h = _norm(x, w["post_norm"], eps).reshape(B * S, H)
            y, r = moe(h, w, c, mm, held, matmul_inputs)
            routed.append(r)
            x = x + y.reshape(B, S, H)
        x = _norm(x, p["norm"], eps).reshape(B * S, H)
        return mm(x, p["lm_head"]), routed


def loss_sums(params, c, input_ids, labels, held=None, matmul_inputs=None,
              state_dtype=None):
    """Sums over some sequences that chunks of a batch can add: ``ce`` (sum
    of the cross-entropy over positions with a label >= 0), ``n`` (their
    count), ``tokens``, and per layer ``load [E]`` (pairs at each of all
    routed experts) and ``prob [E]`` (sum over tokens of the router's
    probabilities).  Also ``chosen``, per layer ``[T, k]``, for the
    comparison of routing."""
    logits, routed = forward(params, c, input_ids, held, matmul_inputs,
                             state_dtype)
    flat = jnp.asarray(labels).reshape(-1)
    valid = flat >= 0
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, flat, 0)[:, None],
                              -1)[:, 0]
    E = routed[0][0].shape[-1]
    return {
        "ce": jnp.sum(ce * valid), "n": valid.sum(),
        "tokens": flat.shape[0],
        "load": jnp.stack([jnp.sum(jax.nn.one_hot(ch, E), (0, 1))
                           for _, ch in routed]),
        "prob": jnp.stack([pr.sum(0) for pr, _ in routed]),
        "chosen": jnp.stack([ch for _, ch in routed])}


def loss_from_sums(sums, lbl_weight):
    """``{"loss", "ce", "lbl"}`` from added-up ``loss_sums``."""
    T, E = sums["tokens"], sums["load"].shape[-1]
    ce = sums["ce"] / jnp.maximum(sums["n"], 1)
    lbl = jnp.sum(E * jnp.sum(sums["load"] / T * sums["prob"] / T, -1))
    return {"loss": ce + lbl_weight * lbl, "ce": ce, "lbl": lbl}


def pretraining_loss(params, c, input_ids, labels, lbl_weight, held=None):
    """The loss of one batch taken whole (what the tests differentiate)."""
    sums = loss_sums(params, c, input_ids, labels, held)
    return loss_from_sums(sums, lbl_weight)["loss"]
