"""Plain reference of the Nemotron-H decoder (HF ``model_type``
``nemotron_h``; ``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B``,
``modeling_nemotron_h.py``) and its pretraining loss.  Straight ``jax.numpy``
in float32 at the highest matmul precision: the state-space recurrence one
position at a time, every held expert computed for every token and masked by
the router's weights, attention by blocks of query rows against all keys; no
chunked scan, no sort, no grouped product, no kernel.  Independent of
``hetu_tpu/models``, ``hetu_tpu/layers`` and ``hetu_tpu/ops``: it takes the
weights under its own names (``WEIGHTS`` below; matrices are ``[in, out]``,
experts stacked on a leading axis) and the configuration's published keys,
and nothing else.

``H`` hidden size, ``eps`` ``layer_norm_epsilon``::

    N(x; w) = x / sqrt(mean(x^2) + eps) * w                     in f32
    block i is one sublayer, by hybrid_override_pattern[i]:
        x = x + mixer_i(N(x; w_i))
    final N, untied head, no bias but the convolution's

    M, Mamba-2 (h heads of p channels, d = h p; g groups; state n):
        [z | xBC | dt] = in_proj(x), widths d, d + 2 g n, h
        xBC = silu(conv(xBC) + b): depthwise causal convolution of width
            conv_kernel, left padding
        xBC -> x [.., h, p], B [.., g, n], C [.., g, n]; head j reads group
            j // (h / g)
        dt = softplus(dt + dt_bias) (not clamped);  A = -exp(A_log)
        per head, S_0 = 0 [p, n], for t = 1..T:
            S = exp(dt_t A) S + dt_t x_t B_t^T;  y_t = S C_t + D x_t
        y = y silu(z);  y = y / sqrt(mean over each group's d / g channels
            of y^2 + eps) * w_n;  out_proj: d -> H
    E, experts: s = sigmoid(x W_r) over ALL routed experts in f32; the
        num_experts_per_tok largest of s + bias (ties to the lower index);
        their weights s (without the bias) over their sum (norm_topk_prob),
        times routed_scaling_factor; F(x; W_u, W_d) = W_d relu(W_u x)^2;
        y = sum_{e chosen} w_e F_e(x) + F_shared(x)
    *, attention: q_proj: H -> heads d, k_proj, v_proj: H -> kv_heads d; no
        rotary, no bias; causal softmax attention scaled by d^-1/2, each KV
        head serving heads / kv_heads query heads; o_proj: heads d -> H
    loss: mean cross-entropy over labelled positions + lbl_weight *
        sum over E blocks of LBL, LBL = E sum_i f_i P_i over all E routed
        experts, f_i the share of the (token, choice) pairs at expert i,
        P_i the mean over tokens of s_i / sum_j s_j

Departures, each on purpose:

* ``held=(first, count)``: this chip's share of an expert-parallel layer.
  The expert weights given are those of experts ``first .. first + count -
  1`` and the sum over a token's chosen experts runs over those of them
  alone: what the experts on other chips would add is left out, as the
  program leaves it out, and that partial result goes on to the next block.
  The router, its choice, the renormalisation (over all k chosen, held or
  not) and LBL are over all experts.  ``held=None`` is the whole layer.
* The vocabulary may be a slice: ids, logits and the loss are over the rows
  of ``embed`` and ``lm_head`` that are given.
* ``lbl_weight`` and the rule that moves the bias are not in the published
  config; the configuration file lists them under ``assumed``.  The bias is
  an input here like a weight; nothing in this file moves it.
* No rotary embedding in attention (the family's modelling code applies
  none; ``rope_theta`` and ``partial_rotary_factor`` are not read), ``dt``
  not clamped (``time_step_limit`` ``(0, inf)``), ``n_group = topk_group =
  1`` (no group-limited routing), no router z-loss.

``matmul_inputs`` (default None: plain f32) rounds both operands of every
matrix product to that type before multiplying in f32, and ``state_dtype``
carries the state-space state in that type from position to position: the
reference "at a lower precision", used on the chip to see which gaps a lower
precision than the configuration's would open (the traffic file's tolerances
lie below them).

LBL is a statistic of the whole batch, so ``loss_sums`` returns sums that
chunks of sequences can add and ``loss_from_sums`` finishes them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the weights ``forward`` reads: the model's, then per block ``layers.<i>.``
#: its norm and those of its kind
WEIGHTS = ("embed", "norm", "lm_head")
BLOCK_WEIGHTS = ("norm",)
MAMBA_WEIGHTS = ("in_proj", "conv", "conv_bias", "dt_bias", "a_log", "d",
                 "ssm_norm", "out_proj")
EXPERT_WEIGHTS = ("router", "router_bias", "w_up", "w_down", "shared_up",
                  "shared_down")
ATTENTION_WEIGHTS = ("q", "k", "v", "o")
KIND_WEIGHTS = {"M": MAMBA_WEIGHTS, "E": EXPERT_WEIGHTS,
                "*": ATTENTION_WEIGHTS}

#: query rows a block of attention: [heads, 512, S] f32 scores at a time
QUERY_BLOCK = 512


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _round(x, dtype):
    """``x`` rounded to ``dtype`` and back to f32.  To bf16 by
    ``reduce_precision`` and not by a pair of ``astype``, which XLA may drop
    (``xla_allow_excess_precision``); the fp8 types have another exponent
    range and subnormals of their own, so they go through the type itself."""
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    if info.nexp == jnp.finfo(jnp.float32).nexp:
        return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                        mantissa_bits=info.nmant)
    return x.astype(dtype).astype(jnp.float32)


def _mm(a, b, dtype=None):
    return _round(a, dtype) @ _round(b, dtype)


def _relu2(t):
    return jnp.square(jax.nn.relu(t))


def _renormalise(top):
    """``norm_topk_prob``: a token's chosen weights sum to 1 (before the
    scaling factor)."""
    return top / (jnp.sum(top, -1, keepdims=True) + 1e-20)


def _selection(scores, bias):
    """What the experts are chosen by: the scores plus the bias."""
    return scores + bias


def attention(a, w, c, mm):
    """The attention mixer on normed input ``a [B, S, H]``."""
    B, S, _ = a.shape
    nh, nkv, d = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    # query head h reads KV head h // (nh / nkv)
    q = mm(a, w["q"]).reshape(B, S, nkv, nh // nkv, d)
    k = mm(a, w["k"]).reshape(B, S, nkv, d)
    v = mm(a, w["v"]).reshape(B, S, nkv, d)
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
    return mm(o, w["o"])


def causal_conv(x, w, b):
    """Depthwise causal convolution over the sequence with a bias, then
    SiLU: ``x [B, S, C]``, ``w [K, C]``: ``y_t = b + sum_j w[j] x[t - (K - 1)
    + j]``, zeros before the first position (HF's Conv1d weight ``[C, 1, K]``
    transposed)."""
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, j:j + S] * w[j] for j in range(K)) + b)


def ssm_recurrence(x, dt, A, B, C, state_dtype=None, inputs=None):
    """The state-space recurrence one position at a time: ``x [b, T, h, p]``,
    ``dt [b, T, h]`` (after its softplus), ``A [h]``, ``B, C [b, T, g, n]``
    -> ``(y [b, T, h, p] without the skip, the last state [b, h, p, n])``.
    The state is f32 (``state_dtype``: the type it is rounded to after every
    position; ``inputs``: the type ``dt x``, ``B`` and ``C`` are rounded to
    before their products)."""
    b, T, h, p = x.shape
    g, n = B.shape[2:]
    r = h // g
    xdt = _round(x * dt[..., None], inputs).reshape(b, T, g, r, p)
    # exp(dt A) S as S + expm1(dt A) S: a slow head's decay is 1 - 1e-4, and
    # what an exp is off by near 1 adds up over the 10,000 positions such a
    # head remembers (on a v5e 5e-3 of the last state, PR 33; the loss of a
    # model at its initial steps does not see it)
    forget = jnp.expm1(dt * A).reshape(b, T, g, r)
    B, C = _round(B, inputs), _round(C, inputs)

    def step(S, t):
        xdt_t, forget_t, B_t, C_t = t
        S = S + S * forget_t[..., None, None] + (
            xdt_t[..., :, None] * B_t[:, :, None, None, :])
        S = _round(S, state_dtype)
        return S, jnp.einsum("bgrpn,bgn->bgrp", _round(S, inputs), C_t)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xdt, forget, B, C))
    last, y = jax.lax.scan(step, jnp.zeros((b, g, r, p, n), jnp.float32), xs)
    return (jnp.moveaxis(y, 0, 1).reshape(b, T, h, p),
            last.reshape(b, h, p, n))


def mamba(a, w, c, mm, state_dtype=None, matmul_inputs=None):
    """The Mamba-2 mixer on normed input ``a [B, S, H]``."""
    B, S, _ = a.shape
    h, p = c["mamba_num_heads"], c["mamba_head_dim"]
    g, n = c["n_groups"], c["ssm_state_size"]
    d = h * p
    zxbcdt = mm(a, w["in_proj"])
    z, xbc, dt = (zxbcdt[..., :d], zxbcdt[..., d:2 * d + 2 * g * n],
                  zxbcdt[..., 2 * d + 2 * g * n:])
    xbc = causal_conv(xbc, w["conv"], w["conv_bias"])
    x = xbc[..., :d].reshape(B, S, h, p)
    Bm = xbc[..., d:d + g * n].reshape(B, S, g, n)
    Cm = xbc[..., d + g * n:].reshape(B, S, g, n)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    y, _ = ssm_recurrence(x, dt, -jnp.exp(w["a_log"]), Bm, Cm, state_dtype,
                          matmul_inputs)
    y = (y + w["d"][:, None] * x).reshape(B, S, d) * jax.nn.silu(z)
    y = y.reshape(B, S, g, d // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + c["layer_norm_epsilon"])
    return mm(y.reshape(B, S, d) * w["ssm_norm"], w["out_proj"])


def router(h, w_r, bias, c, matmul_inputs=None):
    """``(probs, chosen [T, k], weight [T, E])``: the sigmoid scores over
    all experts normalised to sum to 1 (what LBL averages), each token's
    ``k`` largest of score + bias (ties to the lower index) and the chosen
    scores renormalised and scaled, laid out by expert."""
    scores = jax.nn.sigmoid(_mm(h, w_r, matmul_inputs))
    k = c["num_experts_per_tok"]
    chosen = jnp.argsort(-_selection(scores, bias), axis=-1,
                         stable=True)[:, :k]
    top = jnp.take_along_axis(scores, chosen, -1)
    if c["norm_topk_prob"]:
        top = _renormalise(top)
    top = top * c["routed_scaling_factor"]
    weight = jnp.sum(jax.nn.one_hot(chosen, scores.shape[-1], dtype=h.dtype)
                     * top[..., None], 1)
    return scores / jnp.sum(scores, -1, keepdims=True), chosen, weight


def moe(h, w, c, mm, held=None, matmul_inputs=None):
    """The expert layer on normed tokens ``h [T, H]``: ``(y, (probs,
    chosen))``.  With ``held`` the routed sum is over the held experts."""
    probs, chosen, weight = router(h, w["router"], w["router_bias"], c,
                                   matmul_inputs)
    if held is not None:
        weight = weight[:, held[0]:held[0] + held[1]]
    assert weight.shape[1] == w["w_up"].shape[0], (
        weight.shape, w["w_up"].shape)

    def ffn(w_up, w_down):
        return mm(_relu2(mm(h, w_up)), w_down)

    def expert(y, e):               # every held expert sees every token
        w_up, w_down, weight_e = e
        return y + weight_e[:, None] * ffn(w_up, w_down), None
    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        (w["w_up"], w["w_down"], weight.T))
    return y + ffn(w["shared_up"], w["shared_down"]), (probs, chosen)


def forward(params, c, input_ids, held=None, matmul_inputs=None,
            state_dtype=None):
    """``(logits [B S, V], per E block (probs [T, E], chosen [T, k]))``."""
    def mm(a, b):
        return _mm(a, b, matmul_inputs)

    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        B, S = input_ids.shape
        H, eps = c["hidden_size"], c["layer_norm_epsilon"]
        x = p["embed"][input_ids]
        routed = []
        for i, kind in enumerate(c["hybrid_override_pattern"]):
            w = {k[len(f"layers.{i}."):]: v for k, v in p.items()
                 if k.startswith(f"layers.{i}.")}
            a = _norm(x, w["norm"], eps)
            if kind == "M":
                x = x + mamba(a, w, c, mm, state_dtype, matmul_inputs)
            elif kind == "*":
                x = x + attention(a, w, c, mm)
            else:
                y, r = moe(a.reshape(B * S, H), w, c, mm, held,
                           matmul_inputs)
                routed.append(r)
                x = x + y.reshape(B, S, H)
        x = _norm(x, p["norm"], eps).reshape(B * S, H)
        return mm(x, p["lm_head"]), routed


def loss_sums(params, c, input_ids, labels, held=None, matmul_inputs=None,
              state_dtype=None):
    """Sums over some sequences that chunks of a batch can add: ``ce`` (sum
    of the cross-entropy over positions with a label >= 0), ``n`` (their
    count), ``tokens``, and per E block ``load [E]`` (pairs at each of all
    routed experts) and ``prob [E]`` (sum over tokens of the normalised
    scores).  Also ``chosen``, per E block ``[T, k]``, for the comparison of
    routing."""
    logits, routed = forward(params, c, input_ids, held, matmul_inputs,
                             state_dtype)
    flat = jnp.asarray(labels).reshape(-1)
    valid = flat >= 0
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, flat, 0)[:, None],
                              -1)[:, 0]
    sums = {"ce": jnp.sum(ce * valid), "n": valid.sum(),
            "tokens": flat.shape[0]}
    if not routed:                  # a pattern without an E block
        k = c["num_experts_per_tok"]
        return dict(sums, load=jnp.ones((0, 1)), prob=jnp.zeros((0, 1)),
                    chosen=jnp.zeros((0, flat.shape[0], k), jnp.int32))
    E = routed[0][0].shape[-1]
    return dict(
        sums,
        load=jnp.stack([jnp.sum(jax.nn.one_hot(ch, E), (0, 1))
                        for _, ch in routed]),
        prob=jnp.stack([pr.sum(0) for pr, _ in routed]),
        chosen=jnp.stack([ch for _, ch in routed]))


def loss_from_sums(sums, lbl_weight):
    """``{"loss", "ce", "lbl"}`` from added-up ``loss_sums``."""
    T, E = sums["tokens"], sums["load"].shape[-1]
    ce = sums["ce"] / jnp.maximum(sums["n"], 1)
    share = sums["load"] / jnp.sum(sums["load"], -1, keepdims=True)
    lbl = jnp.sum(E * jnp.sum(share * sums["prob"] / T, -1))
    return {"loss": ce + lbl_weight * lbl, "ce": ce, "lbl": lbl}


def pretraining_loss(params, c, input_ids, labels, lbl_weight, held=None):
    """The loss of one batch taken whole (what the tests differentiate)."""
    sums = loss_sums(params, c, input_ids, labels, held)
    return loss_from_sums(sums, lbl_weight)["loss"]
