"""Plain reference of the OLMoE decoder (Muennighoff et al. 2024,
arXiv:2409.02060; the ``allenai/OLMoE-1B-7B-0125`` layout, HF ``model_type``
``olmoe``) and its pretraining loss.  Straight ``jax.numpy`` in float32 at
the highest matmul precision: every expert is computed for every token and
masked by the top-k weights; no sort, no grouped product, no kernel.
Independent of ``hetu_tpu/models`` and ``hetu_tpu/layers``: it takes the
weights under its own names (``WEIGHTS`` below; matrices are ``[in, out]``,
experts stacked on a leading axis) and nothing else.

The block, as published::

    h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    final RMSNorm, untied head, no bias anywhere

    Attn: q = RMSNorm_q(W_q x), k = RMSNorm_k(W_k x) over all hidden
          channels (before the head split), v = W_v x; heads of
          hidden / num_attention_heads; RoPE (rotate-half, rope_theta) on q
          and k; causal softmax attention scaled by head_dim^-1/2; W_o.
    MoE:  p = softmax(W_g h) over the experts in f32; the
          num_experts_per_tok largest p and their experts (ties to the
          lower index); weights NOT renormalised (norm_topk_prob false);
          y = sum_k p_k W_down,e_k( silu(W_gate,e_k h) * W_up,e_k h ).
          No token is dropped, ever.
    loss: mean cross-entropy over labelled positions
          + lbl_weight * sum_layers LBL + z_weight * sum_layers Z,
          LBL = E sum_i (n_i / T) mean_t p_t,i with n_i the (token, choice)
          pairs at expert i (the form of HF load_balancing_loss_func),
          Z = mean_t (logsumexp_i (W_g h)_t,i)^2.

Departures: none in the mathematics.  ``clip_qkv`` is null in the
published config and is not implemented.  The loss weights (0.01, 0.001)
are the paper's, not ``config.json``'s; the configuration file lists them
under ``assumed`` and the caller passes them.

``matmul_inputs`` (default None: plain f32) rounds both operands of every
matrix product to that type before multiplying in f32: the reference "at a
lower compute precision", used once on the chip to see which gaps a lower
precision than the configuration's would open (the traffic file's
tolerances lie below them; PERF.md section 6, PR 26).

``LBL`` is a statistic of the whole batch, so ``loss_sums`` returns sums
that chunks of sequences can add and ``loss_from_sums`` finishes them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


#: the weights ``forward`` reads: the model's, then per layer ``layers.<i>.``
WEIGHTS = ("embed", "norm", "lm_head")
LAYER_WEIGHTS = ("input_norm", "q", "k", "v", "o", "q_norm", "k_norm",
                 "post_norm", "router", "w_gate", "w_up", "w_down")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate-half RoPE on ``[B, heads, S, d]``, positions from 0."""
    d, S = x.shape[-1], x.shape[-2]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _mm(a, b, dtype=None):
    if dtype is not None:
        a = a.astype(dtype).astype(jnp.float32)
        b = b.astype(dtype).astype(jnp.float32)
    return a @ b


def router(h, w_g, k, matmul_inputs=None):
    """``(logits, probs, chosen [T, k], weight [T, E])``: the softmax over
    experts, each token's ``k`` largest (ties to the lower index) and their
    probabilities laid out by expert, zero elsewhere."""
    logits = _mm(h, w_g, matmul_inputs)
    probs = jax.nn.softmax(logits, -1)
    chosen = jnp.argsort(-probs, axis=-1, stable=True)[:, :k]
    weight = jnp.sum(jax.nn.one_hot(chosen, probs.shape[-1], dtype=h.dtype)
                     * jnp.take_along_axis(probs, chosen, -1)[..., None], 1)
    return logits, probs, chosen, weight


def forward(params, c, input_ids, matmul_inputs=None):
    """``(logits [B S, V], per layer (router logits, probs, chosen))``."""
    def mm(a, b):
        return _mm(a, b, matmul_inputs)

    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        B, S = input_ids.shape
        H, nh = c["hidden_size"], c["num_attention_heads"]
        k = c["num_experts_per_tok"]
        eps, hd = c["rms_norm_eps"], H // nh
        x = p["embed"][input_ids]
        causal = jnp.tril(jnp.ones((S, S), bool))
        routed = []
        for i in range(c["num_hidden_layers"]):
            L = f"layers.{i}"
            a = _rms(x, p[f"{L}.input_norm"], eps)

            def heads(t):
                return t.reshape(B, S, nh, hd).transpose(0, 2, 1, 3)
            q = heads(_rms(mm(a, p[f"{L}.q"]), p[f"{L}.q_norm"], eps))
            kk = heads(_rms(mm(a, p[f"{L}.k"]), p[f"{L}.k_norm"], eps))
            v = heads(mm(a, p[f"{L}.v"]))
            q, kk = _rope(q, c["rope_theta"]), _rope(kk, c["rope_theta"])
            s = mm(q, kk.transpose(0, 1, 3, 2)) / jnp.sqrt(float(hd))
            s = jnp.where(causal, s, -jnp.inf)
            o = mm(jax.nn.softmax(s, -1), v).transpose(0, 2, 1, 3)
            x = x + mm(o.reshape(B, S, H), p[f"{L}.o"])

            h = _rms(x, p[f"{L}.post_norm"], eps).reshape(B * S, H)
            logits, probs, chosen, weight = router(h, p[f"{L}.router"], k,
                                                   matmul_inputs)
            routed.append((logits, probs, chosen))

            def expert(y, e):       # every expert sees every token
                w_gate, w_up, w_down, weight_e = e
                act = jax.nn.silu(mm(h, w_gate)) * mm(h, w_up)
                return y + weight_e[:, None] * mm(act, w_down), None
            y, _ = jax.lax.scan(expert, jnp.zeros_like(h), (
                p[f"{L}.w_gate"], p[f"{L}.w_up"], p[f"{L}.w_down"],
                weight.T))
            x = x + y.reshape(B, S, H)
        x = _rms(x, p["norm"], eps).reshape(B * S, H)
        return mm(x, p["lm_head"]), routed


def loss_sums(params, c, input_ids, labels, matmul_inputs=None):
    """Sums over some sequences that chunks of a batch can add: ``ce`` (sum
    of the cross-entropy over positions with a label >= 0), ``n`` (their
    count), ``tokens``, and per layer ``load [E]`` (pairs at each expert),
    ``prob [E]`` (sum over tokens of the router's probabilities) and ``z``
    (sum of squared logsumexp).  Also ``chosen``, per layer ``[T, k]``, for
    the comparison of routing."""
    logits, routed = forward(params, c, input_ids, matmul_inputs)
    flat = jnp.asarray(labels).reshape(-1)
    valid = flat >= 0
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, flat, 0)[:, None],
                              -1)[:, 0]
    E = c["num_experts"]
    return {
        "ce": jnp.sum(ce * valid), "n": valid.sum(),
        "tokens": flat.shape[0],
        "load": jnp.stack([jnp.sum(jax.nn.one_hot(ch, E), (0, 1))
                           for _, _, ch in routed]),
        "prob": jnp.stack([pr.sum(0) for _, pr, _ in routed]),
        "z": jnp.stack([jnp.sum(jax.nn.logsumexp(lg, -1) ** 2)
                        for lg, _, _ in routed]),
        "chosen": jnp.stack([ch for _, _, ch in routed])}


def loss_from_sums(sums, c, lbl_weight, z_weight):
    """``{"loss", "ce", "lbl", "z"}`` from added-up ``loss_sums``."""
    T, E = sums["tokens"], c["num_experts"]
    ce = sums["ce"] / jnp.maximum(sums["n"], 1)
    lbl = jnp.sum(E * jnp.sum(sums["load"] / T * sums["prob"] / T, -1))
    z = jnp.sum(sums["z"] / T)
    return {"loss": ce + lbl_weight * lbl + z_weight * z, "ce": ce,
            "lbl": lbl, "z": z}


def pretraining_loss(params, c, input_ids, labels, lbl_weight, z_weight):
    """The loss of one batch taken whole."""
    sums = loss_sums(params, c, input_ids, labels)
    return loss_from_sums(sums, c, lbl_weight, z_weight)["loss"]
