"""Plain reference of the Granite 4.0-H decoder (HF ``model_type``
``granitemoehybrid`` with ``num_local_experts`` 0;
``ibm-granite/granite-4.0-h-micro``, ``modeling_granitemoehybrid.py``) and
its pretraining loss.  Straight ``jax.numpy`` in float32 at the highest
matmul precision: the state-space recurrence one position at a time,
attention by blocks of query rows against all keys; no chunked scan, no
kernel.  Independent of ``hetu_tpu/models``, ``hetu_tpu/layers`` and
``hetu_tpu/ops``: it takes the weights under its own names (``WEIGHTS``
below; matrices are ``[in, out]``) and the configuration's published keys,
and nothing else.  The norm, the rounding, the depthwise convolution and
the recurrence are ``reference/nemotron_h.py``'s (plain functions of arrays:
the recurrence there already reads ``B`` and ``C`` by group, here one).

``H`` hidden size, ``eps`` ``rms_norm_eps``::

    N(x; w) = x / sqrt(mean(x^2) + eps) * w                     in f32
    h = embedding_multiplier * E[ids]
    layer i:  h = h + residual_multiplier * mixer_i(N(h; w1_i))
              h = h + residual_multiplier * W_d(silu(W_g n) * (W_u n)),
                                                  n = N(h; w2_i)
    logits = N(h; w) E^T / logits_scaling       (tie_word_embeddings)

    mamba (layer_types[i] == "mamba"; h heads of p channels, d = h p; g =
    mamba_n_groups groups, here 1: every head reads the same B and C; state
    n):
        [z | xBC | dt] = in_proj(x), widths d, d + 2 g n, h; no bias
        xBC = silu(conv(xBC) + b): depthwise causal convolution of width
            mamba_d_conv, left padding
        dt = softplus(dt + dt_bias) (not clamped);  A = -exp(A_log)
        per head, S_0 = 0 [p, n], for t = 1..T:
            S = exp(dt_t A) S + dt_t x_t B_t^T;  y_t = S C_t + D x_t
        y = y silu(z);  y = y / sqrt(mean over each group's d / g channels
            of y^2 + eps) * w_n;  out_proj: d -> H
    attention: q: H -> heads d, k, v: H -> kv_heads d; no position encoding
        (position_embedding_type nope), no bias; causal softmax of q k^T *
        attention_multiplier (1/64 published: NOT d^-1/2), each KV head
        serving heads / kv_heads query heads; o: heads d -> H
    loss: mean cross-entropy over labelled positions

Departures, each on purpose:

* HF's ``shared_mlp.input_linear`` is one ``[2 I, H]`` matrix; its two
  halves are given apart (``mlp_gate``, ``mlp_up``), as the program holds
  them, so that the reference reads the program's f32 masters in place and
  no second copy of the weights is made beside the optimiser's state.
* The vocabulary may be a slice: ids, logits and the loss are over the rows
  of ``embed`` that are given; the head is the same rows.
* ``mamba_chunk_size`` is not read: the recurrence has no chunk.

``matmul_inputs`` (default None: plain f32) rounds both operands of every
matrix product to that type before multiplying in f32, and ``state_dtype``
carries the state-space state in that type from position to position: the
reference "at a lower precision", used on the chip to see which gaps a lower
precision than the configuration's would open (the traffic file's tolerances
lie below them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.nemotron_h import (  # noqa: F401
    QUERY_BLOCK, _mm, _norm, _round, causal_conv, ssm_recurrence)

#: the weights ``forward`` reads: the model's, then per layer ``layers.<i>.``
#: its two norms, its MLP and those of its mixer.  ``lm_head [H, V]`` may be
#: given beside them: the head then reads it and not ``embed`` (an untied
#: head; the tests split the tied matrix's gradient into its two uses by it)
WEIGHTS = ("embed", "norm")
LAYER_WEIGHTS = ("input_norm", "post_norm", "mlp_gate", "mlp_up", "mlp_down")
MIXER_WEIGHTS = {
    "mamba": ("in_proj", "conv", "conv_bias", "dt_bias", "a_log", "d",
              "ssm_norm", "out_proj"),
    "attention": ("q", "k", "v", "o")}


def attention(a, w, c, mm):
    """The attention mixer on normed input ``a [B, S, H]``."""
    B, S, H = a.shape
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    d = H // nh
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
               k.transpose(0, 2, 3, 1)[:, :, None]
               ) * c["attention_multiplier"]
        seen = (lo + jnp.arange(block))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return mm(p, v.transpose(0, 2, 1, 3)[:, :, None])  # [B,kv,g,bq,d]
    o = jax.lax.map(rows, jnp.arange(0, S, block))         # [n,B,kv,g,bq,d]
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(B, S, nh * d)
    return mm(o, w["o"])


def mamba(a, w, c, mm, state_dtype=None, matmul_inputs=None):
    """The Mamba-2 mixer on normed input ``a [B, S, H]``."""
    B, S, _ = a.shape
    h, p = c["mamba_n_heads"], c["mamba_d_head"]
    g, n = c["mamba_n_groups"], c["mamba_d_state"]
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
                          + c["rms_norm_eps"])
    return mm(y.reshape(B, S, d) * w["ssm_norm"], w["out_proj"])


def mlp(n, w, mm):
    return mm(jax.nn.silu(mm(n, w["mlp_gate"])) * mm(n, w["mlp_up"]),
              w["mlp_down"])


def forward(params, c, input_ids, matmul_inputs=None, state_dtype=None):
    """Logits ``[B S, V]`` over the rows of ``embed`` given."""
    def mm(a, b):
        return _mm(a, b, matmul_inputs)

    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        B, S = input_ids.shape
        eps, res = c["rms_norm_eps"], c["residual_multiplier"]
        x = c["embedding_multiplier"] * p["embed"][input_ids]
        for i, kind in enumerate(c["layer_types"]):
            w = {k[len(f"layers.{i}."):]: v for k, v in p.items()
                 if k.startswith(f"layers.{i}.")}
            a = _norm(x, w["input_norm"], eps)
            x = x + res * (
                mamba(a, w, c, mm, state_dtype, matmul_inputs)
                if kind == "mamba" else attention(a, w, c, mm))
            x = x + res * mlp(_norm(x, w["post_norm"], eps), w, mm)
        x = _norm(x, p["norm"], eps).reshape(B * S, -1)
        head = p["lm_head"] if "lm_head" in p else p["embed"].T
        return mm(x, head) / c["logits_scaling"]


def loss_sums(params, c, input_ids, labels, matmul_inputs=None,
              state_dtype=None, keep_logits=False):
    """Sums over some sequences that chunks of a batch can add: ``ce`` (sum
    of the cross-entropy over positions with a label >= 0) and ``n`` (their
    count); with ``keep_logits`` the ``logits`` they came from, too."""
    logits = forward(params, c, input_ids, matmul_inputs, state_dtype)
    flat = jnp.asarray(labels).reshape(-1)
    valid = flat >= 0
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, flat, 0)[:, None],
                              -1)[:, 0]
    sums = {"ce": jnp.sum(ce * valid), "n": valid.sum()}
    return dict(sums, logits=logits) if keep_logits else sums


def loss_from_sums(sums):
    """``{"loss", "ce"}`` from added-up ``loss_sums``: the loss has the one
    term."""
    ce = sums["ce"] / jnp.maximum(sums["n"], 1)
    return {"loss": ce, "ce": ce}


def pretraining_loss(params, c, input_ids, labels):
    """The loss of one batch taken whole (what the tests differentiate)."""
    return loss_from_sums(loss_sums(params, c, input_ids, labels))["loss"]
