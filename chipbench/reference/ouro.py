"""Plain reference of the Ouro looped decoder (HF ``model_type`` ``ouro``,
``ByteDance/Ouro-2.6B``, ``modeling_ouro.py``; the LoopLM paper,
arXiv:2510.25741) and its pretraining loss.  Straight ``jax.numpy`` in float32
at the highest matmul precision: attention by blocks of query rows against
all keys, no kernel, no recomputation.  Independent of ``hetu_tpu/models``,
``hetu_tpu/layers`` and ``hetu_tpu/ops``: it takes the weights under its own
names (``WEIGHTS`` below; matrices are ``[in, out]``) and the configuration's
published keys, and nothing else.  The rounding is ``reference/nemotron_h.py``'s
(a plain function of arrays).

``H`` hidden size, ``eps`` ``rms_norm_eps``, ``P`` ``total_ut_steps``, ``k``
``num_hidden_layers``::

    N(x; w) = x / sqrt(mean(x^2) + eps) * w                      in f32
    layer:  a = x + N(Attn(N(x; n1)); n2);  y = a + N(MLP(N(a; n3)); n4)
            Attn: q, k, v: H -> heads d, no bias; rotary over the whole head
            (half-split, rope_theta) on q and k; causal softmax of
            q k^T / sqrt(d); o: heads d -> H
            MLP(h) = W_down(silu(W_gate h) * (W_up h))
    h_0 = E[ids];  h_t = N(Layers(h_{t-1}); w),  t = 1..P: the SAME k layers
            and the SAME final norm every pass, the normed state fed back
    exit:   lambda_t = sigmoid(w_g . h_t + b_g)
            p_t = lambda_t prod_{j<t} (1 - lambda_j),  t < P
            p_P = prod_{j<P} (1 - lambda_j)      (lambda_P is not read)
    loss:   ce_t = CE(W_head h_t, label)
            L = mean_i sum_t p_t(i) ce_t(i) - beta mean_i H(p(i)),
            H(p) = - sum_t p_t log p_t; means over labelled positions;
            nothing detached

Departures from the published description, each marked (a) where the line
is an assumption (there is no network here; the configuration file lists
them under ``assumed``):

* (a) no attention bias (the published config has no ``attention_bias`` key);
* (a) the final norm is applied inside the loop and its output fed back (HF
  ``modeling_ouro.py``, as recalled);
* (a) ``beta`` is the caller's (0.05 in the configuration file);
* ``early_exit_threshold`` is an inference key and is not read.

For the readings that set the traffic file's limits, pieces can be LEFT OUT
(``leave_out``, a set of names; default none) and operands rounded:

* ``"post_norms"``: ``n2`` and ``n4`` skipped;
* ``"fed_norm"``: the final norm read by the head and the gate but NOT fed
  back, ``h_t = Layers(h_{t-1})`` un-normed;
* ``"last_takes_rest"``: ``p_P = lambda_P prod_{j<P} (1 - lambda_j)``;
* ``"entropy"``: ``beta = 0``;
* ``passes`` (an argument): fewer passes than ``total_ut_steps``;
* ``matmul_inputs`` rounds both operands of every matrix product to that
  type before multiplying in f32 (the reference "at a lower precision").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference.nemotron_h import _mm, _norm  # noqa: F401

QUERY_BLOCK = 512

#: the weights ``forward`` reads: the model's, then per layer ``layers.<i>.``
WEIGHTS = ("embed", "norm", "lm_head", "gate_w", "gate_b")
LAYER_WEIGHTS = ("n1", "n2", "n3", "n4", "q", "k", "v", "o", "mlp_gate",
                 "mlp_up", "mlp_down")


def _rope(x, theta):
    """Half-split rotary over the whole head on ``[B, S, heads, d]``,
    positions from 0."""
    S, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(a, w, c, mm):
    """Causal multi-head attention on normed input ``a [B, S, H]``, a block
    of query rows at a time against all keys."""
    B, S, H = a.shape
    nh = c["num_attention_heads"]
    assert c["num_key_value_heads"] == nh
    d = c.get("head_dim") or H // nh
    q = _rope(mm(a, w["q"]).reshape(B, S, nh, d), c["rope_theta"])
    k = _rope(mm(a, w["k"]).reshape(B, S, nh, d), c["rope_theta"])
    v = mm(a, w["v"]).reshape(B, S, nh, d)
    pos = jnp.arange(S)
    block = min(QUERY_BLOCK, S)
    assert S % block == 0, (S, block)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, block, axis=1)
        s = mm(qb.transpose(0, 2, 1, 3),                    # [B, h, bq, d]
               k.transpose(0, 2, 3, 1)) / jnp.sqrt(float(d))
        seen = (lo + jnp.arange(block))[:, None] >= pos[None, :]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return mm(p, v.transpose(0, 2, 1, 3))               # [B, h, bq, d]
    o = jax.lax.map(rows, jnp.arange(0, S, block))          # [n, B, h, bq, d]
    o = o.transpose(1, 0, 3, 2, 4).reshape(B, S, nh * d)
    return mm(o, w["o"])


def mlp(n, w, mm):
    return mm(jax.nn.silu(mm(n, w["mlp_gate"])) * mm(n, w["mlp_up"]),
              w["mlp_down"])


def layer(x, w, c, matmul_inputs=None, leave_out=()):
    """One application of one layer on ``x [B, S, H]`` (f32); ``w`` the
    layer's weights under ``LAYER_WEIGHTS``."""
    def mm(a, b):
        return _mm(a, b, matmul_inputs)
    eps = c["rms_norm_eps"]
    sandwich = "post_norms" not in leave_out
    with jax.default_matmul_precision("highest"):
        w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
        y = attention(_norm(x, w["n1"], eps), w, c, mm)
        x = x + (_norm(y, w["n2"], eps) if sandwich else y)
        y = mlp(_norm(x, w["n3"], eps), w, mm)
        return x + (_norm(y, w["n4"], eps) if sandwich else y)


def layer_weights(params, i):
    lead = f"layers.{i}."
    return {k[len(lead):]: v for k, v in params.items()
            if k.startswith(lead)}


def forward(params, c, input_ids, passes=None, matmul_inputs=None,
            leave_out=(), layer_of=None):
    """The normed states ``[h_1 .. h_P]``, each ``[B, S, H]`` f32.
    ``layer_of(t, i)`` names the weights pass ``t`` reads for its ``i``-th
    layer (default ``i``: one set of weights for every pass; the tests'
    untied twin gives ``t k + i``)."""
    k = c["num_hidden_layers"]
    layer_of = layer_of or (lambda t, i: i)
    x = jnp.asarray(params["embed"], jnp.float32)[input_ids]
    scale = jnp.asarray(params["norm"], jnp.float32)
    states = []
    for t in range(passes or c["total_ut_steps"]):
        for i in range(k):
            x = layer(x, layer_weights(params, layer_of(t, i)), c,
                      matmul_inputs, leave_out)
        h = _norm(x, scale, c["rms_norm_eps"])
        states.append(h)
        if "fed_norm" not in leave_out:
            x = h                       # (a) the normed state is fed back
    return states


def gate(h, params, matmul_inputs=None):
    """The gate's pre-activation ``[T]`` on ``h [T, H]``."""
    with jax.default_matmul_precision("highest"):
        z = _mm(h, jnp.asarray(params["gate_w"], jnp.float32), matmul_inputs)
        return z[:, 0] + jnp.asarray(params["gate_b"], jnp.float32)[0]


def head(h, params, labels, matmul_inputs=None):
    """``(logits [T, V], ce [T])`` of ``h [T, H]``; ``ce`` 0 where the
    label is negative."""
    with jax.default_matmul_precision("highest"):
        logits = _mm(h, jnp.asarray(params["lm_head"], jnp.float32),
                     matmul_inputs)
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[:, None],
                              -1)[:, 0]
    return logits, jnp.where(valid, ce, 0.0)


def exit_distribution(z, leave_out=()):
    """``p [P, T]`` from the gate's pre-activations ``z [P, T]``."""
    lam = jax.nn.sigmoid(z)
    stay = jnp.cumprod(1.0 - lam, axis=0)                   # prod_{j<=t}
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]])
    p = lam * before
    if "last_takes_rest" in leave_out:
        return p
    return jnp.concatenate([p[:-1], before[-1:]])


def finish(p, ce, labels, beta, leave_out=()):
    """``{"loss", "ce", "entropy", "shares"}`` from ``p [P, T]``, the
    passes' cross-entropies ``ce [P, T]`` and the flat labels."""
    valid = (labels >= 0).astype(jnp.float32)
    n = jnp.maximum(valid.sum(), 1.0)
    expected = jnp.sum(jnp.sum(p * ce, 0) * valid) / n
    h = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                           0.0), 0)
    entropy = jnp.sum(h * valid) / n
    if "entropy" in leave_out:
        beta = 0.0
    return {"loss": expected - beta * entropy, "ce": expected,
            "entropy": entropy, "shares": jnp.sum(p * valid, 1) / n}


def loss_parts(params, c, input_ids, labels, beta, passes=None,
               matmul_inputs=None, leave_out=(), layer_of=None):
    """``finish``'s terms and, beside them, ``p [P, T]`` and ``logits``
    (``P`` arrays ``[T, V]``) of one batch taken whole."""
    flat = jnp.asarray(labels).reshape(-1)
    states = forward(params, c, input_ids, passes, matmul_inputs, leave_out,
                     layer_of)
    H = states[0].shape[-1]
    zs, ces, logits = [], [], []
    for h in states:
        h = h.reshape(-1, H)
        zs.append(gate(h, params, matmul_inputs))
        out, ce = head(h, params, flat, matmul_inputs)
        logits.append(out)
        ces.append(ce)
    p = exit_distribution(jnp.stack(zs), leave_out)
    return dict(finish(p, jnp.stack(ces), flat, beta, leave_out), p=p,
                logits=logits)


def pretraining_loss(params, c, input_ids, labels, beta, **kwargs):
    """The loss of one batch taken whole (what the tests differentiate)."""
    return loss_parts(params, c, input_ids, labels, beta, **kwargs)["loss"]
