"""Plain reference of a SambaY decoder-hybrid-decoder's training pass
(``microsoft/Phi-4-mini-flash-reasoning``, ``model_type`` "phi4flash";
arXiv:2507.06607) and its loss.  Straight ``jax.numpy`` in float32 at the
highest matmul precision: the Mamba-1 recurrence one position at a time,
attention by blocks of query rows against all keys under a dense boolean mask,
two softmaxes a query pair written out; no kernel, no chunking of the scan,
no recomputation.  Independent of ``hetu_tpu/models``, ``hetu_tpu/layers`` and
``hetu_tpu/ops``: it takes the weights under its own names (``WEIGHTS`` below;
matrices are ``[in, out]``) and the configuration file's keys, and nothing
else.

Hidden ``d``; ``LN(x; w, b) = (x - mean) / sqrt(var + eps) * w + b`` with
``eps = layer_norm_eps``; no dropout; no position encoding.  Layer ``l`` (its
PUBLISHED index, ``first_layer_index`` + its place in the run that is built)::

    x <- x + Mixer_l(LN(x; in));  x <- x + (up * silu(gate)) W_down,
                                  gate = LN(x; post) W_gate, up = .. W_up

then ``LN(x; norm)``, logits on the tied ``embed`` (its rows are the slice),
mean cross-entropy over all positions.  With ``h = published layers / 2``:

* even ``l <= h``, **Mamba-1** (``d_in = mamba_expand d``, ``N``
  ``mamba_d_state``, ``R`` ``mamba_dt_rank``, ``K`` ``mamba_d_conv`` taps):
  ``[x | z] = u W_in``; ``xc_t = silu(sum_j w_j x_(t - K + 1 + j) + b_c)``;
  ``[r | B | C] = xc W_x``; ``Delta = softplus(r W_dt + b_dt)``; ``A =
  -exp(A_log)``; ``h_t = exp(Delta_t A) h_(t-1) + (Delta_t xc_t) B_t`` from
  zero (written ``h + expm1(Delta_t A) h``: the chip's ``exp`` near 1 is a
  few parts in ten million off, and a product of thousands of them drifts);
  ``y_t = h_t C_t + D xc_t``; ``out = (y silu(z)) W_out``.  Layer ``h`` hands
  out ``M = y``.
* odd ``l < h`` **differential attention over the last ``sliding_window``
  keys** (the position's own among them), ``l = h + 1`` **over all earlier
  keys**: ``[q | k | v] = u W_qkv + b``; query heads ``(2i, 2i+1)`` are the
  pair ``(q1_i, q2_i)``, key heads ``(2j, 2j+1)`` ``(k1_j, k2_j)``, ``V_j =
  [v_2j | v_(2j+1)]``, ``j = i // (H / KV)``; ``A^c_i = softmax(q^c_i (k^c_j)^T
  / sqrt(head) + mask) V_j``; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init(l)``, ``lambda_init(l) = 0.8 - 0.6 exp(-0.3 l)``; ``O_i = (1 -
  lambda_init(l)) RMSNorm(A^1_i - lambda A^2_i; gamma)``; ``out = [O_0 | ..]
  W_o + b_o``.  Layer ``h + 1`` hands out its ``k`` and ``v``.
* even ``l > h`` **GMU**: ``out = (M * silu(u W_g)) W_o``.
* odd ``l > h + 1`` **cross**: ``q = u W_q + b_q``; ``k``, ``v`` layer ``h +
  1``'s; the same differential form with the layer's own lambdas and gamma.

Departures from the published description, each on purpose:

* The vocabulary may be a slice: ids, logits and the loss are over the rows
  of ``embed`` that are given.
* A run of consecutive layers is built, under their published indices.

``matmul_inputs`` (default None: plain f32) rounds both operands of every
matrix product to that type before multiplying in f32; ``without`` changes one
piece (``CONTROLS``): the reference "at a lower precision" or "with a piece
changed", used on the chip to see which gaps each would open (the traffic
file's tolerances lie below them).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .ling3 import _mm

#: the weights ``forward`` reads: the model's, then per layer ``layers.<i>.``
#: (``i`` the layer's place in the run), by the layer's kind
WEIGHTS = ("embed", "norm", "norm_bias")
LAYER_WEIGHTS = {
    "every": ("input_norm", "input_norm_bias", "post_norm", "post_norm_bias",
              "mlp_gate", "mlp_up", "mlp_down"),
    "mamba": ("in_proj", "conv", "conv_bias", "x_proj", "dt_proj", "dt_bias",
              "a_log", "d", "out_proj"),
    "attention": ("qkv", "qkv_bias", "o", "o_bias", "lq1", "lk1", "lq2",
                  "lk2", "subln"),
    "gmu": ("in_proj", "out_proj"),
}

#: query rows a block of attention, rows a block of an MLP
QUERY_BLOCK, ROW_BLOCK = 256, 2048

#: what ``without`` may name, and what each changes
CONTROLS = {
    "bf16_state": "the scan's state carried in bf16 between positions",
    "subtract": "A^2 not subtracted: one softmax a pair",
    "lambda_index": "lambda_init at the layer's place in the run (1, 3, 5) "
                    "where the published index (15, 17, 19) belongs",
    "sub_norm": "no RMSNorm on a pair's difference",
    "memory_after_gate": "M taken after the gate y silu(z)",
    "own_kv": "the cross layer on a projection of K and V of its own "
              "(seeded, N(0, 0.02))",
    "window_511": "a window of 511 keys",
    "window_513": "a window of 513 keys",
    "memory_skip": "the memory's D xc skip left out",
}


def kind_of(c, index):
    """The mixer of the layer with the PUBLISHED ``index``."""
    half = c["deployment"]["num_hidden_layers"] // 2
    if index % c["mb_per_layer"] == 0:
        return "mamba" if index <= half else "gmu"
    if index < half:
        return "window"
    return "full" if index == half + 1 else "cross"


def lambda_init(index):
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def in_blocks(fn, x, rows):
    """``fn`` over ``x [B, S, .]`` in blocks of ``rows`` positions."""
    B, S, _ = x.shape
    rows = min(rows, S)
    if S % rows:
        return fn(x)
    out = jax.lax.map(fn, x.reshape(B, S // rows, rows, -1).swapaxes(0, 1))
    return out.swapaxes(0, 1).reshape(B, S, -1)


def mlp(u, w, mm):
    def rows(t):
        return mm(jax.nn.silu(mm(t, w["mlp_gate"])) * mm(t, w["mlp_up"]),
                  w["mlp_down"])
    return in_blocks(rows, u, ROW_BLOCK)


def recurrence(xc, delta, A, Bm, Cm, state_dtype=jnp.float32):
    """``y [B, S, C]``: the selective scan one position at a time, without
    the skip; the state ``[B, C, N]`` carried in ``state_dtype``."""
    def step(h, t):
        x_t, d_t, b_t, c_t = t
        h = h.astype(jnp.float32)
        h = h + jnp.expm1(d_t[..., None] * A) * h + (
            (d_t * x_t)[..., None] * b_t[:, None, :])
        return h.astype(state_dtype), jnp.sum(h * c_t[:, None, :], -1)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (xc, delta, Bm, Cm))
    h0 = jnp.zeros(xc.shape[:1] + A.shape, state_dtype)
    return jnp.moveaxis(jax.lax.scan(step, h0, xs)[1], 0, 1)


def mamba(u, w, c, mm, without=()):
    """``(out, M)``: the mixer's output and what it hands out."""
    a = c["assumed"]
    d_in = a["mamba_expand"] * c["hidden_size"]
    N, R, K = a["mamba_d_state"], a["mamba_dt_rank"], a["mamba_d_conv"]
    S = u.shape[1]
    xz = mm(u, w["in_proj"])
    x, z = xz[..., :d_in], xz[..., d_in:]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    xc = jax.nn.silu(sum(xp[:, j:j + S] * w["conv"][j] for j in range(K))
                     + w["conv_bias"])
    dbc = mm(xc, w["x_proj"])
    delta = jax.nn.softplus(mm(dbc[..., :R], w["dt_proj"]) + w["dt_bias"])
    scan = recurrence(
        xc, delta, -jnp.exp(w["a_log"]), dbc[..., R:R + N],
        dbc[..., R + N:R + 2 * N],
        jnp.bfloat16 if "bf16_state" in without else jnp.float32)
    y = scan + w["d"] * xc
    gated = y * jax.nn.silu(z)
    memory = (gated if "memory_after_gate" in without
              else scan if "memory_skip" in without else y)
    return mm(gated, w["out_proj"]), memory


def differential(q, k, v, w, c, index, window, mm, without=()):
    """The differential form on projected ``q [B, S, H d]``, ``k``, ``v [B, S,
    KV d]``: a layer's output before ``W_o``."""
    B, S, _ = q.shape
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"] // H
    q = q.reshape(B, S, H // 2, 2, d)
    k = k.reshape(B, S, KV // 2, 2, d)
    v = v.reshape(B, S, KV // 2, 2 * d)
    reads = jnp.arange(H // 2) // (H // KV)
    k, v = k[:, :, reads], v[:, :, reads]          # a key pair a query pair
    lam_init = lambda_init(index)
    lam = (jnp.exp(jnp.sum(w["lq1"] * w["lk1"]))
           - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + lam_init)
    rows_a_block = min(QUERY_BLOCK, S)
    assert S % rows_a_block == 0, (S, rows_a_block)
    keys = jnp.arange(S)

    def rows(lo):
        qb = jax.lax.dynamic_slice_in_dim(q, lo, rows_a_block, axis=1)
        at = lo + jnp.arange(rows_a_block)
        seen = keys[None, :] <= at[:, None]
        if window is not None:
            seen = seen & (at[:, None] - keys[None, :] < window)
        out = []
        for half in range(2):
            s = mm(qb[:, :, :, half].transpose(0, 2, 1, 3),   # [B, P, bq, d]
                   k[:, :, :, half].transpose(0, 2, 3, 1)) / math.sqrt(d)
            prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
            out.append(mm(prob, v.transpose(0, 2, 1, 3)))     # [B, P, bq, 2d]
        diff = out[0] if "subtract" in without else out[0] - lam * out[1]
        if "sub_norm" not in without:
            diff = diff * jax.lax.rsqrt(
                jnp.mean(diff * diff, -1, keepdims=True)
                + c["layer_norm_eps"]) * w["subln"]
        return (1.0 - lam_init) * diff
    o = jax.lax.map(rows, jnp.arange(0, S, rows_a_block))  # [n, B, P, bq, 2d]
    return o.transpose(1, 0, 3, 2, 4).reshape(B, S, H * d)


def attention(u, w, c, index, place, kind, mm, shared, without=(),
              window=None):
    """An attention layer's output; a full layer leaves its ``k`` and ``v``
    in ``shared``, a cross layer reads them there."""
    H, KV = c["num_attention_heads"], c["num_key_value_heads"]
    d = c["hidden_size"] // H
    qkv = mm(u, w["qkv"]) + w["qkv_bias"]
    q = qkv[..., :H * d]
    if kind == "cross":
        k, v = shared["k"], shared["v"]
        if "own_kv" in without:
            own = 0.02 * jax.random.normal(
                jax.random.PRNGKey(index), (u.shape[-1], 2 * KV * d))
            k, v = jnp.split(mm(u, own), 2, -1)
    else:
        k, v = qkv[..., H * d:(H + KV) * d], qkv[..., (H + KV) * d:]
        if kind == "full":
            shared["k"], shared["v"] = k, v
    o = differential(q, k, v, w, c,
                     place if "lambda_index" in without else index, window,
                     mm, without)
    return mm(o, w["o"]) + w["o_bias"]


def window_of(c, without=()):
    return c["sliding_window"] + ("window_513" in without) - (
        "window_511" in without)


def forward(params, c, input_ids, matmul_inputs=None, without=(), keep=(),
            edges=False):
    """``(hidden [B, S, d] after the final norm, kept)``: ``kept`` holds, of
    the names in ``keep``, ``memory`` (``M``), and the mixer's own output of
    the first ``window``, the ``full``, the first ``gmu`` and the first
    ``cross`` layer; with ``edges`` also ``edges``, the window layer's output
    at one key fewer and one key more."""
    assert set(without) <= set(CONTROLS), without

    def mm(a, b):
        return _mm(a, b, matmul_inputs)

    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
        eps = c["layer_norm_eps"]
        x = p["embed"][input_ids]
        shared, kept = {}, {}
        for place in range(c["num_hidden_layers"]):
            index = c["first_layer_index"] + place
            kind = kind_of(c, index)
            w = {k[len(f"layers.{place}."):]: v for k, v in p.items()
                 if k.startswith(f"layers.{place}.")}
            u = layer_norm(x, w["input_norm"], w["input_norm_bias"], eps)
            if kind == "mamba":
                out, memory = mamba(u, w, c, mm, without)
                if index == c["deployment"]["num_hidden_layers"] // 2:
                    shared["memory"] = memory
                    if "memory" in keep:
                        kept["memory"] = memory
            elif kind == "gmu":
                out = mm(shared["memory"] * jax.nn.silu(mm(u, w["in_proj"])),
                         w["out_proj"])
            else:
                window = window_of(c, without) if kind == "window" else None
                out = attention(u, w, c, index, place, kind, mm, shared,
                                without, window)
                if kind == "window" and edges and "edges" not in kept:
                    kept["edges"] = jnp.stack([
                        attention(u, w, c, index, place, kind, mm, shared,
                                  without, c["sliding_window"] + off)
                        for off in (-1, 1)])
            if kind in keep and kind not in kept:
                kept[kind] = out
            x = x + out
            x = x + mlp(layer_norm(x, w["post_norm"], w["post_norm_bias"],
                                   eps), w, mm)
        return layer_norm(x, p["norm"], p["norm_bias"], eps), kept, mm, p


def logits_of(hidden, embed, matmul_inputs=None):
    """The logits ``[rows, V]`` of final-normed ``hidden [rows, d]`` on the
    tied ``embed [V, d]``."""
    with jax.default_matmul_precision("highest"):
        return _mm(jnp.asarray(hidden, jnp.float32),
                   jnp.asarray(embed, jnp.float32).T, matmul_inputs)


def loss_sums(params, c, input_ids, labels, matmul_inputs=None, without=(),
              keep_logits=False, keep=(), edges=False, keep_hidden=False):
    """Sums over some sequences that chunks of a batch can add: ``ce`` (the
    sum of cross-entropy over positions with a label >= 0) and ``n`` (those
    positions).  With ``keep_logits`` the logits ``[B S, V]``, with
    ``keep_hidden`` the final norm's output ``[B S, d]`` (``logits_of`` makes
    the logits of some of its rows); what ``keep`` names as ``forward`` keeps
    it."""
    x, kept, _, p = forward(params, c, input_ids, matmul_inputs, without,
                            keep, edges)
    hidden = x.reshape(-1, x.shape[-1])
    logits = logits_of(hidden, p["embed"], matmul_inputs)
    flat = jnp.asarray(labels).reshape(-1)
    valid = flat >= 0
    logp = jax.nn.log_softmax(logits, -1)
    ce = -jnp.take_along_axis(logp, jnp.where(valid, flat, 0)[:, None],
                              -1)[:, 0] * valid
    out = dict(kept, ce=jnp.sum(ce), n=valid.sum())
    if keep_logits:
        out["logits"] = logits
    if keep_hidden:
        out["hidden"] = hidden
    return out


def loss_from_sums(sums):
    """``{"loss", "ce"}`` from added-up ``loss_sums``."""
    ce = sums["ce"] / jnp.maximum(sums["n"], 1)
    return {"loss": ce, "ce": ce}


def training_loss(params, c, input_ids, labels):
    """The loss of one batch taken whole (what the tests differentiate)."""
    return loss_from_sums(loss_sums(params, c, input_ids, labels))["loss"]
