"""Plain reference of BERT pretraining (Devlin et al. 2018, the
``google-bert/bert-base-uncased`` layout): embeddings, post-LN encoder,
pooler, MLM head tied to the word embedding, NSP head, and the summed loss.
Straight ``jax.numpy`` in float32 at the highest matmul precision; no
kernel, no bucket, no dropout.  Independent of ``hetu_tpu/models``: it
takes the weights by name and nothing else.

Departures it shares with the program, because they are the program's
mathematics and not its speed: GELU in its tanh form and LayerNorm epsilon
1e-5 (the published config says erf GELU and 1e-12), both listed under
``assumed`` in the configuration file.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _ln(x, p, name):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return ((x - mu) / jnp.sqrt(var + LN_EPS) * p[f"{name}_scale"]
            + p[f"{name}_bias"])


def _dense(x, p, name):
    return x @ p[f"{name}_weight"] + p[f"{name}_bias"]


def _ce(logits, labels):
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.take_along_axis(logp, labels[:, None], -1)[:, 0]


def loss_sums(params, c, input_ids, token_type_ids, attention_mask,
              mlm_labels, nsp_labels, name="bert"):
    """``(sum of the MLM loss over positions with a label >= 0, their
    count, sum of the NSP loss over sequences)`` of some sequences, so that
    a batch can be taken a few sequences at a time.  ``mlm_labels`` is
    ``[B, S]``."""
    with jax.default_matmul_precision("highest"):
        p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()
             if jnp.issubdtype(v.dtype, jnp.floating)}
        B, S = input_ids.shape
        H, nh = c["hidden_size"], c["num_attention_heads"]
        x = (p[f"{name}_embeddings_word_table"][input_ids]
             + p[f"{name}_embeddings_tok_type_table"][token_type_ids]
             + p[f"{name}_embeddings_position"][None, :S])
        x = _ln(x, p, f"{name}_embeddings_ln")
        bias = (1.0 - jnp.asarray(attention_mask, jnp.float32)
                )[:, None, None, :] * -10000.0
        for i in range(c["num_hidden_layers"]):
            L = f"{name}_layer{i}"

            def heads(t):
                return t.reshape(B, S, nh, H // nh).transpose(0, 2, 1, 3)
            q = heads(_dense(x, p, f"{L}_attn_q"))
            k = heads(_dense(x, p, f"{L}_attn_k"))
            v = heads(_dense(x, p, f"{L}_attn_v"))
            s = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(H / nh) + bias
            a = (jax.nn.softmax(s, -1) @ v).transpose(0, 2, 1, 3)
            x = _ln(x + _dense(a.reshape(B, S, H), p, f"{L}_attn_out"),
                    p, f"{L}_ln1")
            f = jax.nn.gelu(_dense(x, p, f"{L}_ffn_in"), approximate=True)
            x = _ln(x + _dense(f, p, f"{L}_ffn_out"), p, f"{L}_ln2")
        pooled = jnp.tanh(_dense(x[:, 0], p, f"{name}_pooler"))
        nsp = _ce(_dense(pooled, p, f"{name}_nsp"), jnp.asarray(nsp_labels))
        flat = x.reshape(B * S, H)
        labels = jnp.asarray(mlm_labels).reshape(-1)
        h = _ln(jax.nn.gelu(_dense(flat, p, f"{name}_mlm_transform"),
                            approximate=True), p, f"{name}_mlm_ln")
        logits = (h @ p[f"{name}_embeddings_word_table"].T
                  + p[f"{name}_mlm_bias"])
        valid = labels >= 0
        ce = _ce(logits, jnp.where(valid, labels, 0))
        return jnp.sum(ce * valid), valid.sum(), nsp.sum()


def pretraining_loss(params, c, *batch, name="bert"):
    """MLM loss averaged over the positions with a label >= 0, plus the NSP
    loss averaged over sequences."""
    mlm, n, nsp = loss_sums(params, c, *batch, name=name)
    return mlm / jnp.maximum(n, 1) + nsp / batch[0].shape[0]
