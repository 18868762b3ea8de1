"""Ouro looped decoder LMs (``ByteDance/Ouro-1.4B`` / ``-2.6B``, the LoopLM
family): ONE stack of Llama-shaped layers walked ``total_ut_steps`` times on
one set of weights, an exit gate a token and a pass, and a pretraining loss
that is an expectation over the exits.

A layer has four RMS norms, one before and one BEHIND each sublayer
(sandwich)::

    a = x + n2(Attn(n1(x)));  y = a + n4(MLP(n3(a)))

The stack, ``P = total_ut_steps`` passes over the same ``Layers`` and the same
final ``Norm``; the normed state is what the next pass reads::

    h_0 = Embed(ids);  h_t = Norm(Layers(h_{t-1})),  t = 1..P

The exit gate reads every pass's normed state, in f32, and the last pass
takes the mass that is left (``lambda_P`` is computed and not read)::

    lambda_t = sigmoid(w_g . h_t + b_g)
    p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < P),  p_P = prod_{j<P} (1 - lambda_j)

The loss (uniform prior over the exits), means over labelled positions,
nothing detached, so the gate learns through the weights ``p_t``::

    L = mean_i sum_t p_t(i) CE(W_head h_t(i), label(i)) - beta mean_i H(p(i))

**Not modelled**: early exit at inference (``early_exit_threshold``) and the
KV cache a pass that serving would need; a pipeline whose micro-batches go
round its stages more than once (``pipeline_stages`` is refused).

``remat`` (on by default) has the backward pass recompute whole decoder
layers, one group a layer APPLICATION (``P x k`` groups read ``k`` layers'
variables, each keeping the residual stream that enters it), and every
pass's head product and cross-entropy (the ``[T, V]`` logits of a pass are
then live one pass at a time).

``loss_terms`` is three pieces a caller may also walk by hand, a pass at a
time (a comparison that wants one pass's logits in memory, not ``P``):
``OuroModel.walk`` (the ``k`` layers and the final norm), ``exit_terms`` (a
pass's logits, cross-entropies and gate pre-activations) and ``exit_loss``
(the distribution over the exits and the loss from the ``P`` passes' terms).
"""

from __future__ import annotations

from contextlib import nullcontext

import numpy as np

from .. import initializers as init
from ..graph.node import (Op, VariableOp, remat as remat_scope, scope,
                          scoped_init)
from ..layers import Linear, RMSNorm
from ..layers.moe import MoELoadOp
from ..ops import array_reshape_op, softmax_cross_entropy_sparse_op
from .bert import MaskedMeanOp
from .llama import (LlamaConfig, LlamaDecoderLayer, LlamaForCausalLM,
                    LlamaModel, residual_sublayer)

class OuroConfig(LlamaConfig):
    """``LlamaConfig`` and the loop: ``total_ut_steps`` (the published key),
    the entropy term's weight ``exit_entropy_coeff`` (not in ``config.json``)
    and whether the backward pass recomputes (``remat``: every layer
    application and every head pass, or nothing)."""

    def __init__(self, total_ut_steps=4, exit_entropy_coeff=0.05,
                 remat=True, **kwargs):
        kwargs.setdefault("rms_eps", 1e-6)
        kwargs.setdefault("rope_theta", 1e6)
        super().__init__(**kwargs)
        assert not self.num_experts, "the looped stack is dense"
        assert total_ut_steps >= 1, total_ut_steps
        self.total_ut_steps = total_ut_steps
        self.exit_entropy_coeff = exit_entropy_coeff
        self.remat = bool(remat)


# published shapes (the checkpoint's config.json)
OURO_CONFIGS = {
    "ouro-2.6b": dict(vocab_size=49152, hidden_size=2048, num_layers=48,
                      num_heads=16, intermediate_size=5632),
}


class OuroDecoderLayer(LlamaDecoderLayer):
    """The Llama layer with a second norm behind each sublayer."""

    def __init__(self, config, name, rope_tables=None):
        super().__init__(config, name, rope_tables=rope_tables)
        c = config
        self.attn_out_norm = RMSNorm(c.hidden_size, eps=c.rms_eps,
                                     name=f"{name}_input_norm_2")
        self.mlp_out_norm = RMSNorm(c.hidden_size, eps=c.rms_eps,
                                    name=f"{name}_post_norm_2")
        self._layer_scope = remat_scope if c.remat else nullcontext

    def __call__(self, x, seq_len=None):
        with self._layer_scope():       # one recomputed group an application
            x = residual_sublayer(x, self.input_norm, self.attn,
                                  post_norm=self.attn_out_norm,
                                  seq_len=seq_len)
            return residual_sublayer(x, self.post_norm, self.mlp,
                                     post_norm=self.mlp_out_norm)


def _count_layer_call(loop_pass):
    from .. import telemetry
    telemetry.get_registry().counter(
        "hetu_loop_layer_calls_total",
        "Decoder-layer applications built by a looped stack, by pass",
        labels=("pass",)).labels(**{"pass": str(loop_pass)}).inc()


class OuroModel(LlamaModel):
    def __init__(self, config, name="ouro", pipeline_stages=None):
        if pipeline_stages:
            raise NotImplementedError(
                "a pipeline whose micro-batches go round its stages "
                "total_ut_steps times is not built "
                "(parallel/graph_pipeline.py walks its stages once)")
        super().__init__(config, name=name)

    def _layer(self, i, name):
        return OuroDecoderLayer(self.config, name=name,
                                rope_tables=self.rope_tables)

    def walk(self, x, loop_pass=None):
        """One pass: the ``k`` layers and the final norm on ``x [B, S, H]``
        (the embeddings, or the normed state the pass before handed on);
        counted under ``loop_pass`` where the caller numbers its passes."""
        for layer in self.layers:
            x = layer(x, seq_len=self.config.seq_len)
            if loop_pass is not None:
                _count_layer_call(loop_pass)
        with scope("hetu_head"):
            return self.norm(x)         # the SAME norm, fed back

    def __call__(self, input_ids):
        """The ``P`` normed states ``h_1 .. h_P``, each ``[B, S, H]``."""
        x = self._embed(input_ids)
        states = []
        for t in range(self.config.total_ut_steps):
            x = self.walk(x, loop_pass=t)
            states.append(x)
        return states


class ExitGateOp(Op):
    """``h . w + b`` of the exit gate, ``[T, H] -> [T]``, summed and kept in
    f32 whatever the compute type."""

    def _compute(self, input_vals, ctx):
        import jax.numpy as jnp
        h, w, b = input_vals
        z = jnp.einsum("th,ho->t", h, w, preferred_element_type=jnp.float32)
        return z + b.astype(jnp.float32)[0]


class ExitDistributionOp(Op):
    """``[P, T]`` f32: the distribution over the exits a token from the
    ``P`` gate pre-activations ``z_t``; formed through ``log sigmoid`` so that
    no product of small survivals leaves f32.  The last pass takes what is
    left."""

    def _compute(self, input_vals, ctx):
        import jax
        import jax.numpy as jnp
        z = jnp.stack([v.astype(jnp.float32) for v in input_vals])
        stay = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)   # log prod (1-l)
        before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
        log_p = jnp.concatenate(
            [before[:-1] + jax.nn.log_sigmoid(z[:-1]), before[-1:]])
        return jnp.exp(log_p)


class ExitExpectationOp(Op):
    """``sum_t p_t x_t`` a token: ``p`` ``[P, T]`` and ``P`` vectors ``[T]``.
    It stands on the loss's path, inside the gradient's vjp, whose interior
    the step's other fetches cannot see: so it also hands the mean of ``p_t``
    over labelled positions, ``[P]``, out as a state update of ``share_var``,
    which ``OuroForCausalLM.exit_shares`` reads beside the loss with no
    second forward pass (as an expert layer hands out its load)."""

    def __init__(self, p, xs, labels, share_var):
        super().__init__(p, *xs, labels)
        self.share_var = share_var

    def _compute(self, input_vals, ctx):
        import jax.numpy as jnp
        p, *xs, labels = input_vals
        valid = (labels.reshape(-1) >= 0).astype(p.dtype)
        ctx.record_update(self.share_var, jnp.sum(p * valid, 1)
                          / jnp.maximum(jnp.sum(valid), 1.0))
        return jnp.sum(p * jnp.stack([x.astype(p.dtype) for x in xs]), 0)


class ExitEntropyOp(Op):
    """``H(p) = - sum_t p_t log p_t`` a token, ``0 log 0 = 0``."""

    def _compute(self, input_vals, ctx):
        import jax.numpy as jnp
        p, = input_vals
        safe = jnp.where(p > 0, p, 1.0)
        return -jnp.sum(p * jnp.log(safe), 0)


def record_exit_shares(shares):
    """Set ``hetu_loop_exit_share{pass}`` from the fetched ``[P]`` vector of
    ``OuroForCausalLM.exit_shares`` (the mean share of the exit mass a pass
    took, last step).  The registry keeps nothing while telemetry is
    disabled."""
    from .. import telemetry
    gauge = telemetry.get_registry().gauge(
        "hetu_loop_exit_share",
        "Mean share of the exit distribution a pass took, last step",
        labels=("pass",))
    for t, share in enumerate(np.asarray(shares, np.float64)):
        gauge.labels(**{"pass": str(t)}).set(float(share))


class OuroForCausalLM(LlamaForCausalLM):
    """After ``loss_terms``: ``exit_shares`` (a ``[P]`` node to fetch beside
    the loss: it reads what the loss's own graph recorded, and in a program
    that does not evaluate the loss the state's last value), ``exit_p``
    (``[P, T]``) and ``pass_logits`` (``P`` nodes ``[T, V]``) of that
    call."""
    model_cls = OuroModel

    @scoped_init
    def __init__(self, config, name="ouro", pipeline_stages=None):
        assert not config.tie_embeddings, "the head is untied"
        super().__init__(config, name=name, pipeline_stages=pipeline_stages)
        self.exit_gate = Linear(config.hidden_size, 1, bias=True,
                                name=f"{name}_exit_gate")
        #: the mean exit shares ``[P]`` of the last step, a state the loss's
        #: graph updates (`ExitExpectationOp`)
        self.exit_share_var = VariableOp(
            f"{name}_exit_share", (config.total_ut_steps,), init.zeros(),
            trainable=False)

    def _flat(self, h):
        return array_reshape_op(h, output_shape=(-1, self.config.hidden_size))

    def __call__(self, input_ids):
        """The LAST pass's logits ``[B S, V]`` (no exit is taken early)."""
        with scope("hetu_head"):
            return self.lm_head(self._flat(self.model(input_ids)[-1]))

    def exit_terms(self, h, flat):
        """One pass's ``(logits [T, V], ce [T], z [T])`` from its normed
        state ``h [B, S, H]`` and the flat labels: the head product through
        the one ``lm_head``, the loss kernel, and the exit gate's
        pre-activation in f32."""
        with scope("hetu_head"):
            h = self._flat(h)
        with (remat_scope if self.config.remat else nullcontext)():
            with scope("hetu_head"):
                logits = self.lm_head(h)
            with scope("hetu_loss"):
                ce = softmax_cross_entropy_sparse_op(logits, flat,
                                                     ignored_index=-1)
        with scope("hetu_exit"):
            return logits, ce, ExitGateOp(h, self.exit_gate.weight,
                                          self.exit_gate.bias)

    def exit_loss(self, zs, ces, flat):
        """``(loss, terms)`` as ``loss_terms`` names them, from the ``P``
        passes' gate pre-activations and cross-entropies (``[T]`` each) and
        the flat labels; sets ``exit_p`` and ``exit_shares``."""
        with scope("hetu_exit"):
            self.exit_p = ExitDistributionOp(*zs)
            weighted = ExitExpectationOp(self.exit_p, ces, flat,
                                         self.exit_share_var)
            entropy = ExitEntropyOp(self.exit_p)
            self.exit_shares = MoELoadOp(self.exit_share_var)
        with scope("hetu_loss"):
            terms = {"ce": MaskedMeanOp(weighted, flat),
                     "entropy": MaskedMeanOp(entropy, flat)}
            return (terms["ce"] - self.config.exit_entropy_coeff
                    * terms["entropy"]), terms

    def loss_terms(self, input_ids, labels, logits=None):
        """``(loss, {"ce": the expected cross-entropy, "entropy": the mean
        entropy of the exit distribution})``, ``loss = ce -
        exit_entropy_coeff x entropy``.  The ``P`` head passes go through the
        one ``lm_head`` and the loss kernel."""
        assert logits is None, "a looped model has P logits, not one"
        with scope("hetu_loss"):
            flat = array_reshape_op(labels, output_shape=(-1,))
        logits, ces, zs = zip(*(
            self.exit_terms(h, flat) for h in self.model(input_ids)))
        self.pass_logits = list(logits)
        return self.exit_loss(zs, ces, flat)

    @property
    def attention_layers(self):
        """Attention applications a forward pass: ``P x k``."""
        return self.config.total_ut_steps * self.config.num_layers
