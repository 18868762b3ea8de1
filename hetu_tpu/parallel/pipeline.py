"""Pipeline parallelism over a 'pp' mesh axis.

Reference: /root/reference/python/hetu/gpu_ops/{gpipe,pipedream}_subexecutor.py
— GPipe (all-forward-then-all-backward with micro-batch arr maps) and
PipeDream-1F1B with weight stashing, driven by per-rank Python schedulers
exchanging NCCL P2P messages (PipelineSend/Receive ops, shape handshakes).

TPU redesign: the whole pipeline is ONE SPMD program.  Stages are identical
sub-programs whose parameters carry a leading [pp] dim sharded on the 'pp'
mesh axis; micro-batches rotate between neighbor stages with
`lax.ppermute` inside a `lax.scan` over clock ticks (bubble included).
Differentiating the scanned forward gives the reverse schedule for free —
semantically the GPipe flush schedule (grads accumulated over micro-batches,
single optimizer step), with `jax.checkpoint` on the stage body as the
activation-memory knob (the reference's weight-stashing exists to tolerate
async staleness, which a flush schedule does not incur).  The 1F1B
"pipedream_flush" memory profile comes from `schedule='interleaved'`, which
scans micro-batches with immediate backward via jax.vjp inside the loop.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from jax import shard_map

from .collectives import varying


def _varying(x, axes=("pp",)):
    return varying(x, axes)


def spmd_pipeline(stage_fn, n_stages, n_micro, *, remat=True):
    """Build the per-shard pipeline body (call inside shard_map over 'pp').

    stage_fn(stage_params, x) -> y : one stage applied to one micro-batch.
    Inputs xs: [n_micro, mb, ...] (replicated across pp); returns
    [n_micro, mb, ...] outputs of the LAST stage (valid on every shard).
    """
    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    def body(params, xs):
        # shard of [n_stages, ...]-stacked params has leading dim 1
        params = jax.tree_util.tree_map(lambda p: jnp.squeeze(p, 0), params)
        stage = lax.axis_index("pp")
        n_ticks = n_micro + n_stages - 1
        mb_shape = xs.shape[1:]
        # initial carries must be marked device-varying over 'pp' (they
        # become varying after the first ppermute / stage-dependent update)
        state = _varying(jnp.zeros(mb_shape, xs.dtype))
        outs = _varying(jnp.zeros_like(xs))

        def tick(carry, t):
            state, outs = carry
            # stage 0 injects micro-batch t (zeros past the last one)
            inject = lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, n_micro - 1), axis=0, keepdims=False)
            x_in = jnp.where(stage == 0, inject, state)
            y = stage_fn(params, x_in)
            # last stage emits micro-batch t - (n_stages-1)
            out_idx = t - (n_stages - 1)
            emit = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            idx = jnp.maximum(out_idx, 0)
            cur = lax.dynamic_index_in_dim(outs, idx, axis=0, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(emit, y, cur), idx, axis=0)
            # rotate activations to the next stage (ring; the wraparound
            # value into stage 0 is ignored by the injection mux)
            nxt = lax.ppermute(
                y, "pp", [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outs), None

        (state, outs), _ = lax.scan(tick, (state, outs),
                                    jnp.arange(n_ticks))
        # every shard returns the last stage's outputs (broadcast over pp)
        mask = (stage == n_stages - 1).astype(xs.dtype)
        return lax.psum(outs * mask, "pp")

    return body


class PipelineParallel:
    """Host-level wrapper: pipelined loss/train over a mesh with a 'pp' axis.

    ``stage_fn(stage_params, x) -> x'`` is the repeated stage;
    ``loss_fn(last_out, targets) -> scalar`` closes the graph (computed
    replicated after the pipeline).  ``loss_fn`` MUST reduce by MEAN over
    the leading micro-batch dimension it is given (any mean-style loss):
    'interleaved' evaluates it per micro-batch and averages, so a sum-style
    reduction would disagree with 'gpipe' by a factor of n_micro.
    ``schedule``: 'gpipe' (scan + grad, all activations stashed unless
    remat) — the reference's SubExecutor4Gpipe; 'interleaved' computes
    fwd+bwd per micro-batch (1F1B-flush memory profile; reference
    SubExecutor4Pipedream with pipedream_flush semantics).
    """

    def __init__(self, mesh, stage_fn, n_stages, n_micro, loss_fn,
                 schedule="gpipe", remat=True):
        assert "pp" in mesh.axis_names
        assert mesh.shape["pp"] == n_stages
        self.mesh = mesh
        self.n_stages = n_stages
        self.n_micro = n_micro
        self.loss_fn = loss_fn
        self.schedule = schedule
        self.stage_fn = stage_fn
        self.remat = remat

    def _specs(self, params):
        # stage-stacked params: leading dim = pp
        return jax.tree_util.tree_map(lambda _: P("pp"), params)

    def loss(self, params, xs, targets):
        """xs: [n_micro, mb, ...]; targets: [n_micro, mb, ...]."""
        pipe = spmd_pipeline(self.stage_fn, self.n_stages, self.n_micro,
                             remat=self.remat)

        def shard_body(params, xs, targets):
            outs = pipe(params, xs)
            return self.loss_fn(outs, targets)

        f = shard_map(shard_body, mesh=self.mesh,
                      in_specs=(self._specs(params), P(), P()),
                      out_specs=P())
        return f(params, xs, targets)

    def grads(self, params, xs, targets):
        if self.schedule == "interleaved":
            return self._grads_1f1b(params, xs, targets)
        loss, g = jax.value_and_grad(self.loss)(params, xs, targets)
        return loss, g

    def _grads_1f1b(self, params, xs, targets):
        """Per-micro-batch fwd+bwd accumulation (pipedream-flush memory:
        at most one micro-batch of activations live per stage)."""
        pipe = spmd_pipeline(self.stage_fn, self.n_stages, 1,
                             remat=self.remat)

        def shard_body(params, xs, targets):
            def one_micro(carry, xt):
                acc, lsum = carry
                x, t = xt

                def mloss(p):
                    outs = pipe(p, x[None])
                    return self.loss_fn(outs, t[None])

                l, g = jax.value_and_grad(mloss)(params)
                acc = jax.tree_util.tree_map(jnp.add, acc, g)
                return (acc, lsum + l), None

            zero = jax.tree_util.tree_map(jnp.zeros_like, params)
            (g, lsum), _ = lax.scan(one_micro, (zero, 0.0), (xs, targets))
            n = xs.shape[0]
            return lsum / n, jax.tree_util.tree_map(lambda a: a / n, g)

        f = shard_map(shard_body, mesh=self.mesh,
                      in_specs=(self._specs(params), P(), P()),
                      out_specs=(P(), self._specs(params)))
        return f(params, xs, targets)
