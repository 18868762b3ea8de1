"""Trace-time autodiff: ``gradients(loss, xs)`` as graph nodes.

The reference builds the backward graph symbolically at define time with
hand-written per-op gradient rules (/root/reference/python/hetu/gpu_ops/
executor.py:1265 `gradients()` — reverse topo walk calling `node.gradient`).
Here gradient nodes are thin wrappers that, when the graph is traced, rebase
the loss subgraph on ``xs`` and call ``jax.vjp`` — so every op differentiates
for free (including future Pallas kernels via their custom VJPs), and XLA CSE
dedupes the re-traced forward against the primal forward.  The user-facing
contract matches the reference: ``gradients`` returns one graph node per x,
usable as inputs to optimizer ops or comm ops.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .node import Op, PlaceholderOp, VariableOp, find_topo_sort
from .trace import TraceContext, evaluate


class GradientsBundleOp(Op):
    """Internal: computes all d loss / d xs in one vjp call.

    ``fuses_primal`` marks that the vjp's forward pass produces the loss
    value itself: when the loss subgraph is stateless, `evaluate`
    (trace.py) computes this bundle FIRST and injects the vjp primal as
    the loss's value, so the forward is traced exactly once — measured on
    TPU v5e, the old evaluate-loss-then-vjp structure cost 25% extra
    FLOPs/step on BERT-base because XLA CSE does NOT reliably merge the
    primal forward with the vjp's re-trace (and cannot across Pallas
    custom_vjp boundaries).
    """

    fuses_primal = True

    def __init__(self, loss, xs, grad_out=None):
        self.xs = list(xs)
        self.grad_out = grad_out
        inputs = [loss] + self.xs + ([grad_out] if grad_out is not None else [])
        super().__init__(*inputs, name=f"grads_of_{loss.name}")
        # no block of its own: the inner `evaluate` names each node's
        self.scope = None
        self.loss = loss

    # evaluated via _compute_with_env (special-cased by trace/executor)
    def _compute_with_env(self, env, ctx: TraceContext, want_primal=False):
        sub_topo = find_topo_sort([self.loss])
        x_set = set(self.xs)
        # Rebase on true graph leaves only; everything between leaves and loss
        # is re-traced with xs overridden (xs may be intermediate nodes, e.g.
        # stage-boundary activations for pipeline partitioning).  Binding any
        # already-computed interior node would cut the path from xs to loss.
        leaves = [n for n in sub_topo
                  if isinstance(n, (PlaceholderOp, VariableOp))
                  and n not in x_set]

        # stateful updates (batchnorm running stats, assigns) surface as
        # the vjp's aux so the primal-fusion path can record them; on the
        # non-fused path they're discarded (the primal forward already
        # recorded them).  RNG is shared either way, so dropout masks
        # replay identically.
        node_by_name = {}  # aux pytree keys must sort; map names back

        def f(x_vals):
            inner = TraceContext(key=ctx.key, training=ctx.training,
                                 mesh=ctx.mesh,
                                 master_params=ctx.master_params,
                                 differentiated=True)
            bind = {n: env[n] for n in leaves if n in env}
            bind.update(dict(zip(self.xs, x_vals)))
            (loss_val,), _ = evaluate([self.loss], bind, inner)
            node_by_name.update({v.name: v for v in inner.updates})
            return loss_val, {v.name: val
                              for v, val in inner.updates.items()}

        primals = [env[x] for x in self.xs]
        loss_val, vjp_fn, updates = jax.vjp(f, primals, has_aux=True)
        if self.grad_out is not None:
            ct = env[self.grad_out]
        else:
            ct = jnp.ones_like(loss_val)
        (grads,) = vjp_fn(ct)
        if want_primal:
            return loss_val, tuple(grads), {node_by_name[k]: v
                                            for k, v in updates.items()}
        return tuple(grads)

    def _compute(self, input_vals, ctx):
        raise RuntimeError("GradientsBundleOp is evaluated with env access")


class GradientSliceOp(Op):
    """Selects one gradient out of a GradientsBundleOp."""

    def __init__(self, bundle, idx, of):
        super().__init__(bundle, name=f"grad_{of.name}")
        self.scope = None
        self.idx = idx
        self.of = of  # the x this is the gradient of

    def _compute(self, input_vals, ctx):
        return input_vals[0][self.idx]


def gradients(loss, node_list, grad_out=None, return_all=False):
    """Build gradient nodes of ``loss`` w.r.t. each node in ``node_list``.

    API-compatible with reference executor.py:1265.  ``return_all`` returns
    (grads, backward2forward, forward2backward) maps used by the pipeline
    partitioner; here the maps are {x: grad_node} / {grad_node: x}.
    """
    node_list = list(node_list)
    bundle = GradientsBundleOp(loss, node_list, grad_out=grad_out)
    grads = [GradientSliceOp(bundle, i, x) for i, x in enumerate(node_list)]
    if return_all:
        f2b = {x: g for x, g in zip(node_list, grads)}
        b2f = {g: x for x, g in zip(node_list, grads)}
        return grads, b2f, f2b
    return grads
