"""Graph tracing: evaluate an op DAG as a pure jax function.

This replaces the reference's interpreted per-node dispatch loop
(/root/reference/python/hetu/gpu_ops/executor.py:1191 `SubExecutor.compute`):
instead of dispatching one ctypes kernel per node per step, we walk the topo
order ONCE inside `jax.jit` tracing, so the whole step compiles to a single
XLA program.  Python dispatch overhead disappears after the first call and XLA
fuses across op boundaries (the reference relied on stream overlap to hide its
per-node Python hot loop).
"""

from __future__ import annotations

from contextlib import nullcontext

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from .node import Op, PlaceholderOp, VariableOp, find_topo_sort


class TraceContext:
    """Per-trace services available to op ``_compute`` implementations.

    * ``rng_for(op)`` — deterministic per-op, per-step PRNG key (reference
      keeps a seed + seqnum in python/hetu/random.py:1-43 for reproducible
      dropout; here we fold the op id into the step key, which also makes the
      autodiff re-trace of the forward see identical randomness).
    * ``training`` — train/eval flag (dropout, batchnorm).
    * ``record_update(var, value)`` — stateful ops (batchnorm running stats,
      assign) register new values for VariableOps; the executor threads them
      into the functional state.
    """

    def __init__(self, key=None, training=True, mesh=None,
                 master_params=None, cp_impl="ring", differentiated=False):
        self.key = key
        self.training = training
        self.mesh = mesh
        # the trace is the primal of a ``jax.vjp`` (``GradientsBundleOp``):
        # what a recomputed group keeps is kept for a backward pass
        self.differentiated = differentiated
        # long-context lowering flavor over a 'cp' mesh axis: 'ring'
        # (K/V rotate the ICI ring) or 'ulysses' (all-to-all head
        # parallelism); Executor(cp_impl=...) selects it
        self.cp_impl = cp_impl
        self.updates = {}        # VariableOp -> new value (tracer)
        self.opt_state = {}      # {optimizer_op_name: state pytree} (input)
        self.new_opt_state = {}  # {optimizer_op_name: state pytree} (output)
        # mixed precision: full-precision {var_name: value} master copies;
        # set when the executor casts bindings to a lower compute dtype so
        # optimizers update the f32 masters, not the bf16 working copies.
        self.master_params = master_params

    def rng_for(self, op: Op):
        if self.key is None:
            raise RuntimeError(
                f"op {op.name} needs RNG but no key was provided to the trace")
        return jax.random.fold_in(self.key, op.id)

    def record_update(self, var: VariableOp, value):
        self.updates[var] = value


@jax.custom_vjp
def _grad_link(x):
    """``x``; its cotangent passes an optimization barrier, so that what
    flows on from here is a buffer of its own and not a term of a sum that
    XLA fuses further down (`evaluate`: a variable read by several
    recomputed groups)."""
    return x


_grad_link.defvjp(lambda x: (x, None),
                  lambda _, ct: (jax.lax.optimization_barrier(ct),))


def evaluate(eval_nodes, bindings, ctx: TraceContext, topo=None,
             _remat=True):
    """Evaluate ``eval_nodes`` given ``bindings`` {node: value}.

    ``bindings`` must cover every PlaceholderOp/VariableOp reachable; other
    nodes may also be pre-bound (used by autodiff to rebase gradients).
    Returns (values list, env dict).  ``_remat=False`` disables remat-group
    handling (used INSIDE a group's checkpointed body, where the group's
    own nodes must evaluate plainly).
    """
    env = dict(bindings)
    if topo is None:
        topo = find_topo_sort(eval_nodes)
    # -- primal-fusion pass: gradient bundles compute the loss as their
    # vjp primal.  When the loss subgraph is stateless and the bundle's
    # other operands are already bound, run the bundle FIRST and inject
    # its primal as the loss value — the forward then traces exactly once
    # (XLA CSE does not reliably dedupe the re-trace, and cannot across
    # Pallas custom_vjp boundaries; 25% extra FLOPs on BERT-base).
    for node in topo:
        if (getattr(node, "fuses_primal", False) and node not in env
                and node.loss not in env
                and all(x in env for x in node.xs)
                and (node.grad_out is None or node.grad_out in env)):
            primal, grads, updates = node._compute_with_env(
                env, ctx, want_primal=True)
            env[node] = grads
            env[node.loss] = primal
            # stateful ops in the (now skipped) primal forward recorded
            # their updates on the vjp's inner trace; thread them out
            for var, val in updates.items():
                ctx.record_update(var, val)
    # -- demand pruning: with losses pre-bound, their interior forward
    # nodes may be orphaned; compute only what the eval nodes still need
    needed = set()
    stack = [n for n in eval_nodes if n not in env]
    while stack:
        n = stack.pop()
        if n.id in needed:
            continue
        needed.add(n.id)
        stack.extend(i for i in n.inputs if i not in env)
    # -- remat groups: ops created under `with ht.remat():` evaluate as
    # one jax.checkpoint'ed function (their activations recompute in the
    # vjp instead of being saved — the FLOPs-for-HBM memory planner), but
    # for the kernel residuals `dispatch.KEPT` names: the policy keeps
    # those, so the vjp makes no kernel's output again that it held
    remat_groups = {}
    if _remat:
        for node in topo:
            if (node.id in needed and node not in env
                    and not isinstance(node, (PlaceholderOp, VariableOp))
                    and node.remat_scope is not None):
                remat_groups.setdefault(node.remat_scope, []).append(node)
    group_outputs = {}
    # a variable that enters several groups (a stack of layers walked
    # several times on one set of weights): its gradient is the sum of one
    # product a group, and XLA fuses that whole sum into the optimizer's
    # update, so every group's product lives to the end of the backward
    # pass.  `_grad_link` chains the uses instead: each group reads the
    # value through one more link, and the cotangent passes a barrier at
    # every link, so the sum is kept as ONE running accumulator
    shared = {}
    if len(remat_groups) > 1:
        uses = {}
        for group in remat_groups.values():
            for var in {i for n in group for i in n.inputs
                        if isinstance(i, VariableOp)}:
                uses[var] = uses.get(var, 0) + 1
        shared = {var: env[var] for var, n in uses.items() if n > 1}
    from ..ops.pallas import dispatch
    kept = []               # (kernel, bytes) a kernel call the groups keep
    if remat_groups:
        eval_ids = {n.id for n in eval_nodes}
        # what a group hands on: every node of it that a node OUTSIDE it
        # reads, a later group among them (a layer that hands its keys and
        # values, or its scan's output, to layers behind it:
        # `models/phi4flash.py`).  Such a value is a RESULT of its group's
        # checkpointed function and an ARGUMENT of each reader's: it is kept,
        # no reader makes it again, and jax sums the readers' cotangents
        # before the group's own backward pass runs
        # (`tests/test_phi4flash_reference.py`)
        consumed_outside = {}
        for n in topo:
            scope = getattr(n, "remat_scope", None)
            for i in n.inputs:
                iscope = getattr(i, "remat_scope", None)
                if iscope is not None and iscope != scope:
                    consumed_outside.setdefault(iscope, set()).add(i.id)
        for scope, group in remat_groups.items():
            outs = [n for n in group
                    if n.id in consumed_outside.get(scope, ())
                    or n.id in eval_ids]
            group_outputs[scope] = outs or group[-1:]

    done_ids = set()

    def eval_remat_group(scope):
        group = remat_groups[scope]
        gids = {n.id for n in group}
        for n in group:
            if n.is_stateful:
                raise ValueError(
                    f"stateful op {n.name} inside a remat scope — its "
                    "update would replay on recompute; move it outside")
        ins, seen = [], set()
        for n in group:
            for i in n.inputs:
                if i.id not in gids and i.id not in seen:
                    seen.add(i.id)
                    ins.append(i)
        missing = [i for i in ins if i not in env]
        if missing:
            # external inputs later in topo than the group's first node:
            # demand-evaluate them now (a cycle through the group itself
            # is impossible to checkpoint as one function)
            closure = find_topo_sort(missing)
            if any(getattr(c, "remat_scope", None) == scope
                   for c in closure if c not in env):
                raise ValueError(
                    "remat scope interleaves with outside computation; "
                    "split the scope")
            _, env2 = evaluate(missing, env, ctx)
            env.update(env2)
        outs = group_outputs[scope]

        updated = []

        def f(*in_vals):
            # bind ONLY the group's external inputs: everything the group
            # needs flows through the checkpoint boundary as an argument
            # (no closure captures), so the vjp recomputes exactly the
            # group's interior and saves only `ins` and the named kernel
            # residuals (a flash call: its context, B x S x heads x d of
            # the compute type, and log-sum-exp, B x heads x S f32)
            before = dict(ctx.updates)
            vals, _ = evaluate(outs, dict(zip(ins, in_vals)), ctx,
                               _remat=False)
            # what an op of the group hands to the step's state (an expert
            # layer's load and selection bias) leaves the checkpointed
            # function as a result, not as a tracer of its trace
            new = {v: val for v, val in ctx.updates.items()
                   if before.get(v) is not val}
            ctx.updates.clear()
            ctx.updates.update(before)
            updated[:] = list(new)
            return tuple(vals), tuple(new.values())

        for i in ins:
            if i in shared:
                shared[i] = _grad_link(shared[i])
        with dispatch.keeping(kept if ctx.differentiated else None):
            out_vals, new_vals = jax.checkpoint(
                f, policy=dispatch.KEEP_POLICY)(
                *[shared[i] if i in shared else env[i] for i in ins])
        for n, v in zip(outs, out_vals):
            env[n] = v
        for var, val in zip(updated, new_vals):
            ctx.record_update(var, val)
        done_ids.update(gids)

    for node in topo:
        if node in env or node.id not in needed or node.id in done_ids:
            continue
        if isinstance(node, (PlaceholderOp, VariableOp)):
            raise RuntimeError(f"{node} reached trace without a binding")
        if _remat and node.remat_scope is not None:
            eval_remat_group(node.remat_scope)
            continue
        # the node's block (`ht.scope`) into every instruction's op_name
        with (jax.named_scope(node.scope) if node.scope is not None
              else nullcontext()):
            if hasattr(node, "_compute_with_env"):
                env[node] = node._compute_with_env(env, ctx)
            else:
                input_vals = [env[i] for i in node.inputs]
                env[node] = node._compute(input_vals, ctx)
        # interior sharding annotations (set by a Strategy or ht.dispatch)
        # lower to with_sharding_constraint — the per-node reshard points
        # the reference's rewrite pass materialized as comm ops
        # (context.py:1469); GSPMD emits the collectives.
        if (node.dist_state is not None and ctx.mesh is not None
                and hasattr(env[node], "ndim")):
            sh = NamedSharding(ctx.mesh,
                               node.dist_state.to_pspec(env[node].ndim))
            env[node] = jax.lax.with_sharding_constraint(env[node], sh)
    if _remat and ctx.differentiated:
        dispatch.record_kept(kept)
    return [env[n] for n in eval_nodes], env


def constant_like(shape, dtype, value=0):
    return jnp.full(shape, value, dtype=dtype)
