"""Optimizers as graph ops.

Reference: /root/reference/python/hetu/optimizer.py — `Optimizer.minimize`
builds gradient nodes and an `OptimizerOp` whose compute applies fused CUDA
updates (src/ops/Optimizers.cu).  Here the update math is plain jnp inside the
traced step, fused by XLA into the backward program; parameters are threaded
functionally (old value in, new value out) with buffer donation, which is the
TPU analogue of the reference's in-place kernels.

Sparse (IndexedSlices) updates: the reference keeps sparse-aware op pairs for
embedding grads.  Under XLA, gradient-of-gather is already a scatter-add that
never densifies the embedding table update path when wrapped in
``apply_sparse`` (segment-sum on unique ids); the ps/ subsystem additionally
hosts server-side optimizer states for PS-mode tables.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..graph.node import Op, VariableOp, scope
from ..graph.autodiff import gradients
from .lr_scheduler import as_schedule


class Optimizer:
    """Base optimizer: subclasses define slot init + dense update rule."""

    slot_names = ()
    # rowwise (lazy) sparse application is exact for elementwise update
    # rules; optimizers with whole-tensor terms (Lamb trust ratio) opt out
    supports_sparse = True

    def __init__(self, learning_rate=0.01, l2reg=0.0):
        self.lr = as_schedule(learning_rate)
        self.l2reg = l2reg

    # -- functional update rule -------------------------------------------
    def init_slots(self, param):
        return {name: jnp.zeros_like(param) for name in self.slot_names}

    def apply_dense(self, param, grad, slots, lr, step):
        raise NotImplementedError

    def apply_sparse(self, param, ids, grad_rows, slots, lr, step):
        """LAZY sparse update (reference src/ops/OptimizersSparse.cu):
        gather the touched rows of param and slots, run the dense rule
        rowwise, scatter back — untouched rows (and their moments) are
        never read or written.  ``ids`` are deduped with pad -1."""
        mask = (ids >= 0).reshape(-1, *([1] * (param.ndim - 1)))
        gather = jnp.maximum(ids, 0).astype(jnp.int32)
        # pad entries write OUT OF BOUNDS so the scatter DROPS them — a
        # clamped pad index would race the real row-0 update (duplicate
        # scatter indices have no ordering guarantee)
        scatter = jnp.where(ids >= 0, ids,
                            param.shape[0]).astype(jnp.int32)
        p_rows = param[gather]
        s_rows = {k: v[gather] for k, v in slots.items()}
        g_rows = jnp.where(mask, grad_rows.astype(param.dtype), 0)
        new_rows, new_s = self.apply_dense(p_rows, g_rows, s_rows, lr, step)
        new_param = param.at[scatter].set(new_rows, mode="drop")
        new_slots = {k: slots[k].at[scatter].set(new_s[k], mode="drop")
                     for k in slots}
        return new_param, new_slots

    def _regularized(self, param, grad):
        if self.l2reg > 0.0:
            return grad + self.l2reg * param
        return grad

    # -- graph construction ------------------------------------------------
    def minimize(self, loss, var_list=None, sparse_vars=()):
        """Build grads + the OptimizerOp.

        ``sparse_vars``: variables (embedding tables) to update LAZILY —
        gradients are taken w.r.t. their lookup OUTPUTS and applied as
        deduped (ids, rows) without ever densifying a [V, H] gradient
        (reference optimizer.py sparse op pairs + OptimizersSparse.cu).
        A listed var consumed by anything other than embedding_lookup
        falls back to the dense path.
        """
        from ..graph.node import graph_variables, find_topo_sort
        if var_list is None:
            var_list = graph_variables([loss], trainable_only=True)
        sparse_set = set(sparse_vars)
        if sparse_set and not self.supports_sparse:
            raise ValueError(
                f"{type(self).__name__} has whole-tensor update terms; "
                "rowwise sparse application would change its semantics")
        unknown = sparse_set - set(var_list)
        if unknown:
            # loud, not silent: a sparse var outside var_list would get
            # no gradient and no fallback — the table would never train
            raise ValueError(
                "sparse_vars must be optimized variables (in var_list / "
                "trainable): " + ", ".join(v.name for v in unknown))
        dense_vars, sparse_entries = [], []
        topo = find_topo_sort([loss]) if sparse_set else []
        for v in var_list:
            if v not in sparse_set:
                dense_vars.append(v)
                continue
            uses = [n for n in topo if v in n.inputs]
            lookups = [n for n in uses
                       if getattr(n, "op_kind", None) == "embedding_lookup"
                       and n.inputs[0] is v]
            if not lookups or len(uses) != len(lookups):
                dense_vars.append(v)     # non-lookup uses: stay dense
                continue
            sparse_entries.append((v, lookups))
        targets = dense_vars + [lk for _, lks in sparse_entries
                                for lk in lks]
        # var_list may be empty (all params PS-resident); the OptimizerOp
        # then only anchors the loss for PS-embedding grad derivation
        grads = gradients(loss, targets) if targets else []
        nd = len(dense_vars)
        sparse, k = [], nd
        for v, lks in sparse_entries:
            sites = []
            for lk in lks:
                sites.append((grads[k], lk.inputs[1]))
                k += 1
            sparse.append((v, sites))
        op = OptimizerOp(grads[:nd], dense_vars, self, sparse=sparse)
        op.loss = loss  # lets the executor derive PS-embedding grads
        return op

    def apply_gradients(self, grads_and_vars):
        grads, var_list = zip(*grads_and_vars)
        return OptimizerOp(list(grads), list(var_list), self)


class SGDOptimizer(Optimizer):
    def apply_dense(self, param, grad, slots, lr, step):
        grad = self._regularized(param, grad)
        return param - lr * grad, slots


class MomentumOptimizer(Optimizer):
    slot_names = ("velocity",)

    def __init__(self, learning_rate=0.01, momentum=0.9, nesterov=False,
                 l2reg=0.0):
        super().__init__(learning_rate, l2reg)
        self.momentum = momentum
        self.nesterov = nesterov

    def apply_dense(self, param, grad, slots, lr, step):
        grad = self._regularized(param, grad)
        v = self.momentum * slots["velocity"] - lr * grad
        if self.nesterov:
            new_param = param + self.momentum * v - lr * grad
        else:
            new_param = param + v
        return new_param, {"velocity": v}


class AdaGradOptimizer(Optimizer):
    slot_names = ("accum",)

    def __init__(self, learning_rate=0.01, initial_accumulator_value=0.0,
                 eps=1e-7, l2reg=0.0):
        super().__init__(learning_rate, l2reg)
        self.initial_accumulator_value = initial_accumulator_value
        self.eps = eps

    def init_slots(self, param):
        return {"accum": jnp.full_like(param, self.initial_accumulator_value)}

    def apply_dense(self, param, grad, slots, lr, step):
        grad = self._regularized(param, grad)
        acc = slots["accum"] + grad * grad
        new_param = param - lr * grad / (jnp.sqrt(acc) + self.eps)
        return new_param, {"accum": acc}


class AdamOptimizer(Optimizer):
    slot_names = ("m", "v")

    def __init__(self, learning_rate=0.01, beta1=0.9, beta2=0.999, eps=1e-7,
                 amsgrad=False, l2reg=0.0):
        super().__init__(learning_rate, l2reg)
        self.beta1, self.beta2, self.eps = beta1, beta2, eps
        self.amsgrad = amsgrad

    def init_slots(self, param):
        slots = {"m": jnp.zeros_like(param), "v": jnp.zeros_like(param)}
        if self.amsgrad:
            slots["vhat"] = jnp.zeros_like(param)
        return slots

    def _moments(self, grad, slots, step):
        t = step.astype(jnp.float32) + 1.0
        m = self.beta1 * slots["m"] + (1.0 - self.beta1) * grad
        v = self.beta2 * slots["v"] + (1.0 - self.beta2) * grad * grad
        # bias correction from the step counter replaces the reference's
        # BetatsUpdateOp running-product state (optimizer.py:434).
        mhat = m / (1.0 - jnp.power(self.beta1, t))
        vhat = v / (1.0 - jnp.power(self.beta2, t))
        return m, v, mhat, vhat

    def apply_dense(self, param, grad, slots, lr, step):
        grad = self._regularized(param, grad)
        m, v, mhat, vhat = self._moments(grad, slots, step)
        new_slots = {"m": m, "v": v}
        if self.amsgrad:
            vmax = jnp.maximum(slots["vhat"], vhat)
            new_slots["vhat"] = vmax
            denom = jnp.sqrt(vmax) + self.eps
        else:
            denom = jnp.sqrt(vhat) + self.eps
        return param - lr * mhat / denom, new_slots


class AMSGradOptimizer(AdamOptimizer):
    def __init__(self, learning_rate=0.01, beta1=0.9, beta2=0.999, eps=1e-7,
                 l2reg=0.0):
        super().__init__(learning_rate, beta1, beta2, eps, amsgrad=True,
                         l2reg=l2reg)


class AdamWOptimizer(AdamOptimizer):
    def __init__(self, learning_rate=0.01, beta1=0.9, beta2=0.999, eps=1e-7,
                 weight_decay=0.01):
        super().__init__(learning_rate, beta1, beta2, eps)
        self.weight_decay = weight_decay

    def apply_dense(self, param, grad, slots, lr, step):
        m, v, mhat, vhat = self._moments(grad, slots, step)
        update = mhat / (jnp.sqrt(vhat) + self.eps) + self.weight_decay * param
        return param - lr * update, {"m": m, "v": v}


class LambOptimizer(AdamOptimizer):
    """Layer-wise adaptive moments (reference optimizer.py:686)."""

    supports_sparse = False   # whole-tensor trust ratio

    def __init__(self, learning_rate=0.01, beta1=0.9, beta2=0.999, eps=1e-6,
                 weight_decay=0.0):
        super().__init__(learning_rate, beta1, beta2, eps)
        self.weight_decay = weight_decay

    def apply_dense(self, param, grad, slots, lr, step):
        m, v, mhat, vhat = self._moments(grad, slots, step)
        update = mhat / (jnp.sqrt(vhat) + self.eps) + self.weight_decay * param
        w_norm = jnp.linalg.norm(param.reshape(-1))
        u_norm = jnp.linalg.norm(update.reshape(-1))
        trust = jnp.where(w_norm > 0,
                          jnp.where(u_norm > 0, w_norm / u_norm, 1.0), 1.0)
        return param - lr * trust * update, {"m": m, "v": v}


class OptimizerOp(Op):
    """Graph node applying the optimizer to (grad, var) pairs.

    Evaluated with env access: reads current parameter values bound in the
    trace env, reads/writes optimizer slot state via the TraceContext, records
    new parameter values for the executor to thread out.  Evaluates to None
    (matching reference train_op semantics).
    """

    def __init__(self, grads, var_list, optimizer, clip_global_norm=None,
                 sparse=None):
        assert len(grads) == len(var_list)
        # sparse: [(var, [(rows_grad_node, ids_node), ...]), ...] — lazy
        # embedding updates (Optimizer.minimize sparse_vars)
        self.sparse = list(sparse or [])
        extra = [n for _, sites in self.sparse
                 for g, ids in sites for n in (g, ids)]
        super().__init__(*grads, *extra, name=f"optimizer_{_opt_count()}")
        # the clip, the update and the masters' write are one block, under
        # whatever `ht.scope` the caller builds the optimizer
        self.scope = scope("hetu_optim").name
        self.var_list = list(var_list)
        self.optimizer = optimizer
        self.clip_global_norm = clip_global_norm
        self.loss = None
        for v in list(var_list) + [v for v, _ in self.sparse]:
            assert isinstance(v, VariableOp), f"cannot optimize {v}"

    @property
    def is_stateful(self):
        return True

    def init_state(self, params):
        """Initial optimizer state given {var_name: value}."""
        return {
            "step": jnp.zeros((), dtype=jnp.int32),
            "slots": {v.name: self.optimizer.init_slots(params[v.name])
                      for v in (self.var_list
                                + [sv for sv, _ in self.sparse])},
        }

    @staticmethod
    def _bucket(n, floor=64):
        b = floor
        while b < n:
            b *= 2
        return b

    def _compute_with_env(self, env, ctx):
        from ..ops.embedding import reduce_indexedslices
        state = ctx.opt_state[self.name]
        step = state["step"]
        lr = self.optimizer.lr.get(step)
        grads = [env[g] for g in self.inputs[:len(self.var_list)]]
        # lazy-sparse vars: dedup each var's (ids, rows) across its
        # lookup sites FIRST, so the clip norm matches the dense norm
        # exactly (duplicate ids would double-count otherwise)
        sparse_ready = []
        for var, sites in self.sparse:
            ids = jnp.concatenate(
                [env[i].reshape(-1) for _, i in sites]).astype(jnp.int32)
            rows = jnp.concatenate(
                [env[g].reshape(-1, env[g].shape[-1]) for g, _ in sites])
            uniq, summed = reduce_indexedslices(
                ids, rows, self._bucket(int(ids.shape[0])))
            sparse_ready.append((var, uniq, summed))
        if self.clip_global_norm is not None:
            # accumulate the norm in f32 (bf16 grads under mixed precision
            # would underestimate it once the sum saturates the mantissa)
            sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                     for g in grads)
            sq += sum(jnp.sum(jnp.square(r.astype(jnp.float32)))
                      for _, _, r in sparse_ready)
            gnorm = jnp.sqrt(sq)
            scale = jnp.minimum(1.0, self.clip_global_norm / (gnorm + 1e-6))
            grads = [g * scale for g in grads]
            sparse_ready = [(v, i, r * scale) for v, i, r in sparse_ready]
        new_slots = {}
        master = ctx.master_params

        def _param_of(var):
            # mixed precision: update the full-precision master copy, not
            # the low-precision working value bound in the trace env.
            return master[var.name] if (master is not None
                                        and var.name in master) else env[var]

        for var, grad in zip(self.var_list, grads):
            param = _param_of(var)
            grad = grad.astype(param.dtype)
            new_p, ns = self.optimizer.apply_dense(
                param, grad, state["slots"][var.name], lr, step)
            new_slots[var.name] = ns
            ctx.record_update(var, new_p)
        for var, uniq, summed in sparse_ready:
            param = _param_of(var)
            new_p, ns = self.optimizer.apply_sparse(
                param, uniq, summed, state["slots"][var.name], lr, step)
            new_slots[var.name] = ns
            ctx.record_update(var, new_p)
        ctx.new_opt_state[self.name] = {"step": step + 1, "slots": new_slots}
        return None


_opt_counter = [0]


def _opt_count():
    _opt_counter[0] += 1
    return _opt_counter[0]
