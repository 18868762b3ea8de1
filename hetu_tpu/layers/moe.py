"""MoE layer with expert parallelism.

Reference: /root/reference/python/hetu/layers/moe_layer.py — MoELayer:
reshape → gate → layout_transform → alltoall → expert FFNs → alltoall →
reverse_layout_transform (:60-88); BASE-layer variant (:90) with balance
assignment; gates in layers/{TopGate,KTop1Gate,HashGate,SAMGate,BalanceGate}.

TPU redesign: gating + dispatch are dense einsums (ops/moe.py); the expert
dim of dispatched activations and of expert weights carries an 'ep' mesh-axis
annotation, so GSPMD inserts the all-to-all pair the reference ran as
explicit AllToAllOps (for multi-node topologies,
parallel/collectives.hierarchical_all_to_all composes the DCN×ICI staging
explicitly inside shard_map).
"""

from __future__ import annotations

import numpy as np

from .base import BaseLayer, fresh_name
from ..graph.node import Op, VariableOp, named_scope, scope
from .. import initializers as init
from ..ops.moe import (top_k_gating, hash_gating, ktop1_gating, sam_gating,
                       base_balance_gating, top_k_balance_aux,
                       ktop1_balance_aux, sam_balance_aux,
                       top_k_gating_choices, hash_gating_choices,
                       ktop1_gating_choices, sam_gating_choices)


def _orthogonal_rows(rng, rows, cols, gain=0.1):
    """Orthogonal centroid init (reference BalanceGate.generate_orthogonal)."""
    flat = rng.normal(0, 1, (max(rows, cols), min(rows, cols)))
    q, r = np.linalg.qr(flat)
    q = q * np.sign(np.diag(r))
    if rows < cols:
        q = q.T
    return (q[:rows, :cols] * gain).astype(np.float32)


class TopKGate(BaseLayer):
    """Top-k softmax gate weights for any ``k <= E`` (reference TopGate.py
    is the GShard top-1/top-2 case).  ``renorm`` rescales a token's kept
    gates to sum to 1 (GShard top-2, Mixtral); False uses the softmax
    weights as they are (OLMoE).  Routing hyper-parameters (k, capacity)
    live on the MoELayer, the single source of truth.

    ``score="sigmoid"`` (dropless path only) is the router of DeepSeek-V3
    and Nemotron-H (``ops/moe.py top_k_route``): sigmoid scores, the experts
    chosen by ``score + bias``, the gates the chosen scores times ``scale``.
    ``bias`` is a ``[E]`` variable that is no weight: it has no gradient and
    no optimizer state, and the layer's op moves it inside the training step
    by ``bias_rate * sign(mean(load) - load)`` from the step's pair counts
    over all ``E`` experts (DeepSeek-V3's auxiliary-loss-free balancing);
    ``bias_rate=None`` leaves it where it is.  ``groups=(n_group,
    topk_group)`` limits the choice to the experts of the best groups
    (dropless path only)."""

    def __init__(self, hidden_size, num_experts, renorm=True, name=None,
                 score="softmax", scale=None, bias_rate=None, groups=None):
        name = fresh_name(name or "gate")
        self.renorm = renorm
        self.score, self.scale, self.bias_rate = score, scale, bias_rate
        #: (n_group, topk_group): group-limited selection (``top_k_route``)
        self.groups = groups
        self.wg = VariableOp(f"{name}_w", (hidden_size, num_experts),
                             init.xavier_uniform())
        self.bias = VariableOp(f"{name}_bias", (num_experts,), init.zeros(),
                               trainable=False) \
            if score == "sigmoid" else None

    def gating(self, tokens, wg, ids, k, capacity):
        return top_k_gating(tokens @ wg, k, capacity,
                            second_renorm=self.renorm)

    def gating_choices(self, tokens, wg, ids, k, capacity):
        return top_k_gating_choices(tokens @ wg, k, capacity,
                                    second_renorm=self.renorm)

    def route(self, tokens, wg, k, bias=None):
        """Dropless routing: ``(logits, idx, gate, probs)``, the logits and
        everything after them in f32 at full matmul precision, so that which
        experts a token takes does not depend on the compute type."""
        import jax
        import jax.numpy as jnp
        from ..ops.moe import top_k_route
        logits = jnp.matmul(tokens.astype(jnp.float32),
                            wg.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        return (logits,) + top_k_route(logits, k, renorm=self.renorm,
                                       score=self.score, bias=bias,
                                       scale=self.scale, groups=self.groups)

    def aux(self, tokens, wg, ids, k):
        return top_k_balance_aux(tokens @ wg)


class HashGate(BaseLayer):
    """Deterministic id-hash gate (reference HashGate.py).  Requires token
    ids passed to MoELayer.__call__."""

    has_aux = False   # routing is deterministic: no balance loss

    def __init__(self, num_experts, name=None):
        self.num_experts = num_experts
        self.wg = None

    def gating(self, tokens, wg, ids, k, capacity):
        return hash_gating(ids.reshape(-1), self.num_experts, capacity,
                           dtype=tokens.dtype)

    def gating_choices(self, tokens, wg, ids, k, capacity):
        return hash_gating_choices(ids.reshape(-1), self.num_experts,
                                   capacity, dtype=tokens.dtype)


class KTop1Gate(BaseLayer):
    """k-prototype top-1 gate (reference KTop1Gate.py): experts split into
    k prototypes; each token routes top-1 within every prototype."""

    def __init__(self, hidden_size, num_experts, name=None):
        name = fresh_name(name or "ktop1_gate")
        self.wg = VariableOp(f"{name}_w", (hidden_size, num_experts),
                             init.xavier_uniform())

    def gating(self, tokens, wg, ids, k, capacity):
        return ktop1_gating(tokens @ wg, k, capacity)

    def gating_choices(self, tokens, wg, ids, k, capacity):
        return ktop1_gating_choices(tokens @ wg, k, capacity)

    def aux(self, tokens, wg, ids, k):
        return ktop1_balance_aux(tokens @ wg, k)


class SAMGate(BaseLayer):
    """Switch-and-mix locality gate (reference SAMGate.py): pick the
    expert GROUP (host) with the largest mass, then top-k inside it."""

    def __init__(self, hidden_size, num_experts, num_groups, name=None):
        name = fresh_name(name or "sam_gate")
        assert num_experts % num_groups == 0
        self.num_groups = num_groups
        self.wg = VariableOp(f"{name}_w", (hidden_size, num_experts),
                             init.xavier_uniform())

    def gating(self, tokens, wg, ids, k, capacity):
        return sam_gating(tokens @ wg, k, capacity, self.num_groups)

    def gating_choices(self, tokens, wg, ids, k, capacity):
        return sam_gating_choices(tokens @ wg, k, capacity,
                                  self.num_groups)

    def aux(self, tokens, wg, ids, k):
        return sam_balance_aux(tokens @ wg, self.num_groups)


class BalanceGate(BaseLayer):
    """BASE-layer gate (reference BalanceGate.py): balanced assignment
    against fixed orthogonal expert centroids, sigmoid combine."""

    has_aux = False   # assignment is balanced by construction

    def __init__(self, hidden_size, num_experts, seed=0, name=None):
        name = fresh_name(name or "balance_gate")
        cent = _orthogonal_rows(np.random.default_rng(seed), num_experts,
                                hidden_size)
        # wg = centroids^T so scores = tokens @ wg, like the other gates
        self.wg = VariableOp(f"{name}_centroids", (hidden_size, num_experts),
                             init.NumpyInit(cent.T.copy()), trainable=False)

    def gating(self, tokens, wg, ids, k, capacity):
        return base_balance_gating(tokens @ wg, capacity)


def _state_router(u, w_down, b_down, norm_w, w1, b1, w2, b2, w3, *rest,
                  eps):
    """``(logits [T, E + skip], state [.., R])``, both f32: the equations of
    ``StateRouter``; ``rest`` is ``(gamma, prev)`` where a state comes in."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32

    def mm(a, w):
        return jnp.matmul(a, w.astype(f32),
                          precision=jax.lax.Precision.HIGHEST)
    r = mm(u.astype(f32), w_down) + b_down.astype(f32)
    if rest:
        gamma, prev = rest
        r = r + gamma.astype(f32) * prev.astype(f32)
    h = r * jax.lax.rsqrt(jnp.mean(r * r, -1, keepdims=True) + eps)
    h = h * norm_w.astype(f32)
    h = jax.nn.gelu(mm(h, w1) + b1.astype(f32), approximate=False)
    h = jax.nn.gelu(mm(h, w2) + b2.astype(f32), approximate=False)
    logits = mm(h, w3)
    return logits.reshape(-1, logits.shape[-1]), r


class StateRouter(BaseLayer):
    """The ZAYA1 router (arXiv:2511.17127): an MLP of width ``width`` on a
    down-projection of the token that carries its own state down the depth.
    Called ``(u, prev_state) -> (logits [T, E + skip], state [.., R])`` on the
    normed hidden states ``u [.., C]`` and the state the layer above handed on
    (None in the first layer), under ``hetu_moe_route``, in f32 at full
    matmul precision whatever the compute type::

        r = u W_d + b_d  (+ gamma * prev_state)          the state handed on
        logits = W_3 gelu(W_2 gelu(W_1 N_w(r) + b_1) + b_2)

    The softmax over the ``num_experts + skip`` logits, the choice by ``p +
    bias`` and the gate ``p_e`` are the layer's (``route``: ``ops/moe.py
    top_k_route(score="softmax", bias=)``), so that one function routes every
    dropless layer; the last ``skip`` choices are no experts
    (``MoELayer(router=)``).  ``bias [E + skip]`` is no weight: no
    gradient, no optimizer state, moved inside the step by ``bias_rate *
    sign(mean(load) - load)`` like ``TopKGate``'s.  ``gamma`` (ones at the
    start) enters the graph only where a state comes in.  GELU is the exact
    one (assumed)."""

    def __init__(self, hidden_size, num_experts, width, skip=1, eps=1e-5,
                 bias_rate=None, name=None):
        name = fresh_name(name or "state_router")
        self.num_experts, self.width, self.skip = num_experts, width, skip
        self.eps, self.bias_rate = eps, bias_rate
        out = num_experts + skip

        def var(n, shape, how):
            return VariableOp(f"{name}_{n}", shape, how)
        self.down = var("down_weight", (hidden_size, width),
                        init.xavier_uniform())
        self.down_bias = var("down_bias", (width,), init.zeros())
        self.gamma = var("eda_scale", (width,), init.ones())
        self.norm = var("norm_scale", (width,), init.ones())
        self.w1, self.w2 = (var(f"mlp{i}_weight", (width, width),
                                init.xavier_uniform()) for i in (1, 2))
        self.b1, self.b2 = (var(f"mlp{i}_bias", (width,), init.zeros())
                            for i in (1, 2))
        self.w3 = var("out_weight", (width, out), init.xavier_uniform())
        self.bias = VariableOp(f"{name}_bias", (out,), init.zeros(),
                               trainable=False)
        #: a gate's weight that ``_MoEOp`` would multiply the tokens by: none
        self.wg = None

    def __call__(self, u, prev_state=None):
        from ..ops.base import ScopedOp
        from ..ops.rotary import pair_item_op
        more = () if prev_state is None else (self.gamma, prev_state)
        with scope("hetu_moe_route"):
            both = ScopedOp(_state_router, "hetu_moe_route", u, self.down,
                            self.down_bias, self.norm, self.w1, self.b1,
                            self.w2, self.b2, self.w3, *more, eps=self.eps)
            return pair_item_op(both, index=0), pair_item_op(both, index=1)

    def route(self, tokens, logits, k, bias=None):
        """``(logits, idx, gate, probs)`` as ``TopKGate.route``: the softmax,
        the ``k`` largest of ``p + bias`` and their ``p``, not renormalised."""
        from ..ops.moe import top_k_route
        return (logits,) + top_k_route(logits, k, renorm=False,
                                       score="softmax", bias=bias)


class _MoEOp(Op):
    """Fused gate+dispatch+experts+combine (single graph node so the EP
    sharding annotations stay local to the op)."""

    def __init__(self, x, gate, w1, b1, w2, b2, num_experts, capacity_factor,
                 k, ep_axis=None, ids=None, sparse=True, w3=None,
                 load_var=None, held=None, scores=None, state=None, skip=0,
                 name=None):
        # swiglu experts are biasless: b1/b2 are None and stay out of the
        # graph entirely (no dead optimizer state / checkpoint entries)
        inputs = [x, w1, w2] if b1 is None else [x, w1, b1, w2, b2]
        self.has_biases = b1 is not None
        if w3 is not None:                    # swiglu experts: up proj
            inputs.append(w3)
        # what the router hands the op: a gate's weight, or the logits of a
        # router that is a layer of its own (``StateRouter``)
        self.router_in = gate.wg if scores is None else scores
        if self.router_in is not None:
            inputs.append(self.router_in)
        if ids is not None:
            inputs.append(ids)
        self._state_at = len(inputs) if state is not None else None
        if state is not None:        # read for its RMS alone (the load's row)
            inputs.append(state)
        self.bias_var = getattr(gate, "bias", None)
        self._bias_at = len(inputs)
        if self.bias_var is not None:
            inputs.append(self.bias_var)
        if load_var is not None:
            # last input, read by nobody: it puts the variable into every
            # program that runs this op, so its update has a state to go to
            inputs.append(load_var)
        super().__init__(*inputs, name=name or "moe")
        self.gate = gate
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.k = k
        self.ep_axis = ep_axis
        self.sparse = sparse
        self.has_w3 = w3 is not None
        self.has_ids = ids is not None
        self.load_var = load_var
        self.held = held
        #: the router's last ``skip`` choices are no experts
        self.skip = skip
        assert not skip or (held is not None and held[1] >= 2), (
            "a skip choice is laid out as a pair held nowhere (held=)")
        assert held is None or capacity_factor is None, (
            "a share of the experts (held=) is laid out by the dropless path")
        if capacity_factor is None:
            assert b1 is None and hasattr(gate, "route"), (
                "dropless routing (capacity_factor=None) runs experts "
                "without biases (swiglu, relu2) behind a TopKGate")
        else:
            assert self.bias_var is None and (w3 is not None
                                              or b1 is not None), (
                "a sigmoid-scored router and relu2 experts are laid out by "
                "the dropless path (capacity_factor=None)")

    @property
    def dropless(self):
        return self.capacity_factor is None

    def _unpack(self, input_vals):
        """Input layout shared with MoEAuxLossOp (same inputs list)."""
        if self.has_biases:
            x, w1, b1, w2, b2 = input_vals[:5]
            rest = list(input_vals[5:])
        else:
            x, w1, w2 = input_vals[:3]
            b1 = b2 = None
            rest = list(input_vals[3:])
        w3 = rest.pop(0) if self.has_w3 else None
        wg = rest.pop(0) if self.router_in is not None else None
        ids = rest.pop(0) if self.has_ids else None
        return x, w1, b1, w2, b2, w3, wg, ids

    def _bias(self, input_vals, ctx):
        """The router's selection bias in f32: under a lower compute type
        the f32 master, as an optimizer reads a weight (a bias of 0.5 moved
        by 0.001 in bf16 would not move)."""
        if self.bias_var is None:
            return None
        if ctx.master_params is not None:
            return ctx.master_params[self.bias_var.name]
        return input_vals[self._bias_at]

    def _capacity(self, T):
        return max(int(np.ceil(self.capacity_factor * T * self.k
                               / self.num_experts)), 1)

    def routing(self, input_vals, ctx):
        """The dropless routing of this op's tokens, traced once per trace:
        the loss terms (``MoEAuxLossOp``, ``MoEZLossOp``) read what the layer
        itself routed by.  Keyed by the identity of ``x``, so a node
        evaluated in another trace (a remat body, a second program) routes
        afresh."""
        x, wg = input_vals[0], self._unpack(input_vals)[6]
        memo = ctx.__dict__.setdefault("_moe_routing", {})
        if self.id not in memo or memo[self.id][0] is not x:
            extra = (() if self.bias_var is None
                     else (self._bias(input_vals, ctx),))
            with named_scope("hetu_moe_route"):
                memo[self.id] = (x, self.gate.route(
                    x.reshape(-1, x.shape[-1]), wg, self.k, *extra))
        return memo[self.id][1]

    def _move_bias(self, input_vals, idx, ctx):
        """``bias += rate * sign(mean(load) - load)`` from this step's pair
        counts over all experts, as a state update of the training step."""
        import jax.numpy as jnp
        from ..ops.moe import expert_load
        if (self.bias_var is None or not self.gate.bias_rate
                or not ctx.training):
            return
        rate = self.gate.bias_rate
        with named_scope("hetu_moe_route"):
            load = expert_load(idx, self.num_experts + self.skip).astype(
                jnp.float32)
            bias = self._bias(input_vals, ctx).astype(jnp.float32)
            ctx.record_update(self.bias_var, bias + rate * jnp.sign(
                jnp.mean(load) - load))

    def _record_load(self, ctx, *rows):
        """Hand the per-expert pair counts of this step (routed, computed;
        with ``held`` a third row whose first entry is the pairs routed to
        experts held elsewhere, and a fourth of the pairs computed by a pass
        after the first) to the executor's state (``MoELayer.load()`` fetches
        them beside the loss; with skip choices a fifth: the pairs that
        chose none of the experts, and the carried router state's RMS)."""
        import jax.numpy as jnp
        if self.load_var is not None:
            ctx.record_update(self.load_var, jnp.stack(
                rows).astype(jnp.float32))

    def _compute(self, input_vals, ctx):
        import jax
        import jax.numpy as jnp
        from ..ops.moe import sparse_dispatch, sparse_combine, dropless_moe
        x, w1, b1, w2, b2, w3, wg, ids = self._unpack(input_vals)

        orig_shape = x.shape
        h = x.shape[-1]
        tokens = x.reshape(-1, h)
        T = tokens.shape[0]
        if self.dropless:
            _, idx, gate, _ = self.routing(input_vals, ctx)
            self._move_bias(input_vals, idx, ctx)
            # an expert that is not gated (relu2) has no w3: w1 is its up
            # projection
            w_gate, w_up = (w1, w3) if self.has_w3 else (None, w1)
            if self.held is None:
                y, load = dropless_moe(tokens, idx, gate, w_gate, w_up, w2,
                                       mesh=ctx.mesh)
                self._record_load(ctx, load, load)
                return y.reshape(orig_shape)
            from ..ops.moe import held_rows
            count = self.held[1]
            y, lay = dropless_moe(
                tokens, idx, gate, w_gate, w_up, w2, mesh=ctx.mesh,
                held=self.held,
                rows=held_rows(T * self.k, self.num_experts, count))
            none = jnp.zeros((count,), jnp.int32)
            rows = [lay["load"], lay["computed"],
                    none.at[0].set(lay["elsewhere"]),
                    lay["computed"] - lay["kept"]]
            if self.skip:
                # a pair whose choice is no expert has no row and gives zero;
                # the layout counted it among those held elsewhere
                skipped = jnp.sum(idx >= self.num_experts)
                rows[2] = none.at[0].set(lay["elsewhere"] - skipped)
                last = none.astype(jnp.float32).at[0].set(skipped)
                if self._state_at is not None:
                    r = input_vals[self._state_at].astype(jnp.float32)
                    last = last.at[1].set(jnp.sqrt(jnp.mean(r * r)))
                rows.append(last)
            self._record_load(ctx, *rows)
            return y.reshape(orig_shape)
        C = self._capacity(T)

        # scatter-style dispatch (reference LayoutTransform.cu) when the
        # gate exposes routing CHOICES: memory is O(T·H + E·C·H), never
        # the O(T·E·C) one-hot tensors of the dense einsum form — at real
        # T·E·C those are the memory wall (SURVEY §2.1 N3).  Gates
        # without a choices form (BASE auction) keep the dense path.
        sparse = self.sparse and hasattr(self.gate, "gating_choices")
        if sparse:
            choices, aux = self.gate.gating_choices(tokens, wg, ids,
                                                    self.k, C)
            # pallas_call does not partition under GSPMD: inside ANY
            # meshed program (ep-sharded or just dp) the gather lowers
            # via XLA instead; row_gather records which form ran
            # (pallas/dispatch.py)
            pallas_ok = ctx.mesh is None
            expert_in = sparse_dispatch(tokens, choices,
                                        self.num_experts, C,
                                        use_pallas=pallas_ok)
            if self.load_var is not None:
                hot = [jax.nn.one_hot(i, self.num_experts,
                                      dtype=jnp.float32)
                       for i, _, _ in choices]
                self._record_load(
                    ctx, sum(o.sum(0) for o in hot),
                    sum((o * (p < C)[:, None]).sum(0)
                        for o, (_, _, p) in zip(hot, choices)))
        else:
            dispatch, combine, aux = self.gate.gating(tokens, wg, ids,
                                                      self.k, C)
            expert_in = jnp.einsum("tec,th->ech", dispatch, tokens)
            # a gate without a choices form does not say what it routed
            # beyond capacity: only the kept pairs are known
            kept = jnp.sum(dispatch, axis=(0, 2))
            self._record_load(ctx, kept, kept)
        if self.ep_axis is not None and ctx.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            expert_in = jax.lax.with_sharding_constraint(
                expert_in, NamedSharding(ctx.mesh,
                                         P(self.ep_axis, None, None)))
        # per-expert FFN: [E, C, H] @ [E, H, F] -> [E, C, F]
        if self.has_w3:
            # swiglu experts (Mixtral-style): silu(x@w1) * (x@w3) @ w2
            a = (jax.nn.silu(jnp.einsum("ech,ehf->ecf", expert_in, w1))
                 * jnp.einsum("ech,ehf->ecf", expert_in, w3))
            out = jnp.einsum("ecf,efh->ech", a, w2)
        else:
            a = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", expert_in, w1)
                            + b1[:, None, :])
            out = jnp.einsum("ecf,efh->ech", a, w2) + b2[:, None, :]
        if self.ep_axis is not None and ctx.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            out = jax.lax.with_sharding_constraint(
                out, NamedSharding(ctx.mesh, P(self.ep_axis, None, None)))
        if sparse:
            combined = sparse_combine(out, choices,
                                      use_pallas=pallas_ok)
        else:
            combined = jnp.einsum("ech,tec->th", out, combine)
        return combined.reshape(orig_shape)


class MoEAuxLossOp(Op):
    def __init__(self, moe_op):
        super().__init__(*moe_op.inputs, name=f"{moe_op.name}_aux")
        self.moe = moe_op

    def _compute(self, input_vals, ctx):
        # aux-only gate path: O(T·E) logits work, never the [T,E,C]
        # dispatch/combine tensors — an aux evaluated in a separate
        # subexecutor from the MoE op must not pay the full dispatch
        # recompute (in the same jitted program, CSE merges it anyway)
        import jax.numpy as jnp
        x, _, _, _, _, _, wg, ids = self.moe._unpack(input_vals)
        if self.moe.dropless:
            # balance loss over top-k counts, from the routing the layer
            # itself ran by (ops/moe.py load_balancing_loss)
            from ..ops.moe import load_balancing_loss, expert_load
            _, idx, _, probs = self.moe.routing(input_vals, ctx)
            load = expert_load(idx, self.moe.num_experts)
            if getattr(self.moe.gate, "score", "softmax") == "sigmoid":
                # the share of the pairs, DeepSeek-V3's f_i
                return load_balancing_loss(probs, load, pairs=idx.size)
            return load_balancing_loss(probs, load)
        if not getattr(self.moe.gate, "has_aux", True):
            # hash/balance gates have identically-zero aux: skip the
            # dispatch recompute entirely
            return jnp.asarray(0.0, x.dtype)
        tokens = x.reshape(-1, x.shape[-1])
        aux_fn = getattr(self.moe.gate, "aux", None)
        if aux_fn is not None:
            aux = aux_fn(tokens, wg, ids, self.moe.k)
        else:
            # caller-built gate without the aux-only fast path: fall back
            # to full gating (CSE removes the cost when jitted with the
            # MoE op)
            _, _, aux = self.moe.gate.gating(
                tokens, wg, ids, self.moe.k,
                self.moe._capacity(tokens.shape[0]))
        return jnp.asarray(aux, x.dtype)


class MoEZLossOp(Op):
    """Router z-loss ``mean_t logsumexp(logits_t)^2`` of a dropless MoE op,
    in f32 (ST-MoE; OLMoE trains with 0.001 of it a layer)."""

    def __init__(self, moe_op):
        assert moe_op.dropless, "the z-loss reads the dropless routing"
        super().__init__(*moe_op.inputs, name=f"{moe_op.name}_zloss")
        self.moe = moe_op

    def _compute(self, input_vals, ctx):
        from ..ops.moe import router_z_loss
        return router_z_loss(self.moe.routing(input_vals, ctx)[0])


class MoEChosenOp(Op):
    """``[T, k]`` int32: the experts each token of a dropless MoE op takes,
    largest weight first (for checks against a reference's routing)."""

    def __init__(self, moe_op):
        assert moe_op.dropless
        super().__init__(*moe_op.inputs, name=f"{moe_op.name}_chosen")
        self.moe = moe_op

    def _compute(self, input_vals, ctx):
        return self.moe.routing(input_vals, ctx)[1]


def _shared_expert(x, *w, act="swiglu", gated=True):
    import jax
    import jax.numpy as jnp
    w = list(w)
    w_sg = w.pop() if gated else None
    if act == "swiglu":
        w_gate, w_up, w_down = w
        y = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
    else:
        w_up, w_down = w
        y = jnp.square(jax.nn.relu(x @ w_up)) @ w_down
    return y if w_sg is None else y * jax.nn.sigmoid(x @ w_sg)


def shared_expert_op(x, *w, act="swiglu", gated=True):
    """The shared expert of a sparse block, an FFN every token goes through.
    Qwen2-MoE and Qwen3-Next: SwiGLU, scaled token by token by the sigmoid
    of a one-column gate, ``sigmoid(x w_sg) W_d (silu(W_g x) * W_u x)``
    (``w`` = gate, up, down, sigmoid).  Nemotron-H: ``act="relu2"``,
    ``gated=False``: ``W_d relu(W_u x)^2`` (``w`` = up, down).  Its device
    operations carry the scope ``hetu_moe_shared``."""
    from ..ops.base import ScopedOp
    return ScopedOp(_shared_expert, "hetu_moe_shared", x, *w, act=act,
                    gated=gated)


class MoELoadOp(Op):
    """``[2, E]`` f32: the (token, choice) pairs routed to each expert and
    those of them the layer computed (all, on the dropless path), as the
    MoE op of THIS step recorded them.  The MoE op runs inside the
    gradient's vjp, whose interior the step's other fetches cannot see; it
    hands the counts out as a state update and this node reads that update,
    so fetching it beside the loss adds 512 bytes to the step's one
    device-to-host copy and no second pass.  In a program where the MoE op
    has not run by the time this node is evaluated it gives the state's
    value, the last step's counts.  ``MoELayer.router_bias()`` reads the
    router's selection bias, as this step moved it, the same way."""

    def __init__(self, load_var):
        super().__init__(load_var, name=f"{load_var.name}_read")
        self.var = load_var

    def _compute(self, input_vals, ctx):
        if self.var in ctx.updates:
            return ctx.updates[self.var]
        if ctx.master_params is not None:   # not the compute type's rounding
            return ctx.master_params[self.var.name]
        return input_vals[0]


class MoELayer(BaseLayer):
    """Expert-parallel FFN block (drop-in for TransformerFFN).

    ``capacity_factor=None`` is the dropless path (ops/moe.py
    ``dropless_moe``): every (token, choice) pair is computed, by grouped
    products over the pairs sorted by expert; it needs the ``top`` gate and
    experts without biases: ``expert_act="swiglu"`` (``silu(x W1) * (x W3)``
    then ``W2``, three grouped products a pass) or ``"relu2"`` (``relu(x
    W1)^2`` then ``W2``, two; no ``w3``).  ``"gelu"`` experts have biases and
    run behind a capacity; ``"relu2"`` runs on the dropless path alone.
    ``renorm_topk`` is the gate's ``renorm``; ``router_score="sigmoid"``,
    ``router_scale`` and ``router_bias_rate`` are the gate's ``score``,
    ``scale`` and ``bias_rate``, ``router_groups`` its ``groups`` (``TopKGate``;
    dropless path alone), and
    ``router_bias()`` fetches the bias as ``load()`` fetches the load.
    ``track_load`` adds a ``[2, E]`` state variable of per-expert pair
    counts (routed, kept) that ``load()`` fetches.

    ``held=(first, count)`` is one device's share of an expert-parallel
    layer without the other devices: the router keeps its ``num_experts``
    outputs and ``k`` a token, the layer holds the weights of the experts
    ``first .. first + count - 1`` alone and computes only the pairs routed to
    them (dropless path); what the absent experts would add is left out and
    nothing stands in for it.  One pass lays out rows for twice the mean
    share (``ops/moe.py held_rows``); pairs a batch routes here over that
    bound are computed by further passes (``dropless_moe``), none is dropped.
    The load is ``[4, count]`` then: routed here, computed (the same),
    in ``[2, 0]`` the pairs routed elsewhere, and the pairs that took a pass
    after the first.
    ``router=`` is a router that is a layer of its own (``StateRouter``):
    the op takes its logits where it took the gate's weight, selects on
    ``softmax + bias`` and hands the router's state on (``__call__(x,
    state=)``, ``self.state``).  Its last ``router.skip`` outputs are no
    experts: a pair that chooses one goes to no expert, gives zero, and is
    counted apart in a fifth row of the load (``[4, 0]``; ``[4, 1]`` is the
    RMS of the router state); the layer is laid out as a held one (all its
    experts where ``held`` is None).
    ``shared_width`` adds a shared expert of that width and of the experts'
    kind (swiglu or relu2), computed for every token, and ``shared_gate``
    says whether the sigmoid of a one-column gate scales it
    (``shared_expert_op``)."""

    def __init__(self, hidden_size, intermediate_size, num_experts, k=2,
                 capacity_factor=1.25, gate="top", ep_axis=None,
                 num_groups=None, sparse=True, expert_act="gelu",
                 renorm_topk=True, track_load=False, held=None,
                 shared_width=None, shared_gate=True, router_score="softmax",
                 router_scale=None, router_bias_rate=None,
                 router_groups=None, router=None, name=None):
        name = fresh_name(name or "moe")
        self.router, self.skip = router, getattr(router, "skip", 0)
        #: the router's state node of the last call (``router=``)
        self.state = None
        if router is not None:
            assert capacity_factor is None and gate == "top", (
                "a router layer routes the dropless path")
            assert router.num_experts == num_experts, (
                "the router's width is the layer's")
            gate = router
        if self.skip and held is None:
            # a pair that chose no expert is laid out as one held nowhere
            held = (0, num_experts)
        self.held = held
        n_held = num_experts
        if held is not None:
            first, n_held = held
            assert 0 <= first and first + n_held <= num_experts, held
            assert ep_axis is None, "held= is one device's share, unsharded"
        if isinstance(gate, BaseLayer):
            self.gate = gate                      # caller-built gate
        elif gate == "top":
            self.gate = TopKGate(hidden_size, num_experts,
                                 renorm=renorm_topk, name=name,
                                 score=router_score, scale=router_scale,
                                 bias_rate=router_bias_rate,
                                 groups=router_groups)
            assert router_groups is None or capacity_factor is None, (
                "group-limited selection is the dropless path's")
        elif gate == "hash":
            self.gate = HashGate(num_experts)
        elif gate == "ktop1":
            self.gate = KTop1Gate(hidden_size, num_experts, name=name)
        elif gate == "sam":
            self.gate = SAMGate(hidden_size, num_experts,
                                num_groups or 2, name=name)
        elif gate == "balance":
            self.gate = BalanceGate(hidden_size, num_experts, name=name)
        else:
            raise ValueError(gate)
        assert expert_act in ("gelu", "swiglu", "relu2"), expert_act
        assert router_score == "softmax" or gate == "top", (
            "the sigmoid-scored router is the top gate's")
        assert capacity_factor is None or (
            expert_act != "relu2" and router_score == "softmax"), (
            "relu2 experts and the sigmoid-scored router run on the "
            "dropless path (capacity_factor=None)")
        self.expert_act = expert_act
        self.w1 = VariableOp(f"{name}_w1",
                             (n_held, hidden_size, intermediate_size),
                             init.xavier_uniform())
        self.b1 = VariableOp(f"{name}_b1", (n_held, intermediate_size),
                             init.zeros()) \
            if expert_act == "gelu" else None
        self.w2 = VariableOp(f"{name}_w2",
                             (n_held, intermediate_size, hidden_size),
                             init.xavier_uniform())
        self.b2 = VariableOp(f"{name}_b2", (n_held, hidden_size),
                             init.zeros()) \
            if expert_act == "gelu" else None
        # swiglu experts (Mixtral-style, reference-beyond): gated FFN
        # silu(x@w1) * (x@w3) @ w2, no biases
        self.w3 = VariableOp(f"{name}_w3",
                             (n_held, hidden_size, intermediate_size),
                             init.xavier_uniform()) \
            if expert_act == "swiglu" else None
        self.shared = None
        self.shared_kind = dict(act=expert_act, gated=bool(shared_gate))
        if shared_width:
            assert expert_act in ("swiglu", "relu2"), expert_act
            parts = ([("gate", (hidden_size, shared_width))]
                     if expert_act == "swiglu" else [])
            parts += [("up", (hidden_size, shared_width)),
                      ("out", (shared_width, hidden_size))]
            if shared_gate:
                parts.append(("sigmoid", (hidden_size, 1)))
            self.shared = tuple(
                VariableOp(f"{name}_shared_{n}", shape, init.xavier_uniform())
                for n, shape in parts)
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.k = k
        self.ep_axis = ep_axis
        # sparse=False forces the dense one-hot einsum dispatch (debug /
        # exactness oracle); sparse routing needs a gate with a choices
        # form and is the default memory-safe path
        self.sparse = sparse
        self.load_var = VariableOp(
            f"{name}_load",
            (2, num_experts) if held is None
            else (5 if self.skip else 4, n_held), init.zeros(),
            trainable=False) if track_load else None
        if ep_axis is not None:
            ep_vars = [v for v in (self.w1, self.b1, self.w2, self.b2,
                                   self.w3) if v is not None]
            for v in ep_vars:
                from ..parallel.mesh import DistState
                v.dist_state = DistState({0: ep_axis})
        self.last_op = None

    def __call__(self, x, ids=None, state=None):
        """``state``: the router state the layer above handed on
        (``router=``; this layer's is ``self.state`` afterwards)."""
        more = {}
        if self.router is not None:
            scores, self.state = self.router(x, state)
            more = dict(scores=scores, state=self.state, skip=self.skip)
        elif self.gate.wg is None and ids is None:
            raise ValueError(
                "hash-gated MoELayer requires token ids: moe(x, ids=...)")
        # what of the block lies outside its five regions (the reshapes,
        # the load's counts, the loop of further passes, the sum with the
        # shared expert) has a name of its own: the regions' names are what
        # the moe_block metrics read, and hold what they held
        with scope("hetu_moe_other"):
            self.last_op = _MoEOp(x, self.gate, self.w1, self.b1, self.w2,
                                  self.b2, self.num_experts,
                                  self.capacity_factor, self.k,
                                  ep_axis=self.ep_axis, ids=ids,
                                  sparse=self.sparse, w3=self.w3,
                                  load_var=self.load_var, held=self.held,
                                  **more)
            if self.shared is not None:
                return self.last_op + shared_expert_op(x, *self.shared,
                                                       **self.shared_kind)
        return self.last_op

    def aux_loss(self):
        assert self.last_op is not None
        with scope("hetu_loss"):
            return MoEAuxLossOp(self.last_op)

    def z_loss(self):
        assert self.last_op is not None
        with scope("hetu_loss"):
            return MoEZLossOp(self.last_op)

    def chosen(self):
        assert self.last_op is not None
        with scope("hetu_moe_other"):
            return MoEChosenOp(self.last_op)

    def load(self):
        assert self.load_var is not None, "MoELayer(track_load=True)"
        with scope("hetu_moe_other"):
            return MoELoadOp(self.load_var)

    def router_bias(self):
        """``[E]`` f32: the router's selection bias after this step's move
        (``router_score="sigmoid"``)."""
        assert getattr(self.gate, "bias", None) is not None
        with scope("hetu_moe_other"):
            return MoELoadOp(self.gate.bias)


def record_moe_load(layer, load, bias=None):
    """Count one step's per-expert load of MoE layer ``layer`` (a label) in
    the telemetry registry.  ``load`` is the fetched value of
    ``MoELayer.load()``, ``[2, E]``: pairs routed and pairs computed.
    ``bias``, the fetched value of ``MoELayer.router_bias()``, sets
    ``hetu_moe_router_bias_max_abs{layer}``: how far the sigmoid-scored
    router's selection bias has moved from zero, over all experts.

    * ``hetu_moe_pairs_routed_total{layer}``: (token, choice) pairs routed;
    * ``hetu_moe_pairs_dropped_total{layer}``: those of them no expert
      computed (capacity overflow; the dropless path keeps it at 0);
    * ``hetu_moe_expert_load_max_over_mean{layer}``: the fullest expert's
      pairs over the mean, this step (1.0 is perfectly even).

    From a layer that holds a share of its experts (``MoELayer(held=)``,
    ``[4, count]``) ``routed``, ``dropped`` and the gauge are over the held
    experts, ``hetu_moe_pairs_elsewhere_total{layer}`` counts the pairs the
    router sent to experts this device does not hold, and
    ``hetu_moe_pairs_over_bound_total{layer}`` those of the held experts'
    pairs that one pass's rows did not hold and a further pass computed.
    From a layer with skip choices (``[5, count]``)
    ``hetu_moe_pairs_skipped_total{layer}`` counts the pairs that chose no
    expert and ``hetu_moe_router_state_rms{layer}`` is the RMS of the router
    state the layer handed on, last step.

    The registry counts nothing while telemetry is disabled."""
    from .. import telemetry
    reg = telemetry.get_registry()
    if bias is not None:
        reg.gauge("hetu_moe_router_bias_max_abs",
                  "Largest |selection bias| of the router, last step",
                  labels=("layer",)).labels(layer=layer).set(
                      float(np.abs(np.asarray(bias, np.float64)).max()))
    load = np.asarray(load, np.float64)
    routed, kept = load[:2]
    total = routed.sum()
    if len(load) > 2 and total + load[2, 0] > 0:
        reg.counter("hetu_moe_pairs_elsewhere_total",
                    "Routed pairs whose expert another device holds",
                    labels=("layer",)).labels(layer=layer).inc(load[2, 0])
        reg.counter("hetu_moe_pairs_over_bound_total",
                    "Pairs on held experts computed by a pass after the first",
                    labels=("layer",)).labels(layer=layer).inc(
                        load[3].sum() if len(load) > 3 else 0)
    if len(load) > 4 and total + load[2, 0] + load[4, 0] > 0:
        reg.counter("hetu_moe_pairs_skipped_total",
                    "Routed pairs whose choice is no expert (computed by none)",
                    labels=("layer",)).labels(layer=layer).inc(load[4, 0])
        reg.gauge("hetu_moe_router_state_rms",
                  "RMS of the router state a layer handed on, last step",
                  labels=("layer",)).labels(layer=layer).set(load[4, 1])
    if total <= 0:          # the state's initial zeros: no step has run
        return
    reg.counter("hetu_moe_pairs_routed_total",
                "(token, choice) pairs the router sent to an expert",
                labels=("layer",)).labels(layer=layer).inc(total)
    reg.counter("hetu_moe_pairs_dropped_total",
                "Routed pairs no expert computed (capacity overflow)",
                labels=("layer",)).labels(layer=layer).inc(
                    total - kept.sum())
    reg.gauge("hetu_moe_expert_load_max_over_mean",
              "Pairs at the fullest expert over the mean, last step",
              labels=("layer",)).labels(layer=layer).set(
                  routed.max() * routed.size / total)
