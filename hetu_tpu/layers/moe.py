"""MoE layer with expert parallelism.

Reference: /root/reference/python/hetu/layers/moe_layer.py — MoELayer:
reshape → gate → layout_transform → alltoall → expert FFNs → alltoall →
reverse_layout_transform (:60-88); BASE-layer variant (:90) with balance
assignment; gates in layers/{TopGate,KTop1Gate,HashGate,SAMGate,BalanceGate}.

TPU redesign: one graph node a layer, of one of two regimes that share no
line (``MoELayer`` chooses by ``capacity_factor is None``).  Dropless
(``_DroplessOp``; every cell with experts): the pairs sorted by expert and
the experts as grouped products over the ragged groups (``ops/moe.py
dropless_moe``), all of them or one device's share (``held=``).  Capacity
(``_CapacityOp``; the reference's gates): ``[E, C, H]`` expert inputs gathered
by the gate's routing choices (``sparse_dispatch`` / ``sparse_combine``; the
dense one-hot einsums for a gate without a choices form), whose expert
dimension and the expert weights' carry an 'ep' mesh-axis annotation, so
GSPMD inserts the all-to-all pair the reference ran as explicit AllToAllOps
(for multi-node topologies, parallel/collectives.hierarchical_all_to_all
composes the DCN×ICI staging explicitly inside shard_map).
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

    def route(self, tokens, wg, k, bias=None, mesh=None):
        """Dropless routing: ``(logits, idx, gate, probs)``, the logits and
        everything after them in f32 at full matmul precision, so that which
        experts a token takes does not depend on the compute type.  ``mesh``:
        the node's, for the kernel behind the choice (``ops/moe.py
        select_k``)."""
        import jax
        import jax.numpy as jnp
        from ..ops.moe import top_k_route
        logits = jnp.matmul(tokens.astype(jnp.float32),
                            wg.astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        return (logits,) + top_k_route(logits, k, renorm=self.renorm,
                                       score=self.score, bias=bias,
                                       scale=self.scale, groups=self.groups,
                                       mesh=mesh)

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

    def route(self, tokens, logits, k, bias=None, mesh=None):
        """``(logits, idx, gate, probs)`` as ``TopKGate.route``: the softmax,
        the ``k`` largest of ``p + bias`` and their ``p``, not renormalised."""
        from ..ops.moe import top_k_route
        return (logits,) + top_k_route(logits, k, renorm=False,
                                       score="softmax", bias=bias, mesh=mesh)


#: the rows of a layer's load (``MoELayer.load()``), an entry an expert the
#: layer holds: the pairs routed and those of them computed; of a share of
#: the experts (``held=``) also ``[ELSEWHERE, 0]``, the pairs routed to the
#: others, and the pairs a pass after the first computed; with skip choices
#: ``[SKIPPED, 0]``, the pairs that chose no expert, and ``[SKIPPED, 1]``,
#: the RMS of the router state handed on
ROUTED, COMPUTED, ELSEWHERE, LATER, SKIPPED = range(5)


class _MoEOp(Op):
    """Gate, dispatch, experts and combine as ONE graph node (so the EP
    sharding annotations stay local to the op): what the op of either regime
    is.  The inputs go by NAME (``at``: name -> place in ``inputs``, of the
    nodes that are not None in the order given; ``read``), the loss-side
    nodes' too: they are built from ``op.inputs``."""

    def __init__(self, gate, k, num_experts, load_var, name, **named):
        named = {n: node for n, node in named.items() if node is not None}
        if load_var is not None:
            # read by nobody: it puts the variable into every program that
            # runs this op, so its update has a state to go to
            named["load"] = load_var
        super().__init__(*named.values(), name=name or "moe")
        self.at = {n: i for i, n in enumerate(named)}
        self.gate, self.k, self.num_experts = gate, k, num_experts
        self.load_var = load_var

    def read(self, input_vals, name):
        """The value of the input ``name``; None where the op has none."""
        return input_vals[self.at[name]] if name in self.at else None

    def _record_load(self, ctx, *rows):
        """Hand this step's counts (the rows ``ROUTED``, ``COMPUTED``, ..) to
        the executor's state (``MoELayer.load()`` fetches them)."""
        import jax.numpy as jnp
        if self.load_var is not None:
            ctx.record_update(self.load_var, jnp.stack(
                rows).astype(jnp.float32))


class _DroplessOp(_MoEOp):
    """No capacity: every (token, choice) pair routed to an expert this
    layer holds is computed (``ops/moe.py dropless_moe``).  ``gate`` routes
    (``route``): a ``TopKGate`` on its weight, or a ``StateRouter`` on the
    logits ``scores`` it made, with the ``state`` it hands on (read for its
    RMS alone); ``w3=None`` is an expert that is not gated (relu2: ``w1`` is
    its up projection); the gate's last ``skip`` choices are no experts."""

    def __init__(self, x, gate, w1, w2, w3, k, num_experts, held=None,
                 scores=None, state=None, load_var=None, ep_axis=None,
                 name=None):
        assert hasattr(gate, "route"), "the dropless op routes by gate.route"
        self.held, self.skip = held, getattr(gate, "skip", 0)
        self.ep_axis = ep_axis
        assert ep_axis is None or (held is None and scores is None), (
            "experts spread over an axis: all of them, routed by a gate's "
            "weight")
        #: bytes a device receives in the forward pass's all-gather and
        #: reduce-scatter (``exchange_bytes``), known once the op is traced
        #: under a mesh
        self.exchange = None
        assert not self.skip or (held is not None and held[1] >= 2), (
            "a skip choice is laid out as a pair held nowhere (held=)")
        self.bias_var = getattr(gate, "bias", None)
        super().__init__(gate, k, num_experts, load_var, name, x=x, w1=w1,
                         w2=w2, w3=w3,
                         router=gate.wg if scores is None else scores,
                         state=state, bias=self.bias_var)

    def _bias(self, input_vals, ctx):
        """The router's selection bias in f32: under a lower compute type
        the f32 master, as an optimizer reads a weight (a bias of 0.5 moved
        by 0.001 in bf16 would not move); None where the gate has none."""
        if self.bias_var is not None and ctx.master_params is not None:
            return ctx.master_params[self.bias_var.name]
        return self.read(input_vals, "bias")

    def routing(self, input_vals, ctx):
        """The routing of this op's tokens, traced once per trace: the loss
        terms (``MoEAuxLossOp``, ``MoEZLossOp``) read what the layer itself
        routed by.  Keyed by the identity of ``x``, so a node evaluated in
        another trace (a remat body, a second program) routes afresh."""
        x = self.read(input_vals, "x")
        memo = ctx.__dict__.setdefault("_moe_routing", {})
        if self.id not in memo or memo[self.id][0] is not x:
            tokens = x.reshape(-1, x.shape[-1])
            router = self.read(input_vals, "router")
            bias = self._bias(input_vals, ctx)
            with named_scope("hetu_moe_route"):
                if self._axis_size(ctx, x) is None:
                    routed = self.gate.route(tokens, router, self.k,
                                             bias=bias, mesh=ctx.mesh)
                else:
                    # each device routes its own tokens: a kernel sees no
                    # mesh inside (``select_k``)
                    whole = (router,) + (() if bias is None else (bias,))
                    routed = self._per_shard(
                        ctx, lambda t, r, *b: self.gate.route(
                            t, r, self.k, bias=b[0] if b else None),
                        (tokens,) + whole,
                        sharded=(True,) + (False,) * len(whole),
                        out_sharded=(True,) * 4)
                memo[self.id] = (x, routed)
        return memo[self.id][1]

    def _axis_size(self, ctx, x):
        """How many devices the experts are spread over (``ep_axis``), None
        where they are not: no axis, no mesh, or an axis of one."""
        if self.ep_axis is None or ctx.mesh is None:
            return None
        n = ctx.mesh.shape.get(self.ep_axis, 1)
        assert x.shape[0] % n == 0, (
            f"the batch {x.shape[0]} is divided over the {n} devices of "
            f"{self.ep_axis!r}")
        return n if n > 1 else None

    def _per_shard(self, ctx, fn, args, sharded, out_sharded):
        """``fn`` on each device of ``ep_axis`` under ``shard_map``: an
        argument (a result) that is ``sharded`` has its dim 0 on the axis,
        any other is whole on every device."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        on, whole = P(self.ep_axis), P()
        # pallas out_shapes carry no varying-axes annotations
        return shard_map(
            fn, mesh=ctx.mesh, in_specs=tuple(on if s else whole
                                              for s in sharded),
            out_specs=tuple(on if s else whole for s in out_sharded),
            check_vma=False)(*args)

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

    def _load_rows(self, input_vals, idx, counts):
        """The load's rows from ``dropless_moe``'s ``counts``."""
        import jax.numpy as jnp
        rows = [counts["load"], counts["computed"]]
        if self.ep_axis is not None:
            # the host's counts over all experts: none is held elsewhere
            later = counts.get("later", jnp.zeros_like(rows[0]))
            return rows + [jnp.zeros_like(rows[0]), later]
        if self.held is None:
            return rows
        none = jnp.zeros((self.held[1],), jnp.int32)
        if not self.skip:
            return rows + [none.at[0].set(counts["elsewhere"]),
                           counts["computed"] - counts["kept"]]
        later = counts["computed"] - counts["kept"]   # (the program's order)
        # a pair whose choice is no expert has no row and gives zero; the
        # layout counted it among those held elsewhere
        skipped = jnp.sum(idx >= self.num_experts)
        rows += [none.at[0].set(counts["elsewhere"] - skipped), later]
        last = none.astype(jnp.float32).at[0].set(skipped)
        state = self.read(input_vals, "state")
        if state is not None:
            r = state.astype(jnp.float32)
            last = last.at[1].set(jnp.sqrt(jnp.mean(r * r)))
        return rows + [last]

    def _compute(self, input_vals, ctx):
        from ..ops.moe import dropless_moe, held_rows
        x, w1, w2, w3 = (self.read(input_vals, n)
                         for n in ("x", "w1", "w2", "w3"))
        tokens = x.reshape(-1, x.shape[-1])
        _, idx, gate, _ = self.routing(input_vals, ctx)
        self._move_bias(input_vals, idx, ctx)
        w_gate, w_up = (None, w1) if w3 is None else (w1, w3)
        n = self._axis_size(ctx, x)
        if n is None:
            y, counts = dropless_moe(
                tokens, idx, gate, w_gate, w_up, w2, mesh=ctx.mesh,
                held=self.held, rows=self.held and held_rows(
                    idx.size, self.num_experts, self.held[1]))
        else:
            y, counts = self._over_axis(ctx, n, tokens, idx, gate,
                                        [w_gate, w_up, w2])
        self._record_load(ctx, *self._load_rows(input_vals, idx, counts))
        return y.reshape(x.shape)

    def _over_axis(self, ctx, n, tokens, idx, gate, weights):
        """``dropless_moe_over_axis`` on the ``n`` devices of ``ep_axis``:
        the tokens' dim 0 and the expert stacks' dim 0 on the axis."""
        from .. import telemetry
        from ..ops.moe import dropless_moe_over_axis, exchange_bytes
        names = ("load", "computed", "later")
        gated = weights[0] is not None

        def local(tokens, idx, gate, *w):
            y, host = dropless_moe_over_axis(
                tokens, idx, gate, *(w if gated else (None,) + w),
                axis=self.ep_axis, num_experts=self.num_experts)
            return (y,) + tuple(host[name] for name in names)
        self.exchange = exchange_bytes(
            tokens.shape[0] // n, tokens.shape[1], self.k, n,
            tokens.dtype.itemsize)
        telemetry.get_registry().gauge(
            "hetu_moe_expert_axis_size",
            "Devices the experts of the dropless layers traced last are "
            "spread over (MoELayer(ep_axis=))").set(n)
        w = tuple(a for a in weights if a is not None)
        y, *counts = self._per_shard(
            ctx, local, (tokens, idx, gate) + w, sharded=(True,) * (3 + len(w)),
            out_sharded=(True, False, False, False))
        return y, dict(zip(names, counts))

    def aux(self, input_vals, ctx):
        """The balance loss over top-k counts, from the routing the layer
        itself ran by (``ops/moe.py load_balancing_loss``)."""
        from ..ops.moe import load_balancing_loss, expert_load
        _, idx, _, probs = self.routing(input_vals, ctx)
        load = expert_load(idx, self.num_experts)
        if getattr(self.gate, "score", "softmax") == "sigmoid":
            # the share of the pairs, DeepSeek-V3's f_i
            return load_balancing_loss(probs, load, pairs=idx.size)
        return load_balancing_loss(probs, load)


class _CapacityOp(_MoEOp):
    """``[E, C, H]`` expert inputs with ``C`` from ``capacity_factor``; a
    pair over an expert's capacity is dropped (the reference's regime).
    Experts with biases (gelu) or gated without (swiglu: ``w3``); ``ep_axis``
    shards the expert dimension; ``ids`` is what a hash gate routes by."""

    def __init__(self, x, gate, w1, b1, w2, b2, w3, k, num_experts,
                 capacity_factor, ep_axis=None, ids=None, load_var=None,
                 name=None):
        assert hasattr(gate, "gating"), "the capacity op routes by gate.gating"
        assert (b1 is None) != (w3 is None), (
            "experts with biases (gelu) or gated ones without (swiglu)")
        self.capacity_factor, self.ep_axis = capacity_factor, ep_axis
        super().__init__(gate, k, num_experts, load_var, name, x=x, w1=w1,
                         b1=b1, w2=w2, b2=b2, w3=w3, router=gate.wg, ids=ids)

    def _capacity(self, T):
        return max(int(np.ceil(self.capacity_factor * T * self.k
                               / self.num_experts)), 1)

    def _on_ep(self, a, ctx):
        """``a [E, C, ..]`` with its expert dimension on ``ep_axis``."""
        import jax
        if self.ep_axis is None or ctx.mesh is None:
            return a
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.lax.with_sharding_constraint(
            a, NamedSharding(ctx.mesh, P(self.ep_axis, None, None)))

    def _compute(self, input_vals, ctx):
        import jax
        import jax.numpy as jnp
        from ..ops.moe import sparse_dispatch, sparse_combine
        x, w1, b1, w2, b2, w3, wg, ids = (
            self.read(input_vals, n) for n in
            ("x", "w1", "b1", "w2", "b2", "w3", "router", "ids"))
        tokens = x.reshape(-1, x.shape[-1])
        E, C = self.num_experts, self._capacity(tokens.shape[0])

        # scatter-style dispatch (reference LayoutTransform.cu) when the
        # gate exposes routing CHOICES: memory is O(T·H + E·C·H), never the
        # O(T·E·C) one-hot tensors of the dense einsum form, the memory wall
        # at real T·E·C (SURVEY §2.1 N3).  Gates without a choices form
        # (BASE auction; the tests' oracle) keep the dense einsums.
        sparse = hasattr(self.gate, "gating_choices")
        if sparse:
            choices, _ = self.gate.gating_choices(tokens, wg, ids, self.k, C)
            expert_in = sparse_dispatch(tokens, choices, E, C)
            if self.load_var is not None:
                hot = [jax.nn.one_hot(i, E, dtype=jnp.float32)
                       for i, _, _ in choices]
                self._record_load(
                    ctx, sum(o.sum(0) for o in hot),
                    sum((o * (p < C)[:, None]).sum(0)
                        for o, (_, _, p) in zip(hot, choices)))
        else:
            dispatch, combine, _ = self.gate.gating(tokens, wg, ids,
                                                    self.k, C)
            expert_in = jnp.einsum("tec,th->ech", dispatch, tokens)
            # a gate without a choices form does not say what it routed
            # beyond capacity: only the kept pairs are known
            kept = jnp.sum(dispatch, axis=(0, 2))
            self._record_load(ctx, kept, kept)
        expert_in = self._on_ep(expert_in, ctx)
        # per-expert FFN: [E, C, H] @ [E, H, F] -> [E, C, F]
        if w3 is not None:
            # swiglu experts (Mixtral-style): silu(x@w1) * (x@w3) @ w2
            a = (jax.nn.silu(jnp.einsum("ech,ehf->ecf", expert_in, w1))
                 * jnp.einsum("ech,ehf->ecf", expert_in, w3))
            out = jnp.einsum("ecf,efh->ech", a, w2)
        else:
            a = jax.nn.gelu(jnp.einsum("ech,ehf->ecf", expert_in, w1)
                            + b1[:, None, :])
            out = jnp.einsum("ecf,efh->ech", a, w2) + b2[:, None, :]
        out = self._on_ep(out, ctx)
        if sparse:
            combined = sparse_combine(out, choices)
        else:
            combined = jnp.einsum("ech,tec->th", out, combine)
        return combined.reshape(x.shape)

    def aux(self, input_vals, ctx):
        """The gate's balance term by its aux-only form: O(T·E) logits work,
        never the [T,E,C] dispatch/combine tensors (an aux evaluated in another
        program than the MoE op must not pay the dispatch again)."""
        import jax.numpy as jnp
        x, wg, ids = (self.read(input_vals, n)
                      for n in ("x", "router", "ids"))
        if not getattr(self.gate, "has_aux", True):
            # hash/balance gates have identically-zero aux: skip the
            # dispatch recompute entirely
            return jnp.asarray(0.0, x.dtype)
        tokens = x.reshape(-1, x.shape[-1])
        if hasattr(self.gate, "aux"):
            aux = self.gate.aux(tokens, wg, ids, self.k)
        else:
            # caller-built gate without the aux-only fast path: fall back
            # to full gating (CSE removes the cost when jitted with the
            # MoE op)
            _, _, aux = self.gate.gating(tokens, wg, ids, self.k,
                                         self._capacity(tokens.shape[0]))
        return jnp.asarray(aux, x.dtype)


class MoEAuxLossOp(Op):
    """The balance term of an MoE op's regime (``aux``); like the two nodes
    below it is built from the op's inputs, so the op's names find them."""

    def __init__(self, moe_op):
        super().__init__(*moe_op.inputs, name=f"{moe_op.name}_aux")
        self.moe = moe_op

    def _compute(self, input_vals, ctx):
        return self.moe.aux(input_vals, ctx)


class MoEZLossOp(Op):
    """Router z-loss ``mean_t logsumexp(logits_t)^2`` of a dropless MoE op,
    in f32 (ST-MoE; OLMoE trains with 0.001 of it a layer)."""

    def __init__(self, moe_op):
        assert isinstance(moe_op, _DroplessOp), (
            "the z-loss reads the dropless routing")
        super().__init__(*moe_op.inputs, name=f"{moe_op.name}_zloss")
        self.moe = moe_op

    def _compute(self, input_vals, ctx):
        from ..ops.moe import router_z_loss
        return router_z_loss(self.moe.routing(input_vals, ctx)[0])


class MoEChosenOp(Op):
    """``[T, k]`` int32: the experts each token of a dropless MoE op takes,
    largest weight first (for checks against a reference's routing)."""

    def __init__(self, moe_op):
        assert isinstance(moe_op, _DroplessOp)
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


#: the keywords of ``MoELayer`` that belong to ONE regime: (keyword, the
#: regime, the values that ask for it).  Every other keyword is the layer's
#: under either regime.
_ONE_REGIME_ALONE = (
    ("held", "dropless", lambda v: v is not None),
    ("router", "dropless", lambda v: v is not None),
    ("router_groups", "dropless", lambda v: v is not None),
    ("router_score", "dropless", lambda v: v == "sigmoid"),
    ("expert_act", "dropless", lambda v: v == "relu2"),
    ("expert_act", "capacity", lambda v: v == "gelu"),    # with biases
    # a gate without ``route``: hash, ktop1, sam (``num_groups``), balance,
    # or a caller-built one; a caller-built gate with a selection bias
    ("gate", "capacity", lambda v: v != "top" and not hasattr(v, "route")),
    ("gate", "dropless", lambda v: getattr(v, "bias", None) is not None),
)


class MoELayer(BaseLayer):
    """Expert-parallel FFN block (drop-in for TransformerFFN).

    ``capacity_factor=None`` is the dropless regime (ops/moe.py
    ``dropless_moe``): every (token, choice) pair is computed, by grouped
    products over the pairs sorted by expert; it needs the ``top`` gate and
    experts without biases: ``expert_act="swiglu"`` (``silu(x W1) * (x W3)``
    then ``W2``, three grouped products a pass) or ``"relu2"`` (``relu(x
    W1)^2`` then ``W2``, two; no ``w3``).  A capacity factor is the
    reference's regime: any gate, ``"gelu"`` experts (with biases) or
    ``"swiglu"``.  ``_ONE_REGIME_ALONE`` says which keyword is which
    regime's alone; one of the other regime is refused by name.
    ``renorm_topk`` is the gate's ``renorm``; ``router_score="sigmoid"``,
    ``router_scale`` and ``router_bias_rate`` are the gate's ``score``,
    ``scale`` and ``bias_rate``, ``router_groups`` its ``groups``
    (``TopKGate``), and ``router_bias()`` fetches the bias as ``load()``
    fetches the load.  ``track_load`` adds a ``[2, E]`` state variable of
    per-expert pair counts (routed, kept) that ``load()`` fetches.

    ``held=(first, count)`` is one device's share of an expert-parallel
    layer without the other devices: the router keeps its ``num_experts``
    outputs and ``k`` a token, the layer holds the weights of the experts
    ``first .. first + count - 1`` alone and computes only the pairs routed to
    them (dropless path); what the absent experts would add is left out and
    nothing stands in for it.  One pass lays out rows for twice the mean
    share (``ops/moe.py held_rows``); pairs a batch routes here over that
    bound are computed by further passes (``dropless_moe``), none is dropped.
    The load is ``[4, count]`` then (``ROUTED`` .. ``LATER``).
    ``router=`` is a router that is a layer of its own (``StateRouter``):
    the op takes its logits where it took the gate's weight, selects on
    ``softmax + bias`` and hands the router's state on (``__call__(x,
    state=)``, ``self.state``).  Its last ``router.skip`` outputs are no
    experts: a pair that chooses one goes to no expert, gives zero, and is
    counted apart in a fifth row of the load (``SKIPPED``); the layer is
    laid out as a held one (all its experts where ``held`` is None).
    ``shared_width`` adds a shared expert of that width and of the experts'
    kind (swiglu or relu2), computed for every token, and ``shared_gate``
    says whether the sigmoid of a one-column gate scales it
    (``shared_expert_op``)."""

    def __init__(self, hidden_size, intermediate_size, num_experts, k=2,
                 capacity_factor=1.25, gate="top", ep_axis=None,
                 num_groups=None, expert_act="gelu", renorm_topk=True,
                 track_load=False, held=None, shared_width=None,
                 shared_gate=True, router_score="softmax", router_scale=None,
                 router_bias_rate=None, router_groups=None, router=None,
                 name=None):
        name = fresh_name(name or "moe")
        assert expert_act in ("gelu", "swiglu", "relu2"), expert_act
        # the regime is chosen HERE; a keyword of the other is refused by name
        self.capacity_factor = capacity_factor
        regime = "dropless" if capacity_factor is None else "capacity"
        given = locals()
        for keyword, its, belongs in _ONE_REGIME_ALONE:
            if its != regime and belongs(given[keyword]):
                raise ValueError(
                    f"{keyword}={given[keyword]!r} belongs to the {its} "
                    f"regime (capacity_factor"
                    f"{'=' if its == 'dropless' else ' is not '}None); "
                    f"this layer is {regime}")
        self.router, self.skip = router, getattr(router, "skip", 0)
        #: the router's state node of the last call (``router=``)
        self.state = None
        if router is not None:
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
        self.expert_act = expert_act

        def var(n, *shape, how=init.xavier_uniform):
            return VariableOp(f"{name}_{n}", (n_held,) + shape, how())
        biased = expert_act == "gelu"
        self.w1 = var("w1", hidden_size, intermediate_size)
        self.b1 = var("b1", intermediate_size,
                      how=init.zeros) if biased else None
        self.w2 = var("w2", intermediate_size, hidden_size)
        self.b2 = var("b2", hidden_size, how=init.zeros) if biased else None
        # swiglu experts (Mixtral-style, reference-beyond): gated FFN
        # silu(x@w1) * (x@w3) @ w2, no biases
        self.w3 = var("w3", hidden_size, intermediate_size) \
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
        self.num_experts, self.k, self.ep_axis = num_experts, k, ep_axis
        over_axis = regime == "dropless" and ep_axis is not None
        self.load_var = VariableOp(
            f"{name}_load",
            (4 if over_axis else 2, num_experts) if held is None
            else (5 if self.skip else 4, n_held), init.zeros(),
            trainable=False) if track_load else None
        if ep_axis is not None:
            from ..parallel.mesh import DistState
            for v in (self.w1, self.b1, self.w2, self.b2, self.w3):
                if v is not None:
                    v.dist_state = DistState({0: ep_axis})
        self.last_op = None
        #: the op of this layer's regime on ``x``
        if regime == "dropless":
            self._op = lambda x, ids, scores: _DroplessOp(
                x, self.gate, self.w1, self.w2, self.w3, self.k,
                self.num_experts, held=self.held, scores=scores,
                state=self.state, load_var=self.load_var,
                ep_axis=self.ep_axis)
        else:
            self._op = lambda x, ids, scores: _CapacityOp(
                x, self.gate, self.w1, self.b1, self.w2, self.b2, self.w3,
                self.k, self.num_experts, self.capacity_factor,
                ep_axis=self.ep_axis, ids=ids, load_var=self.load_var)

    def __call__(self, x, ids=None, state=None):
        """``state``: the router state the layer above handed on
        (``router=``; this layer's is ``self.state`` afterwards)."""
        scores = None
        if self.router is not None:
            scores, self.state = self.router(x, state)
        elif self.gate.wg is None and ids is None:
            raise ValueError(
                "hash-gated MoELayer requires token ids: moe(x, ids=...)")
        # what of the block lies outside its five regions (the reshapes,
        # the load's counts, the loop of further passes, the sum with the
        # shared expert) has a name of its own: the regions' names are what
        # the moe_block metrics read, and hold what they held
        with scope("hetu_moe_other"):
            self.last_op = self._op(x, ids, scores)
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


def record_moe_load(layer, load, bias=None, exchange=None):
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

    From a layer whose experts are spread over a mesh axis
    (``MoELayer(ep_axis=)``, ``[4, num_experts]``) every count is the
    host's, over all experts and all devices' tokens: ``elsewhere`` stays 0
    and ``over_bound`` counts the pairs a device's further passes computed.
    ``exchange`` (the bytes one device received in this step's all-gathers
    and reduce-scatters: ``ops/moe.py exchange_bytes_a_step`` of the op's
    ``exchange``, which is sized when the step is traced, and of the step's
    forward passes, which whoever set the recomputation knows) is added to
    ``hetu_moe_exchange_bytes_total{layer, direction}``.

    The registry counts nothing while telemetry is disabled."""
    from .. import telemetry
    reg = telemetry.get_registry()
    for direction, nbytes in (exchange or {}).items():
        reg.counter(
            "hetu_moe_exchange_bytes_total",
            "Bytes one device received in the experts' exchange, by the "
            "traced step's shapes: its all-gathers (of tokens, and of the "
            "sums' cotangent) and its reduce-scatters (of partial sums, and "
            "of the tokens' cotangent), forward, recomputed and backward",
            labels=("layer", "direction")).labels(
                layer=layer, direction=direction).inc(nbytes)

    def metric(kind, name, text):
        return getattr(reg, kind)(name, text, labels=("layer",)).labels(
            layer=layer)
    if bias is not None:
        metric("gauge", "hetu_moe_router_bias_max_abs",
               "Largest |selection bias| of the router, last step").set(
                   float(np.abs(np.asarray(bias, np.float64)).max()))
    load = np.asarray(load, np.float64)
    routed, kept = load[ROUTED], load[COMPUTED]
    total = routed.sum()
    if len(load) > ELSEWHERE and total + load[ELSEWHERE, 0] > 0:
        metric("counter", "hetu_moe_pairs_elsewhere_total",
               "Routed pairs whose expert another device holds").inc(
                   load[ELSEWHERE, 0])
        metric("counter", "hetu_moe_pairs_over_bound_total",
               "Pairs on held experts computed by a pass after the first"
               ).inc(load[LATER].sum() if len(load) > LATER else 0)
    if len(load) > SKIPPED and (total + load[ELSEWHERE, 0]
                                + load[SKIPPED, 0] > 0):
        metric("counter", "hetu_moe_pairs_skipped_total",
               "Routed pairs whose choice is no expert (computed by none)"
               ).inc(load[SKIPPED, 0])
        metric("gauge", "hetu_moe_router_state_rms",
               "RMS of the router state a layer handed on, last step").set(
                   load[SKIPPED, 1])
    if total <= 0:          # the state's initial zeros: no step has run
        return
    metric("counter", "hetu_moe_pairs_routed_total",
           "(token, choice) pairs the router sent to an expert").inc(total)
    metric("counter", "hetu_moe_pairs_dropped_total",
           "Routed pairs no expert computed (capacity overflow)").inc(
               total - kept.sum())
    metric("gauge", "hetu_moe_expert_load_max_over_mean",
           "Pairs at the fullest expert over the mean, last step").set(
               routed.max() * routed.size / total)
