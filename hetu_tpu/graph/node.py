"""Graph node model: the define-then-run op DAG.

TPU-native re-design of the reference's op/node layer
(/root/reference/python/hetu/gpu_ops/Node.py:20 `class Op`).  The reference
dispatches each node through ctypes into hand-written CUDA kernels; here every
op's ``compute`` is a pure jax-traceable function, and the whole DAG is traced
once into a single XLA program by the executor (see graph/executor.py).  That
means:

  * no per-op streams/events — XLA owns scheduling,
  * no hand-written shape rules — shapes come from ``jax.eval_shape``,
  * no hand-written per-op gradients — autodiff is trace-time ``jax.vjp``
    (graph/autodiff.py), with op-level custom VJPs only for Pallas kernels.

The graph API itself (placeholders, Variables, functional ``*_op``
constructors, ``Executor``) is kept compatible in spirit with the reference so
users of Hetu find the same surface.
"""

from __future__ import annotations

import re

import numpy as np

_node_counter = [0]


def _next_id() -> int:
    _node_counter[0] += 1
    return _node_counter[0]


import threading as _threading

_stage_tls = _threading.local()


def _stage_stack():
    # thread-local: launcher.launch_local builds graphs on worker threads
    # concurrently; a shared stack would cross-assign their stages
    stack = getattr(_stage_tls, "stack", None)
    if stack is None:
        stack = _stage_tls.stack = [None]
    return stack


class stage:
    """Pipeline-stage scope: ops created inside get ``raw_ctx = idx``.

    Mirrors the reference's ``with ht.context(ctx)`` device-group scoping
    (context.py:830) that drives pipeline stage inference
    (executor.py:1430); here the annotation is consumed by
    parallel/graph_pipeline.py.  Nests: the innermost scope wins.
    """

    def __init__(self, idx):
        self.idx = int(idx)

    def __enter__(self):
        _stage_stack().append(self.idx)
        return self

    def __exit__(self, *exc):
        _stage_stack().pop()
        return False


_remat_tls = _threading.local()
_remat_counter = [0]


def _remat_stack():
    stack = getattr(_remat_tls, "stack", None)
    if stack is None:
        stack = _remat_tls.stack = [None]
    return stack


class remat:
    """Rematerialization scope: ops created inside form one
    `jax.checkpoint` group — their activations are NOT saved for the
    backward pass; the group recomputes during the vjp instead, but for
    the kernel residuals named below.

    The graph-API face of the reference's memory planner (SURVEY §2.2
    P10: memory_pool.py / swap — on TPU the trade is FLOPs-for-HBM via
    remat, not host swap).  Typical use wraps each transformer layer::

        with ht.remat():
            x = layer(x, ...)

    What a group keeps (PR 55): its inputs and, for every flash attention
    call it holds (the window kernels too), the kernel's context, ``B x S x
    heads x d`` of the compute type, and log-sum-exp, ``B x heads x S`` f32
    (34.1 MB a call at 1 x 8,192 x 2,048 bf16): the kernel is the dearest
    thing of a layer to make again, so the backward pass reads what the
    forward pass wrote and runs no second forward kernel.  The names are one
    table beside the kernels' dispatch (``ops/pallas/dispatch.py KEPT``,
    given INSIDE the kernel's forward rule); a group with no such call keeps
    its inputs alone.  ``hetu_remat_kept_total{kernel}`` and
    ``hetu_remat_kept_bytes`` say how often and how much.

    Stateful ops (batchnorm update, assign) must stay outside — the
    recompute would replay their side effects; `evaluate` raises.
    Nested scopes merge into the outermost group (one coarse checkpoint).
    """

    def __enter__(self):
        _remat_counter[0] += 1
        self.idx = _remat_counter[0]
        _remat_stack().append(self.idx)
        return self

    def __exit__(self, *exc):
        _remat_stack().pop()
        return False


def current_stage():
    return _stage_stack()[-1]


_scope_tls = _threading.local()
_scope_names = {}
_SCOPE_NAME = re.compile(r"hetu_[a-z0-9_]+")


def _scope_stack():
    stack = getattr(_scope_tls, "stack", None)
    if stack is None:
        stack = _scope_tls.stack = [None]
    return stack


def _known_scope(name):
    """``name``, recorded among the names `scopes` reads back.  A reader of
    the device trace finds a block by its name anywhere in an instruction's
    ``op_name``, so no name may lie inside another."""
    if name not in _scope_names:
        if not _SCOPE_NAME.fullmatch(name):
            raise ValueError(f"a scope is named hetu_[a-z0-9_]+, not {name!r}")
        clash = [n for n in _scope_names if n in name or name in n]
        if clash:
            raise ValueError(f"scope {name!r} and {clash[0]!r}: one name "
                             "lies inside the other")
        _scope_names[name] = None
    return name


class scope:
    """Block scope: ops created inside take ``name`` as their ``scope``, and
    `evaluate` (graph/trace.py) runs each of them under
    ``jax.named_scope(name)``.  XLA keeps the name in every instruction's
    ``op_name``, forward, backward (``transpose(jvp(name))``) and recomputed,
    which is how the device trace's readers give a block its device time
    (docs/PROFILING.md).  Metadata only: the compiled step is the same.

    Nests: the INNERMOST scope wins, one name an op.  Wrap a layer's
    ``__call__`` body::

        with ht.scope("hetu_attn"):
            q = self.q_proj(x)
    """

    def __init__(self, name):
        self.name = _known_scope(name)

    def __enter__(self):
        _scope_stack().append(self.name)
        return self

    def __exit__(self, *exc):
        _scope_stack().pop()
        return False


def scopes():
    """Every scope name given so far, in the order given: to `scope`, to a
    ``ScopedOp`` or to `named_scope`."""
    return tuple(_scope_names)


def named_scope(name):
    """``jax.named_scope(name)`` for a region INSIDE one op's ``_compute``
    (trace time), with the name recorded as `scope` records it.  It stands
    after the op's own scope in ``op_name``, and the readers take the last."""
    import jax
    return jax.named_scope(_known_scope(name))


_naming_tls = _threading.local()


def _naming_stack():
    # index 0 is the process-global namespace (scope-less construction
    # keeps its historical behavior); each `with name_scope():` pushes a
    # fresh namespace so names are deterministic per instance.
    stack = getattr(_naming_tls, "stack", None)
    if stack is None:
        stack = _naming_tls.stack = [{"vars": {}, "layers": {}}]
    return stack


class name_scope:
    """Fresh, deterministic naming namespace for variables and layers.

    Construction inside ``with name_scope():`` always produces the same
    variable names, independent of what else was built in the process
    before — so checkpoints keyed by name are stable across construction
    order.  Model constructors open one per instance.  Genuine collisions
    (two same-named variables reaching one Executor) raise there instead
    of being silently renamed.
    """

    def __enter__(self):
        _naming_stack().append({"vars": {}, "layers": {}})
        return self

    def __exit__(self, *exc):
        _naming_stack().pop()
        return False


def scoped_init(init):
    """Decorator: run a model's ``__init__`` inside its own `name_scope`,
    making its parameter names independent of construction order."""
    import functools

    @functools.wraps(init)
    def wrapper(self, *args, **kwargs):
        with name_scope():
            return init(self, *args, **kwargs)

    return wrapper


def _unique_var_name(name: str) -> str:
    table = _naming_stack()[-1]["vars"]
    count = table.get(name)
    if count is None:
        table[name] = 1
        return name
    table[name] = count + 1
    name = f"{name}_{count}"
    table[name] = 1
    return name


class Op:
    """A node in the dataflow graph.

    Subclasses implement ``_compute(input_vals, ctx)`` as a pure jax function
    of the input arrays.  ``ctx`` is a TraceContext (graph/trace.py) giving
    access to per-step RNG, the training flag, and state-update recording.
    """

    __slots__ = (
        "id", "name", "inputs", "attrs", "dist_state", "raw_ctx",
        "remat_scope", "scope", "_shape_cache",
    )

    def __init__(self, *inputs, name=None, **attrs):
        self.id = _next_id()
        self.inputs = list(inputs)
        self.name = name or f"{type(self).__name__}_{self.id}"
        self.attrs = attrs
        # Sharding annotation (parallel/mesh.py DistState), set by dispatch()
        # or by a Strategy; mirrors reference NodeStatus (context.py:248).
        self.dist_state = None
        # Device-group annotation for pipeline-stage placement; mirrors
        # reference raw_ctx (Node.py / context.py DeviceGroup).  Picked up
        # from an enclosing `with stage(i):` scope.
        self.raw_ctx = _stage_stack()[-1]
        # `with remat():` group id (jax.checkpoint at trace time), or
        # None.  The OUTERMOST active scope wins: nested scopes merge
        # into one coarser checkpoint group (wrapping a block whose
        # sublayers also remat composes instead of erroring).
        _rs = _remat_stack()
        self.remat_scope = next((s for s in _rs[1:] if s is not None),
                                None) if len(_rs) > 1 else None
        # `with scope(name):` block name (jax.named_scope at trace time), or
        # None.  The INNERMOST active scope wins.
        self.scope = _scope_stack()[-1]
        self._shape_cache = None

    # -- graph protocol ----------------------------------------------------
    def _compute(self, input_vals, ctx):
        raise NotImplementedError(type(self).__name__)

    @property
    def needs_rng(self) -> bool:
        return False

    @property
    def is_stateful(self) -> bool:
        """True for ops that update variables (optimizer, batchnorm, assign)."""
        return False

    # -- sugar -------------------------------------------------------------
    def __add__(self, other):
        from ..ops.math import add_op, addbyconst_op
        if isinstance(other, Op):
            return add_op(self, other)
        return addbyconst_op(self, other)

    __radd__ = __add__

    def __mul__(self, other):
        from ..ops.math import mul_op, mulbyconst_op
        if isinstance(other, Op):
            return mul_op(self, other)
        return mulbyconst_op(self, other)

    __rmul__ = __mul__

    def __sub__(self, other):
        from ..ops.math import sub_op, addbyconst_op, mulbyconst_op
        if isinstance(other, Op):
            return sub_op(self, other)
        return addbyconst_op(self, -other)

    def __rsub__(self, other):
        from ..ops.math import mulbyconst_op, addbyconst_op
        return addbyconst_op(mulbyconst_op(self, -1.0), other)

    def __neg__(self):
        from ..ops.math import mulbyconst_op
        return mulbyconst_op(self, -1.0)

    def __truediv__(self, other):
        from ..ops.math import div_op, mulbyconst_op
        if isinstance(other, Op):
            return div_op(self, other)
        return mulbyconst_op(self, 1.0 / other)

    def __matmul__(self, other):
        from ..ops.linalg import matmul_op
        return matmul_op(self, other)

    def __repr__(self):
        return f"<{type(self).__name__} {self.name} #{self.id}>"

    def __hash__(self):
        return self.id

    def __eq__(self, other):
        return self is other


class PlaceholderOp(Op):
    """Fed input (reference: gpu_ops/Variable.py placeholder path)."""

    __slots__ = ("shape", "dtype")

    def __init__(self, name, shape=None, dtype=np.float32):
        super().__init__(name=name)
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = np.dtype(dtype)

    def _compute(self, input_vals, ctx):  # value comes from feed_dict
        raise RuntimeError(f"placeholder {self.name} was not fed")


class VariableOp(Op):
    """Trainable / persistent state.

    Reference: gpu_ops/Variable.py Variable (initializer held on node,
    materialized by executor at construction).  Values live in the executor's
    functional state dict, not on the node.
    """

    # monitor: optional callable(float) -> warning-message-or-None; the
    # executor polls monitored variables host-side every monitor_interval
    # steps (in-graph counters, e.g. the BERT MLM overflow total)
    __slots__ = ("shape", "dtype", "initializer", "trainable", "monitor")

    # Executor state is keyed by variable name, so names must be unique
    # within a namespace (`name_scope`); the Executor raises on genuine
    # cross-scope collisions rather than silently renaming.

    def __init__(self, name, shape, initializer, trainable=True,
                 dtype=np.float32):
        name = _unique_var_name(name)
        super().__init__(name=name)
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        self.initializer = initializer
        self.trainable = bool(trainable)

    def _compute(self, input_vals, ctx):
        raise RuntimeError(
            f"variable {self.name} must be bound by the executor")


def find_topo_sort(node_list):
    """Post-order DFS topo sort (reference: executor.py:1515)."""
    visited = set()
    order = []

    def dfs(node):
        stack = [(node, False)]
        while stack:
            n, expanded = stack.pop()
            if expanded:
                order.append(n)
                continue
            if n.id in visited:
                continue
            visited.add(n.id)
            stack.append((n, True))
            for inp in reversed(n.inputs):
                if inp.id not in visited:
                    stack.append((inp, False))

    for node in node_list:
        dfs(node)
    return order


def graph_variables(node_list, trainable_only=False):
    """All VariableOps reachable from node_list, in topo order."""
    out = []
    for n in find_topo_sort(node_list):
        if isinstance(n, VariableOp) and (n.trainable or not trainable_only):
            out.append(n)
    return out


def graph_placeholders(node_list):
    return [n for n in find_topo_sort(node_list) if isinstance(n, PlaceholderOp)]
