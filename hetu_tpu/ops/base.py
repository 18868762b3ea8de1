"""Op definition helpers.

The reference implements each op as a Python class + a hand-written CUDA
kernel (one file per op under /root/reference/python/hetu/gpu_ops/ and
/root/reference/src/ops/).  On TPU the kernel body is a jnp/lax composition
that XLA fuses, so an op definition reduces to a pure function; this module
turns such functions into graph-node constructors.  Ops that need RNG,
train/eval mode, or state updates subclass Op directly in their modules.
"""

from __future__ import annotations

from ..graph.node import Op, scope as _scope


class SimpleOp(Op):
    """Graph node wrapping a pure jnp function of its inputs + attrs."""

    __slots__ = ("impl", "op_kind")

    def __init__(self, impl, op_kind, *inputs, name=None, **attrs):
        super().__init__(*inputs, name=name or f"{op_kind}_{_peek_id()}",
                         **attrs)
        self.impl = impl
        self.op_kind = op_kind

    def _compute(self, input_vals, ctx):
        return self.impl(*input_vals, **self.attrs)


class ScopedOp(Op):
    """``fn(*inputs, **attrs)`` as one node of the block ``scope``, whatever
    `ht.scope` is open around it: a region of the step that the device
    trace's readers find by its scope in the compiled program's ``op_name``
    (forward and backward; `evaluate` opens the scope)."""

    def __init__(self, fn, scope, *inputs, **attrs):
        super().__init__(*inputs, **attrs)
        self.name = f"{scope}_{self.id}"
        self.fn, self.scope = fn, _scope(scope).name

    def _compute(self, input_vals, ctx):
        return self.fn(*input_vals, **self.attrs)


class KernelOp(ScopedOp):
    """A ``ScopedOp`` whose ``fn`` chooses between a Pallas kernel and its
    ``jax.numpy`` form but cannot see a mesh.  ``kernel`` is the label the
    choice is counted under, ``form()`` the ``jax.numpy`` form, looked up
    when the node computes.  Under a mesh the node asks ``dispatch.take``
    itself and hands ``fn`` that form as ``rule=``; off a mesh ``rule`` is
    None and ``fn`` asks."""

    def __init__(self, fn, scope, *inputs, kernel, form, **attrs):
        super().__init__(fn, scope, *inputs, **attrs)
        self.kernel, self.form = kernel, form

    def _compute(self, input_vals, ctx):
        from .pallas import dispatch
        if ctx.mesh is None:
            return self.fn(*input_vals, rule=None, **self.attrs)
        dispatch.take(self.kernel, ctx.mesh)   # its record: never the kernel
        return self.fn(*input_vals, rule=self.form(), **self.attrs)


def _peek_id():
    from ..graph import node as _n
    return _n._node_counter[0] + 1


def simple_op(impl, op_kind, node_cls=SimpleOp):
    """Returns a graph-node constructor for a pure function.

    ``impl(*input_arrays, **attrs)`` must be jax-traceable; non-Op positional
    arguments are forbidden (constants go through attrs).  ``node_cls`` is
    a ``SimpleOp`` subclass for the rare op whose ``_compute`` also reads
    the trace context.
    """

    def ctor(*inputs, name=None, **attrs):
        for i in inputs:
            if not isinstance(i, Op):
                raise TypeError(
                    f"{op_kind}: expected graph nodes as inputs, got "
                    f"{type(i).__name__}; pass constants as keyword attrs")
        return node_cls(impl, op_kind, *inputs, name=name, **attrs)

    ctor.__name__ = op_kind
    return ctor
