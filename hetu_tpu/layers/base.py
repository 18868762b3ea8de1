"""Layer library base (reference: /root/reference/python/hetu/layers/base.py).

Layers are callables that build op subgraphs; parameters are VariableOps
created at layer construction.  Unlike flax Modules there is no separate
param pytree — the graph owns the Variables, matching the reference design.
"""

from __future__ import annotations

from ..graph.node import _naming_stack


def fresh_name(prefix):
    # counters live in the innermost `name_scope` (graph/node.py), so a
    # model instance's default layer names don't depend on process history
    counters = _naming_stack()[-1]["layers"]
    c = counters.get(prefix, 0)
    counters[prefix] = c + 1
    return f"{prefix}{c}" if c else prefix


def project(x, w):
    """The function of a projection's node (``ScopedOp(project, scope, x,
    w)``): one node a projection, so that its backward pass is one product
    for the weight whatever reads the parts."""
    return x @ w


class BaseLayer:
    def __call__(self, *args, **kwargs):
        raise NotImplementedError


class Sequence(BaseLayer):
    """Sequential container (reference layers/sequence.py)."""

    def __init__(self, *layers):
        self.layers = list(layers)

    def __call__(self, x):
        for layer in self.layers:
            x = layer(x)
        return x


class Identity(BaseLayer):
    def __call__(self, x):
        return x
