"""The gate and the RMS norm between a recurrent mixer's scan and its output
product (Gated DeltaNet, Mamba-2), with that product: the layers'
``hetu_gdn_out`` and ``hetu_ssm_out`` nodes.

The mathematics is the layer's, and so is its ``jax.numpy`` form:
``layers/gated_delta_net.py _out`` norms each value head and then gates it,
``layers/mamba2.py _out`` gates and then norms each group of channels.
``OutOp`` is the one node of both.  It is given ``o [B, S, C]``, the
projection's whole output with the ``Window`` in which the gate ``z`` lies,
the scale and the output weight, and the layer's form ``fn(o, z, scale,
w_out, **attrs)``.

What runs where.  On a TPU the gate and the norm run as two Pallas kernels,
``hetu_gated_norm_fwd`` and ``hetu_gated_norm_bwd`` (``ops/pallas/gated_norm
.py``, a ``jax.custom_vjp``: one read of ``o`` and ``z`` and one write a pass,
``z`` read in place, the statistics in f32 on the block in VMEM, nothing kept
for the backward pass but the operands), and the product stays XLA's on their
``[B, S, C]``, where the rule takes the operands: the group's width a multiple
of 128 lanes that divides the channels, ``o`` and ``z`` both bf16 or both f32,
the sequence a multiple of 16, a group of 16 rows within a block.  Each call
counts its choice at trace time in ``hetu_kernel_choice_total{kernel=
"gated_norm", impl, reason}``: ``pallas``, or ``jnp`` with
``width_not_128_aligned``, ``scale_not_a_group_or_all``, ``dtype:<name>``,
``dtype:mixed``, ``seq_not_16_aligned`` or ``group_wider_than_a_block``; what a
mesh and a platform without Mosaic mean is ``dispatch.take``'s rule, and the
layer's form then runs on the slice of ``z``.  The kernels themselves run
anywhere when called directly (interpret mode on the CPU):
``tests/test_gated_norm.py``.
"""

from __future__ import annotations

from .base import ScopedOp
from .pallas import dispatch, gated_norm as kernels
from .pallas.gated_norm import Window      # noqa: F401


class OutOp(ScopedOp):
    """``OutOp(fn, scope, o, wide, scale, w_out, window=, width=,
    gate_first=, eps=, **attrs)``: ``window`` says where ``z`` lies in
    ``wide``, ``width`` is the norm's group, ``gate_first`` the order; ``eps``
    and ``attrs`` are also the layer's form's."""

    def __init__(self, fn, scope, *inputs, window, width, gate_first,
                 **attrs):
        super().__init__(fn, scope, *inputs, **attrs)
        self.how = dict(window=window, width=width, gate_first=gate_first)

    def _compute(self, input_vals, ctx):
        o, wide, scale, w_out = input_vals
        if dispatch.take("gated_norm", ctx.mesh, kernels.unsupported(
                o, wide, scale, width=self.how["width"])):
            return kernels.gated_norm(o, wide, scale, eps=self.attrs["eps"],
                                      **self.how) @ w_out
        z = kernels.take(wide, self.how["window"], o.shape[2])
        return self.fn(o, z, scale, w_out, **self.attrs)
