"""The short causal convolution in front of a recurrent mixer (Gated
DeltaNet, Mamba-2): depthwise over the sequence, then SiLU.

``x [B, S, C]``, ``w [K, C]``: ``y_t = silu(sum_j w_j x_(t - K + 1 + j) + b)``
with zeros before the first position (``b [C]`` where the model has a bias).
``x``, ``w`` and ``b`` are taken to f32, the ``K`` products summed in f32, the
bias added and SiLU taken in f32, and the result cast once to ``x``'s type;
``dw`` and ``db`` are summed in f32 over all positions and cast once.
``window = (lo, hi)`` convolves the channels ``[lo, hi)`` of a wider ``x``
(the Mamba-2 layers' ``xBC`` inside their projection's output).

What runs where.  ``causal_conv`` is what the layers' ``hetu_gdn_conv`` and
``hetu_ssm_conv`` nodes call (``ConvOp``).  On a TPU it runs as two Pallas
kernels, ``hetu_conv_fwd`` and ``hetu_conv_bwd`` (``ops/pallas/causal_conv
.py``, a ``jax.custom_vjp``: one read and one write of ``[S, C]`` a pass, the
taps' shifts in VMEM, a window read in place, nothing kept for the backward
pass but the operands), where it can read that they apply: the window's
offset and width multiples of 128 lanes, up to 8 taps, ``x`` bf16 or f32, the
sequence a multiple of 16; any batch.  Each call counts its choice at trace
time in ``hetu_kernel_choice_total{kernel="causal_conv", impl, reason}``:
``pallas``, or ``jnp`` with ``channels_not_128_aligned``, ``taps>8``,
``dtype:<name>`` or ``seq_not_16_aligned``.  What a mesh (which the node
sees: ``ConvOp``, an ``ops/base.py KernelOp``) and a platform without Mosaic
mean is ``dispatch.take``'s rule; ``causal_conv_jnp`` then runs, bit for bit
what this function was before it had kernels.  The kernels themselves run
anywhere when called directly (interpret mode on the CPU):
``tests/test_causal_conv_kernel.py``.
"""

from __future__ import annotations

from .base import KernelOp


def causal_conv(x, w, b=None, window=None):
    """The Pallas kernel pair where ``dispatch.take`` and its rule allow,
    else the ``jax.numpy`` form."""
    from .pallas import causal_conv as kernels, dispatch
    if dispatch.take("causal_conv", None,
                     kernels.unsupported(x, w, b, window)):
        return kernels.conv(x, w, b, window)
    return causal_conv_jnp(x, w, b, window)


def causal_conv_jnp(x, w, b=None, window=None):
    """``K`` shifted products of a padded copy: no im2col, no transposition
    of the channels.  What the kernels are held to, and what runs wherever
    they do not."""
    import jax
    import jax.numpy as jnp
    if window is not None:
        x = x[..., window[0]:window[1]]
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + S].astype(jnp.float32) * w[j].astype(jnp.float32)
            for j in range(K))
    if b is not None:
        y = y + b.astype(jnp.float32)
    return jax.nn.silu(y).astype(x.dtype)


def _conv(*operands, window, rule):
    return (rule or causal_conv)(*operands, window=window)


class ConvOp(KernelOp):
    """The convolution's node, ``ConvOp(scope, x, w[, b], window=)``: under a
    mesh it calls the ``jax.numpy`` form itself (``ops/base.py KernelOp``)."""

    def __init__(self, scope, *inputs, window=None):
        super().__init__(_conv, scope, *inputs, kernel="causal_conv",
                         form=lambda: causal_conv_jnp,
                         window=window)
