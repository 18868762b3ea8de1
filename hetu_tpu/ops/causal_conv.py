"""The short causal convolution in front of a recurrent mixer (Gated
DeltaNet, Mamba-2): depthwise over the sequence, then SiLU.

``x [B, S, C]``, ``w [K, C]``: ``y_t = silu(sum_j w_j x_(t - K + 1 + j) + b)``
with zeros before the first position (``b [C]`` where the model has a bias).
``x``, ``w`` and ``b`` are taken to f32, the ``K`` products summed in f32, the
bias added and SiLU taken in f32, and the result cast once to ``x``'s type;
``dw`` and ``db`` are summed in f32 over all positions and cast once.
``window = (lo, hi)`` convolves the channels ``[lo, hi)`` of a wider ``x``
(the Mamba-2 layers' ``xBC`` inside their projection's output).  ``act=None``
leaves the activation out, ``y_t = sum_j w_j x_(t - K + 1 + j) + b`` (the
depthwise taps of compressed convolutional attention,
``layers/compressed_attention.py``); ``"silu"`` is the default and the only
other value.

What runs where.  ``causal_conv`` is what the layers' ``hetu_gdn_conv`` and
``hetu_ssm_conv`` nodes call (``ConvOp``).  On a TPU it runs as two Pallas
kernels, ``hetu_conv_fwd`` and ``hetu_conv_bwd`` (``ops/pallas/causal_conv
.py``, a ``jax.custom_vjp``: one read and one write of ``[S, C]`` a pass, the
taps' shifts in VMEM, a window read in place, nothing kept for the backward
pass but the operands), where it can read that they apply: the window's
offset and width multiples of 128 lanes, up to 8 taps, ``x`` bf16 or f32, the
sequence a multiple of 16; any batch.  Each call counts its choice at trace
time in ``hetu_kernel_choice_total{kernel="causal_conv", impl, reason}``:
``pallas``, or ``jnp`` with ``act:none`` (the kernels apply SiLU),
``channels_not_128_aligned``, ``taps>8``, ``dtype:<name>`` or
``seq_not_16_aligned``.  What a mesh (which the node
sees: ``ConvOp``, an ``ops/base.py KernelOp``) and a platform without Mosaic
mean is ``dispatch.take``'s rule; ``causal_conv_jnp`` then runs, bit for bit
what this function was before it had kernels.  The kernels themselves run
anywhere when called directly (interpret mode on the CPU):
``tests/test_causal_conv_kernel.py``.
"""

from __future__ import annotations

from .base import KernelOp


def causal_conv(x, w, b=None, window=None, act="silu"):
    """The Pallas kernel pair where ``dispatch.take`` and its rule allow,
    else the ``jax.numpy`` form."""
    from .pallas import causal_conv as kernels, dispatch
    if dispatch.take("causal_conv", None,
                     kernels.unsupported(x, w, b, window, act)):
        return kernels.conv(x, w, b, window)
    return causal_conv_jnp(x, w, b, window, act)


def causal_conv_jnp(x, w, b=None, window=None, act="silu"):
    """``K`` shifted products of a padded copy: no im2col, no transposition
    of the channels.  What the kernels are held to, and what runs wherever
    they do not."""
    import jax
    import jax.numpy as jnp
    assert act in ("silu", None), act
    if window is not None:
        x = x[..., window[0]:window[1]]
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + S].astype(jnp.float32) * w[j].astype(jnp.float32)
            for j in range(K))
    if b is not None:
        y = y + b.astype(jnp.float32)
    return (y if act is None else jax.nn.silu(y)).astype(x.dtype)


def _conv(*operands, window, rule, **act):
    # ``act`` is there only where it is not SiLU, on the node and in the call
    return (rule or causal_conv)(*operands, window=window, **act)


class ConvOp(KernelOp):
    """The convolution's node, ``ConvOp(scope, x, w[, b], window=, act=)``:
    under a mesh it calls the ``jax.numpy`` form itself (``ops/base.py
    KernelOp``).  A node with SiLU has the attributes it had before ``act``
    existed."""

    def __init__(self, scope, *inputs, window=None, act="silu"):
        super().__init__(_conv, scope, *inputs, kernel="causal_conv",
                         form=lambda: causal_conv_jnp, window=window,
                         **({} if act == "silu" else {"act": act}))
