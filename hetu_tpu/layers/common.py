"""Common layers: Linear, Conv2d, norms, Embedding, Dropout, activations.

Reference: /root/reference/python/hetu/layers/{linear,conv,normalization,
embedding,dropout,relu,gelu,mish,pooling,reshape,concatenate,sum,slice}.py
"""

from __future__ import annotations

import numpy as np

from .base import BaseLayer, fresh_name
from .. import initializers as init
from ..graph.node import VariableOp, scope
from ..ops import (matmul_op, linear_op, broadcastto_op, conv2d_op,
                   conv2d_add_bias_op, conv2d_hwio_op,
                   conv2d_hwio_add_bias_op, conv2d_nhwc_op,
                   conv2d_nhwc_add_bias_op, batch_normalization_op,
                   layer_normalization_op, rms_norm_op, dropout_op, relu_op,
                   gelu_op, silu_op, tanh_op, sigmoid_op, leaky_relu_op,
                   max_pool2d_op, avg_pool2d_op, array_reshape_op,
                   embedding_lookup_op, concatenate_op, softplus_op, mul_op)


class Linear(BaseLayer):
    def __init__(self, in_features, out_features, bias=True,
                 initializer=None, activation=None, name=None):
        name = fresh_name(name or "dense")
        self.weight = VariableOp(
            f"{name}_weight", (in_features, out_features),
            initializer or init.xavier_normal())
        self.bias = VariableOp(f"{name}_bias", (out_features,),
                               init.zeros()) if bias else None
        self.activation = activation

    def __call__(self, x):
        if self.bias is not None:
            out = linear_op(x, self.weight, self.bias)
        else:
            out = matmul_op(x, self.weight)
        if self.activation is not None:
            out = self.activation(out)
        return out


class _HWIOAdapter:
    """Run an OIHW-convention initializer, store the result HWIO.

    Keeps fan-in/fan-out semantics (initializers._fans assumes OIHW for
    4-D shapes) bit-identical to the reference convention while the
    layer stores the TPU-native kernel layout."""

    def __init__(self, inner):
        self.inner = inner

    def __call__(self, key, shape, dtype=np.float32):
        kh, kw, ci, co = shape
        w = self.inner(key, (co, ci, kh, kw), dtype)
        import jax.numpy as jnp
        return jnp.transpose(w, (2, 3, 1, 0))


class Conv2d(BaseLayer):
    """2-D convolution (reference layers/conv.py).

    The weight is stored HWIO (TPU-native): the OIHW->HWIO transpose
    that API-layout parity would need costs a physical copy of every
    kernel every step under XLA (~177 MB/step on ResNet-18/2048).
    ``load_oihw``/``dump_oihw`` convert at the checkpoint boundary for
    torch/ONNX-convention arrays."""

    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, bias=True, initializer=None, activation=None,
                 channels_last=False, name=None):
        name = fresh_name(name or "conv2d")
        ks = kernel_size if isinstance(kernel_size, tuple) \
            else (kernel_size, kernel_size)
        self.weight = VariableOp(
            f"{name}_weight", ks + (in_channels, out_channels),
            _HWIOAdapter(initializer or init.he_normal()))
        self.bias = VariableOp(f"{name}_bias", (out_channels,),
                               init.zeros()) if bias else None
        self.stride, self.padding = stride, padding
        self.activation = activation
        # channels_last: activations are NHWC end to end (zero layout
        # transposes — the fully TPU-native form); default keeps the
        # reference's NCHW activation API
        self.channels_last = channels_last

    @staticmethod
    def load_oihw(w):
        """torch/ONNX-convention (O, I, H, W) array -> this layer's
        stored layout."""
        return np.transpose(np.asarray(w), (2, 3, 1, 0))

    @staticmethod
    def dump_oihw(w):
        """Stored layout -> torch/ONNX-convention (O, I, H, W)."""
        return np.transpose(np.asarray(w), (3, 2, 0, 1))

    def __call__(self, x):
        if self.channels_last:
            op, op_b = conv2d_nhwc_op, conv2d_nhwc_add_bias_op
        else:
            op, op_b = conv2d_hwio_op, conv2d_hwio_add_bias_op
        if self.bias is not None:
            out = op_b(x, self.weight, self.bias,
                       padding=self.padding, stride=self.stride)
        else:
            out = op(x, self.weight, padding=self.padding,
                     stride=self.stride)
        if self.activation is not None:
            out = self.activation(out)
        return out


class BatchNorm(BaseLayer):
    """BatchNorm over [N, C, H, W] (reference layers/normalization.py).

    Batch statistics default to a shifted one-pass form whose shift is
    the RUNNING mean — fastest (fuses with the producing conv), but for
    the first steps the zero-initialized shift gives the raw
    E[x^2]-E[x]^2 f32 form, which cancels catastrophically on inputs
    with per-channel |mean| >> std.  For such offset-heavy inputs pass
    ``precise_stats=True`` (exact two-pass stats, one extra read of x;
    see ops/nn.py BatchNormOp)."""

    def __init__(self, num_channels, momentum=0.1, eps=1e-5,
                 precise_stats=False, channels_last=False, name=None):
        name = fresh_name(name or "bn")
        self.scale = VariableOp(f"{name}_scale", (num_channels,), init.ones())
        self.bias = VariableOp(f"{name}_bias", (num_channels,), init.zeros())
        self.momentum, self.eps = momentum, eps
        self.precise_stats = precise_stats
        self.channel_axis = -1 if channels_last else 1

    def __call__(self, x):
        return batch_normalization_op(x, self.scale, self.bias,
                                      momentum=self.momentum, eps=self.eps,
                                      precise_stats=self.precise_stats,
                                      channel_axis=self.channel_axis)


class LayerNorm(BaseLayer):
    def __init__(self, hidden_size, eps=1e-5, name=None):
        name = fresh_name(name or "ln")
        self.scale = VariableOp(f"{name}_scale", (hidden_size,), init.ones())
        self.bias = VariableOp(f"{name}_bias", (hidden_size,), init.zeros())
        self.eps = eps

    def __call__(self, x):
        return layer_normalization_op(x, self.scale, self.bias, eps=self.eps)


class RMSNorm(BaseLayer):
    """``x / rms(x) * w``, over the last axis (a head's width when ``x`` is
    a ``[..., heads, d]`` view).  ``zero_centered`` stores ``w`` about zero
    and scales by ``1 + w`` in f32 (Qwen3-Next, Gemma)."""

    def __init__(self, hidden_size, eps=1e-6, zero_centered=False,
                 name=None):
        name = fresh_name(name or "rmsnorm")
        self.zero_centered = zero_centered
        self.scale = VariableOp(
            f"{name}_scale", (hidden_size,),
            init.zeros() if zero_centered else init.ones())
        self.eps = eps

    def __call__(self, x):
        if self.zero_centered:
            return rms_norm_op(x, self.scale, eps=self.eps,
                               zero_centered=True)
        return rms_norm_op(x, self.scale, eps=self.eps)


class Embedding(BaseLayer):
    def __init__(self, num_embeddings, embedding_dim, initializer=None,
                 name=None):
        name = fresh_name(name or "embedding")
        self.weight = VariableOp(
            f"{name}_table", (num_embeddings, embedding_dim),
            initializer or init.normal(0.0, 0.01))

    def __call__(self, ids):
        with scope("hetu_embed"):
            return embedding_lookup_op(self.weight, ids)


class DropOut(BaseLayer):
    def __init__(self, keep_prob=0.9):
        self.keep_prob = keep_prob

    def __call__(self, x):
        return dropout_op(x, keep_prob=self.keep_prob)


class Relu(BaseLayer):
    def __call__(self, x):
        return relu_op(x)


class Gelu(BaseLayer):
    def __call__(self, x):
        return gelu_op(x)


class Mish(BaseLayer):
    """x * tanh(softplus(x)) (reference layers/mish.py)."""

    def __call__(self, x):
        return mul_op(x, tanh_op(softplus_op(x)))


class MaxPool2d(BaseLayer):
    def __init__(self, kernel_size, stride=None, padding=0):
        self.k = kernel_size
        self.s = stride or kernel_size
        self.p = padding

    def __call__(self, x):
        return max_pool2d_op(x, kernel_H=self.k, kernel_W=self.k,
                             padding=self.p, stride=self.s)


class AvgPool2d(MaxPool2d):
    def __call__(self, x):
        return avg_pool2d_op(x, kernel_H=self.k, kernel_W=self.k,
                             padding=self.p, stride=self.s)


class Reshape(BaseLayer):
    def __init__(self, shape):
        self.shape = tuple(shape)

    def __call__(self, x):
        return array_reshape_op(x, output_shape=self.shape)


class Concatenate(BaseLayer):
    def __init__(self, axis=0):
        self.axis = axis

    def __call__(self, xs):
        return concatenate_op(list(xs), axis=self.axis)


class SumLayers(BaseLayer):
    def __init__(self, layers):
        self.layers = list(layers)

    def __call__(self, x):
        outs = [l(x) for l in self.layers]
        acc = outs[0]
        for o in outs[1:]:
            acc = acc + o
        return acc
