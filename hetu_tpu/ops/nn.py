"""NN ops: conv/pool/norm/dropout/softmax.

Reference kernels: src/ops/CudnnConv2d*.cu, MaxPool.cu, AvgPool.cu,
CudnnBn.cu, LayerNorm.cu, InstanceNorm2d.cu, Dropout.cu, Softmax.cu,
CudnnSoftmax.cu.  Layouts follow the reference (NCHW, OIHW) for API parity;
XLA re-layouts internally for the MXU so no transposes are exposed.
Dropout uses counter-based per-op RNG (TraceContext.rng_for) so the autodiff
re-trace replays identical masks — the TPU analogue of the reference's
seed+seqnum scheme (python/hetu/random.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..graph.node import Op, VariableOp
from .base import simple_op, SimpleOp
from .. import initializers as init


def _pair(v):
    return (v, v) if isinstance(v, int) else tuple(v)


def _conv2d(x, w, padding=0, stride=1, dilation=1, groups=1):
    ph, pw = _pair(padding)
    sh, sw = _pair(stride)
    dh, dw = _pair(dilation)
    # API layout is NCHW (reference parity) but the compute runs NHWC —
    # the TPU-native conv layout (channels on the lane dim).  XLA's
    # algebraic simplifier pushes the boundary transposes through the
    # elementwise/BN chain so conv→bn→relu→conv stays NHWC end to end
    # (measured: ResNet-18/CIFAR trains ~25% faster than NCHW compute).
    out = lax.conv_general_dilated(
        x.transpose(0, 2, 3, 1), w.transpose(2, 3, 1, 0),
        window_strides=(sh, sw), padding=((ph, ph), (pw, pw)),
        rhs_dilation=(dh, dw), feature_group_count=groups,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32).astype(x.dtype)
    return out.transpose(0, 3, 1, 2)


conv2d_op = simple_op(_conv2d, "conv2d")
conv2d_add_bias_op = simple_op(
    lambda x, w, b, padding=0, stride=1, dilation=1, groups=1:
        _conv2d(x, w, padding, stride, dilation, groups)
        + b.reshape(1, -1, 1, 1),
    "conv2d_add_bias")


def _conv2d_nhwc(x, w, padding=0, stride=1, dilation=1, groups=1):
    """Fully channels-last conv: x NHWC, w HWIO, out NHWC — zero layout
    transposes anywhere (the TPU-native end-to-end form; the NCHW API
    ops keep reference parity and cost boundary transposes that XLA
    mostly, but not always, cancels)."""
    ph, pw = _pair(padding)
    sh, sw = _pair(stride)
    dh, dw = _pair(dilation)
    return lax.conv_general_dilated(
        x, w, window_strides=(sh, sw), padding=((ph, ph), (pw, pw)),
        rhs_dilation=(dh, dw), feature_group_count=groups,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.float32).astype(x.dtype)



def _conv2d_hwio(x, w, padding=0, stride=1, dilation=1, groups=1):
    """Conv with the weight ALREADY in HWIO (the TPU-native kernel
    layout).  The OIHW->HWIO transpose in ``_conv2d`` is a logical
    no-op but XLA materializes it as a physical copy of every kernel
    every step (~177 MB/step on ResNet-18); layers that own their
    weights store HWIO natively (layers/common.py Conv2d) and only the
    op API keeps NCHW activations for reference parity."""
    return _conv2d_nhwc(x.transpose(0, 2, 3, 1), w, padding, stride,
                        dilation, groups).transpose(0, 3, 1, 2)


conv2d_hwio_op = simple_op(_conv2d_hwio, "conv2d_hwio")
conv2d_hwio_add_bias_op = simple_op(
    lambda x, w, b, padding=0, stride=1, dilation=1, groups=1:
        _conv2d_hwio(x, w, padding, stride, dilation, groups)
        + b.reshape(1, -1, 1, 1),
    "conv2d_hwio_add_bias")


conv2d_nhwc_op = simple_op(_conv2d_nhwc, "conv2d_nhwc")
conv2d_nhwc_add_bias_op = simple_op(
    lambda x, w, b, padding=0, stride=1, dilation=1, groups=1:
        _conv2d_nhwc(x, w, padding, stride, dilation, groups) + b,
    "conv2d_nhwc_add_bias")


def _conv2d_transpose(x, w, padding=0, stride=1):
    ph, pw = _pair(padding)
    sh, sw = _pair(stride)
    return lax.conv_transpose(
        x, w, strides=(sh, sw), padding=((ph, ph), (pw, pw)),
        dimension_numbers=("NCHW", "IOHW", "NCHW"))


conv2d_transpose_op = simple_op(_conv2d_transpose, "conv2d_transpose")


def _pool(x, kernel_H, kernel_W, padding=0, stride=1, mode="max"):
    ph, pw = _pair(padding)
    sh, sw = _pair(stride)
    window = (1, 1, kernel_H, kernel_W)
    strides = (1, 1, sh, sw)
    pads = ((0, 0), (0, 0), (ph, ph), (pw, pw))
    if mode == "max":
        # -inf init (not finfo.min): jax only attaches the max-pool VJP
        # rule when the reduction is recognizably reduce-window-max
        neg = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, neg, lax.max, window, strides, pads)
    s = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
    # count_include_pad=True matches the reference AvgPool.cu
    return s / (kernel_H * kernel_W)


max_pool2d_op = simple_op(
    lambda x, kernel_H=2, kernel_W=2, padding=0, stride=2:
        _pool(x, kernel_H, kernel_W, padding, stride, "max"),
    "max_pool2d")
avg_pool2d_op = simple_op(
    lambda x, kernel_H=2, kernel_W=2, padding=0, stride=2:
        _pool(x, kernel_H, kernel_W, padding, stride, "avg"),
    "avg_pool2d")
global_avg_pool2d_op = simple_op(
    lambda x, channels_last=False:
        jnp.mean(x, axis=(1, 2) if channels_last else (2, 3)),
    "global_avg_pool2d")

softmax_op = simple_op(
    lambda x, dim=-1: jax.nn.softmax(x, axis=dim), "softmax")
log_softmax_op = simple_op(
    lambda x, dim=-1: jax.nn.log_softmax(x, axis=dim), "log_softmax")


def _layer_norm(x, scale, bias, eps=1e-5):
    # moments in f32 (bf16 mean/variance loses too much precision)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return (((xf - mean) * lax.rsqrt(var + eps)).astype(x.dtype)
            * scale + bias)


layer_normalization_op = simple_op(_layer_norm, "layer_normalization")


def _rms_norm(x, scale, eps=1e-6, zero_centered=False):
    """``zero_centered``: the weight is stored about zero and the scale is
    ``1 + w`` in f32 (Gemma, Qwen3-Next); the default scales by ``w``."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    if zero_centered:
        return (xf * lax.rsqrt(var + eps)
                * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)
    return (xf * lax.rsqrt(var + eps)).astype(x.dtype) * scale


rms_norm_op = simple_op(_rms_norm, "rms_norm")


def _instance_norm2d(x, eps=1e-7):
    mean = jnp.mean(x, axis=(2, 3), keepdims=True)
    var = jnp.var(x, axis=(2, 3), keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps)


instance_normalization2d_op = simple_op(_instance_norm2d, "instance_norm2d")


import functools as _functools


@_functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _shifted_stats(xf, shift, red, vec):
    """Shifted one-pass batch stats: (mean, var) over ``red`` axes with
    deviations taken against the per-channel ``shift`` (see
    BatchNormOp).  The custom vjp emits the backward in the DISTRIBUTED
    form ``x * k + broadcast(c)`` instead of autodiff's
    ``(x - shift) * k``: numerically identical per element, but the
    subtract in the backward's big elementwise producer blocks XLA from
    matching the canonical conv+BN backward fusion (measured 963
    us/step on ResNet-18/2048 — the whole r2-r4 gap vs the flax twin)."""
    s = shift.reshape(vec)
    d = xf - s
    dmean = jnp.mean(d, axis=red)
    d2mean = jnp.mean(jnp.square(d), axis=red)
    var = jnp.maximum(d2mean - jnp.square(dmean), 0.0)
    return shift + dmean, var


def _shifted_stats_fwd(xf, shift, red, vec):
    mean, var = _shifted_stats(xf, shift, red, vec)
    return (mean, var), (xf, mean, shift)


def _shifted_stats_bwd(red, vec, res, cts):
    xf, mean, shift = res
    ct_mean, ct_var = cts
    n = 1
    for ax in red:
        n *= xf.shape[ax]
    inv_n = 1.0 / n
    # d mean / d x = 1/N;  d var / d x = (2/N) (x - mean) — distributed
    # as x * (2/N ct_var) - broadcast((2/N) ct_var * mean) so the big
    # term stays LINEAR in x (fusable into the backward conv).  The
    # var<0 clamp's boundary gradient is intentionally ignored: it only
    # engages on numerically-negative variances (degenerate inputs).
    k = (2.0 * inv_n) * ct_var
    g = (xf * k.reshape(vec)
         + (inv_n * ct_mean - k * mean).reshape(vec))
    return g.astype(xf.dtype), jnp.zeros_like(shift)


_shifted_stats.defvjp(_shifted_stats_fwd, _shifted_stats_bwd)


class BatchNormOp(Op):
    """BatchNorm with running-stat state (reference CudnnBn.cu keeps
    running mean/var on the op; here they are non-trainable Variables updated
    through the trace context).

    Batch statistics use a shifted one-pass form by default (shift = the
    running mean — a parameter, so the reductions fuse with the producing
    conv; flax's ``use_fast_variance`` default accepts the same
    single-read tradeoff with NO shift at all).  The shift lags the data
    by the EMA horizon, so pathological inputs (per-channel |mean| >> std
    before the EMA catches up) can still lose variance precision in f32;
    ``precise_stats=True`` selects the exact two-pass mean-then-deviations
    form (one extra read of x) for such inputs."""

    def __init__(self, x, scale, bias, momentum=0.1, eps=1e-5,
                 precise_stats=False, channel_axis=1, name=None):
        base = name or f"bn_{scale.name}"
        c = scale.shape[0] if isinstance(scale, VariableOp) else None
        assert c is not None, "BatchNorm scale must be a Variable"
        self.running_mean = VariableOp(base + "_running_mean", (c,),
                                       init.zeros(), trainable=False)
        self.running_var = VariableOp(base + "_running_var", (c,),
                                      init.ones(), trainable=False)
        super().__init__(x, scale, bias, self.running_mean, self.running_var,
                         name=base)
        self.momentum = momentum
        self.eps = eps
        self.precise_stats = precise_stats
        # 1 = NCHW (reference layout); -1 = channels-last (NHWC)
        self.channel_axis = channel_axis

    @property
    def is_stateful(self):
        return True

    def _compute(self, input_vals, ctx):
        x, scale, bias, rmean, rvar = input_vals
        ax = self.channel_axis % x.ndim
        vec = [1] * x.ndim
        vec[ax] = -1
        red = tuple(i for i in range(x.ndim) if i != ax)
        bias = bias.reshape(vec)
        if ctx.training:
            # batch stats in f32; running stats update against the f32
            # masters (bf16 bindings would re-quantize them every step and
            # round small momentum updates away)
            xf = x.astype(jnp.float32)
            m = self.momentum
            master = ctx.master_params
            rm = (master[self.running_mean.name]
                  if master is not None else rmean).astype(jnp.float32)
            rv = (master[self.running_var.name]
                  if master is not None else rvar).astype(jnp.float32)
            if self.precise_stats:
                # exact two-pass mean-then-deviations (one extra read)
                mean = jnp.mean(xf, axis=red)
                var = jnp.mean(jnp.square(
                    xf - mean.reshape(vec)), axis=red)
            else:
                # shifted one-pass stats: x is read once for both
                # reductions (half the stats traffic of the two-pass
                # form), deviations taken against a per-channel shift
                # before squaring — the raw E[x^2]-E[x]^2 form cancels
                # catastrophically in f32 when |mean| >> std.  The shift
                # is the RUNNING mean: a parameter, so it fuses freely (a
                # shift sliced from x itself costs ~7% of a ResNet-18
                # step by blocking the reduction's fusion with the
                # producing conv) and converges to the true mean, the
                # optimal shift.  mean/var are mathematically
                # shift-independent, so stop_gradient keeps the backward
                # pass exact.  See the class docstring for the
                # early-steps caveat and the precise_stats escape hatch.
                # _shifted_stats carries a hand-written vjp in the
                # distributed x*k + broadcast form (autodiff's (x-s)*k
                # blocks the backward conv fusion — 963 us/step on
                # ResNet-18/2048).
                mean, var = _shifted_stats(
                    xf, lax.stop_gradient(rm), red, tuple(vec))
            ctx.record_update(self.running_mean, (1 - m) * rm + m * mean)
            ctx.record_update(self.running_var, (1 - m) * rv + m * var)
            mean = mean.astype(x.dtype)
            var = var.astype(x.dtype)
        else:
            mean, var = rmean, rvar
        # stop_gradient on batch stats is NOT applied: gradients flow through
        # mean/var exactly as in cudnnBatchNormalizationBackward.
        # scale folds into the rsqrt as ONE per-channel multiplier BEFORE
        # touching x: one whole-tensor multiply instead of two, and — the
        # real win — the backward's big reductions become channel-
        # -scalar-free bilinear terms of (x-mean) and g that XLA can CSE
        # into 3 reduces instead of 4 (the 963 us/step ResNet-18 gap vs
        # the flax twin was exactly this extra fused reduction).
        inv = (lax.rsqrt(var.astype(jnp.float32) + self.eps)
               * scale.astype(jnp.float32)).astype(x.dtype)
        return (x - mean.reshape(vec)) * inv.reshape(vec) + bias


def batch_normalization_op(x, scale, bias, momentum=0.1, eps=1e-5,
                           precise_stats=False, channel_axis=1, name=None):
    return BatchNormOp(x, scale, bias, momentum=momentum, eps=eps,
                       precise_stats=precise_stats,
                       channel_axis=channel_axis, name=name)


def _dropout_mask_plan(shape, mesh):
    """Decide where the keep mask of an activation of ``shape`` is drawn.

    Returns ``(reason, batch_axes)``: ``reason`` is None when the Pallas
    kernel draws it from the chip's generator (ops/pallas/dropout.py; on
    each device for its own rows, under ``shard_map`` over ``batch_axes``,
    when that tuple is non-empty), else why ``jax.random.bernoulli``
    does.  The kernel is taken on ``tpu`` only (the generator does not
    exist elsewhere), for shards that are whole int8 tiles, and under a
    mesh only where nothing but ``dp`` splits the program: on any other
    axis the operand may be replicated and its shards must agree on one
    mask."""
    from .pallas import dispatch
    from .pallas.dropout import unsupported
    if not dispatch.mosaic():
        return f"platform:{dispatch.platform()}", ()
    why = unsupported(shape)
    if why is not None:
        return why, ()
    why, axes = dispatch.shard_axes(mesh, {"dp": shape[0]})
    if why is not None:
        return why, ()
    shards = mesh.shape["dp"] if axes["dp"] else 1
    why = unsupported((shape[0] // shards,) + tuple(shape[1:]))
    return why, axes["dp"] if why is None else ()


class DropoutOp(Op):
    """Inverted dropout (reference Dropout.cu / CudnnDropout)."""

    def __init__(self, x, keep_prob=0.9, name=None):
        super().__init__(x, name=name)
        self.keep_prob = keep_prob

    @property
    def needs_rng(self):
        return True

    def _compute(self, input_vals, ctx):
        (x,) = input_vals
        if not ctx.training or self.keep_prob >= 1.0:
            return x
        mask = self._keep_mask(x.shape, ctx)
        return jnp.where(mask, x / self.keep_prob, 0.0).astype(x.dtype)

    def _keep_mask(self, shape, ctx):
        from .pallas import dispatch
        why, batch_axes = _dropout_mask_plan(shape, ctx.mesh)
        if not dispatch.record("dropout", why):
            return jax.random.bernoulli(ctx.rng_for(self), self.keep_prob,
                                        shape)
        from .pallas.dropout import dropout_mask, sharded_dropout_mask
        seed = jax.random.bits(ctx.rng_for(self), (1,),
                               "uint32").astype(jnp.int32)
        if batch_axes:
            mask = sharded_dropout_mask(ctx.mesh, seed, shape,
                                        self.keep_prob,
                                        batch_axes=batch_axes)
        else:
            mask = dropout_mask(seed, shape, self.keep_prob)
        return mask != 0


def dropout_op(x, keep_prob=0.9, name=None):
    return DropoutOp(x, keep_prob=keep_prob, name=name)


def dropout2d_op(x, keep_prob=0.9, name=None):
    """Channel-wise dropout (reference Dropout2d.cu)."""

    class Dropout2dOp(DropoutOp):
        def _compute(self, input_vals, ctx):
            (x,) = input_vals
            if not ctx.training or self.keep_prob >= 1.0:
                return x
            mask = jax.random.bernoulli(
                ctx.rng_for(self), self.keep_prob, x.shape[:2])
            mask = mask.reshape(x.shape[0], x.shape[1], 1, 1)
            return jnp.where(mask, x / self.keep_prob, 0.0).astype(x.dtype)

    return Dropout2dOp(x, keep_prob=keep_prob, name=name)


class RandomSampleOp(Op):
    """Source RNG ops (reference gpu_ops/Rand.py, Sample.py,
    src/ops/Initializers.cu): uniform / normal / gumbel draws as graph
    nodes, keyed by the trace's per-op counter-based RNG so autodiff
    re-traces see identical draws."""

    def __init__(self, shape, dist="normal", low=0.0, high=1.0, mean=0.0,
                 stddev=1.0, dtype=jnp.float32, name=None):
        assert dist in ("normal", "uniform", "gumbel", "randint")
        super().__init__(name=name)
        self.shape = tuple(shape)
        self.dist = dist
        self.low, self.high = low, high
        self.mean, self.stddev = mean, stddev
        self.dtype = dtype

    @property
    def needs_rng(self):
        return True

    def _compute(self, input_vals, ctx):
        key = ctx.rng_for(self)
        if self.dist == "normal":
            return (self.mean + self.stddev
                    * jax.random.normal(key, self.shape, self.dtype))
        if self.dist == "uniform":
            return jax.random.uniform(key, self.shape, self.dtype,
                                      self.low, self.high)
        if self.dist == "randint":
            dt = (jnp.int32 if self.dtype in (jnp.float32, None)
                  else self.dtype)
            return jax.random.randint(key, self.shape, int(self.low),
                                      int(self.high), dt)
        u = jax.random.uniform(key, self.shape, self.dtype, 1e-20, 1.0)
        return -jnp.log(-jnp.log(u))


def random_normal_op(shape, mean=0.0, stddev=1.0, name=None):
    return RandomSampleOp(shape, "normal", mean=mean, stddev=stddev,
                          name=name)


def random_uniform_op(shape, low=0.0, high=1.0, name=None):
    return RandomSampleOp(shape, "uniform", low=low, high=high, name=name)


def gumbel_sample_op(shape, name=None):
    return RandomSampleOp(shape, "gumbel", name=name)


def randint_sample_op(shape, low, high, name=None):
    return RandomSampleOp(shape, "randint", low=low, high=high, name=name)
