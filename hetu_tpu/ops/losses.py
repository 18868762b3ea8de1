"""Loss ops.

Reference kernels: src/ops/SoftmaxCrossEntropy.cu (fused),
SoftmaxCrossEntropySparse.cu, CrossEntropy.cu, CrossEntropySparse.cu,
NllLoss.cu, BinaryCrossEntropyWithLogits.cu, MSELoss via compositions.
The fused softmax-CE forms are written as max-subtracted logsumexp
expressions that XLA fuses into a single pass (no separate softmax
materialization), matching the fusion the reference hand-codes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .base import SimpleOp, simple_op


def _softmax_cross_entropy(y, y_, dim=-1):
    """y = logits, y_ = one-hot (or soft) targets; returns per-row loss."""
    y = y.astype(jnp.float32)  # stable under bf16 compute policies
    y_ = y_.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(y, axis=dim, keepdims=True)
    log_probs = y - lse
    return -jnp.sum(y_ * log_probs, axis=dim)


softmax_cross_entropy_op = simple_op(_softmax_cross_entropy,
                                     "softmax_cross_entropy")


def _ce_kernel_plan(y, dim, mesh):
    """``(reason, row_axes)`` for the fused Pallas softmax-CE: ``reason``
    is None when the kernel runs (per shard over ``row_axes`` when that is
    non-empty), else why the jnp form runs."""
    from .pallas import dispatch
    from .pallas.softmax_ce import unsupported
    if dim not in (-1, y.ndim - 1):
        return "class_dim_not_last", ()
    rows = int(np.prod(y.shape[:-1]))
    # under a mesh: per shard, rows over 'dp'.  Any other axis is refused:
    # a 'tp' axis may leave the vocabulary sharded (tied vocab-parallel
    # heads), which a per-row kernel cannot read without gathering it,
    # while the jnp form partitions under GSPMD
    why, axes = dispatch.shard_axes(mesh, {"dp": rows})
    if why is not None:
        return why, ()
    if axes["dp"]:
        rows //= mesh.shape["dp"]
    return unsupported(jax.ShapeDtypeStruct((rows, y.shape[-1]),
                                            y.dtype)), axes["dp"]


def _softmax_cross_entropy_sparse(y, labels, dim=-1, ignored_index=-1,
                                  mesh=None):
    # fused Pallas path: streams the vocab once with online logsumexp;
    # also sidesteps an XLA pathology for lane-unaligned vocab sizes
    # (GPT-2's 50257: 3.3x slower than 50304 through the jnp form)
    from .pallas import dispatch, softmax_ce
    why, row_axes = _ce_kernel_plan(y, dim, mesh)
    if dispatch.record("softmax_ce", why):
        if row_axes:
            v = y.shape[-1]
            return softmax_ce.sharded_softmax_ce_sparse(
                mesh, y.reshape(-1, v), labels.reshape(-1),
                ignored_index, row_axes).reshape(y.shape[:-1])
        return softmax_ce.fused_softmax_ce_sparse(
            y, labels, ignored_index=ignored_index)
    y = y.astype(jnp.float32)  # stable under bf16 compute policies
    lse = jax.scipy.special.logsumexp(y, axis=dim)
    labels = labels.astype(jnp.int32)
    picked = jnp.take_along_axis(
        y, jnp.expand_dims(jnp.maximum(labels, 0), dim), axis=dim
    ).squeeze(dim)
    loss = lse - picked
    return jnp.where(labels == ignored_index, 0.0, loss)


class _SoftmaxCESparseOp(SimpleOp):
    """The one loss op that needs the trace context: its kernel runs per
    shard when the executor has a mesh."""

    def _compute(self, input_vals, ctx):
        return self.impl(*input_vals, mesh=ctx.mesh, **self.attrs)


softmax_cross_entropy_sparse_op = simple_op(
    _softmax_cross_entropy_sparse, "softmax_cross_entropy_sparse",
    node_cls=_SoftmaxCESparseOp)


def _cross_entropy(y, y_, dim=-1, eps=1e-12):
    """y = probabilities (post-softmax), y_ = one-hot targets."""
    y = y.astype(jnp.float32)
    return -jnp.sum(y_ * jnp.log(jnp.maximum(y, eps)), axis=dim)


crossentropy_op = simple_op(_cross_entropy, "crossentropy")


def _cross_entropy_sparse(y, labels, dim=-1, ignored_index=-1, eps=1e-12):
    y = y.astype(jnp.float32)
    labels = labels.astype(jnp.int32)
    picked = jnp.take_along_axis(
        y, jnp.expand_dims(jnp.maximum(labels, 0), dim), axis=dim
    ).squeeze(dim)
    loss = -jnp.log(jnp.maximum(picked, eps))
    return jnp.where(labels == ignored_index, 0.0, loss)


crossentropy_sparse_op = simple_op(_cross_entropy_sparse,
                                   "crossentropy_sparse")


def _nll_loss(log_probs, labels):
    log_probs = log_probs.astype(jnp.float32)
    labels = labels.astype(jnp.int32)
    return -jnp.take_along_axis(log_probs, labels[:, None], axis=-1)[:, 0]


nll_loss_op = simple_op(_nll_loss, "nll_loss")


def _bce_with_logits(logits, targets):
    # numerically stable: max(x,0) - x*z + log(1+exp(-|x|))
    logits = logits.astype(jnp.float32)
    targets = targets.astype(jnp.float32)
    return (jnp.maximum(logits, 0) - logits * targets
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))


binarycrossentropywithlogits_op = simple_op(_bce_with_logits,
                                            "bce_with_logits")
binary_cross_entropy_op = simple_op(
    lambda y, y_, eps=1e-12:
        -(y_.astype(jnp.float32)
          * jnp.log(jnp.maximum(y.astype(jnp.float32), eps))
          + (1 - y_.astype(jnp.float32))
          * jnp.log(jnp.maximum(1 - y.astype(jnp.float32), eps))),
    "binary_cross_entropy")
mse_loss_op = simple_op(
    lambda y, y_, reduction="mean":
        jnp.mean(jnp.square(y - y_)) if reduction == "mean"
        else jnp.square(y - y_),
    "mse_loss")
mae_loss_op = simple_op(
    lambda y, y_, reduction="mean":
        jnp.mean(jnp.abs(y - y_)) if reduction == "mean"
        else jnp.abs(y - y_),
    "mae_loss")
huber_loss_op = simple_op(
    lambda y, y_, delta=1.0: jnp.where(
        jnp.abs(y - y_) <= delta,
        0.5 * jnp.square(y - y_),
        delta * (jnp.abs(y - y_) - 0.5 * delta)),
    "huber_loss")
kl_div_op = simple_op(
    lambda log_p, q, eps=1e-12: q * (jnp.log(jnp.maximum(q, eps)) - log_p),
    "kl_div")
