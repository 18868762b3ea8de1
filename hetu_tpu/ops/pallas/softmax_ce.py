"""Fused sparse softmax-cross-entropy Pallas kernel.

Reference: src/ops/SoftmaxCrossEntropySparse.cu — one of the kernels the
reference fuses by hand and SURVEY §7's build plan names for Pallas
("softmax-CE").  The jnp composition is memory-bound AND hits an XLA
pathology for lane-unaligned vocab sizes (GPT-2's V=50257: 241 ms
fwd+bwd at [8192, V] on v5e vs 72 ms for V=50304); this kernel streams
the vocab once per pass with online logsumexp, handles any V by masking
the ragged tail chunk, and computes the backward from the saved lse
without materializing log-softmax.

  forward : grid (N/bn, V/bv); scratch (m, l, xt) carries the online
            max / sum-exp / target-logit across vocab chunks (TPU grids
            execute sequentially, so VMEM scratch persists along j);
            loss and lse write on the last chunk.
  backward: dlogits = (exp(x - lse) - onehot(label)) * g_row, streamed
            per chunk; rows with label == ignored_index emit zeros.

Per-row vectors (labels, loss, lse, cotangent, scratch) are (rows, 1)
sublane-major — row reductions of a (bn, bv) tile land there without
relayout, and broadcasts against the tile are natural.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dispatch import interpret

_BN = 256     # rows per program
_BV = 2048    # vocab lanes per chunk, at most
#: rows from which a vocabulary under 1,024 classes takes the kernel too
_NARROW_ROWS = 16384
_NEG = -1e30


def _fwd_kernel(x_ref, lab_ref, loss_ref, lse_ref, m_sc, l_sc, xt_sc, *,
                v, bv, nv, ignored):
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)                       # (bn, bv)
    lab = lab_ref[...]                                       # (bn, 1)
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < v
    s = jnp.where(valid, x, _NEG)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full(m_sc.shape, _NEG, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        xt_sc[...] = jnp.zeros(xt_sc.shape, jnp.float32)

    m = m_sc[...]                                            # (bn, 1)
    l = l_sc[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    l_new = l * jnp.exp(m - m_new) + jnp.sum(jnp.exp(s - m_new),
                                             axis=1, keepdims=True)
    m_sc[...] = m_new
    l_sc[...] = l_new
    hit = (col == lab) & valid
    xt_sc[...] = xt_sc[...] + jnp.sum(jnp.where(hit, x, 0.0),
                                      axis=1, keepdims=True)

    @pl.when(j == nv - 1)
    def _fin():
        lse = m_sc[...] + jnp.log(jnp.maximum(l_sc[...], 1e-37))
        loss = lse - xt_sc[...]
        loss_ref[...] = jnp.where(lab == ignored, 0.0, loss)
        lse_ref[...] = lse


def _bwd_kernel(x_ref, lab_ref, lse_ref, g_ref, dx_ref, *, v, bv, ignored):
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)                       # (bn, bv)
    lab = lab_ref[...]                                       # (bn, 1)
    lse = lse_ref[...]
    g = g_ref[...]
    col = j * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < v
    p = jnp.where(valid, jnp.exp(x - lse), 0.0)
    onehot = ((col == lab) & valid).astype(jnp.float32)
    d = (p - onehot) * g
    d = jnp.where(lab == ignored, 0.0, d)
    dx_ref[...] = d.astype(dx_ref.dtype)


def _pad_rows(n):
    return n if n % _BN == 0 else -(-n // _BN) * _BN


def _row_spec():
    return pl.BlockSpec((_BN, 1), lambda i, j: (i, 0))


def _fwd(logits, labels, ignored):
    n, v = logits.shape
    npad = _pad_rows(n)
    if npad != n:
        logits = jnp.pad(logits, ((0, npad - n), (0, 0)))
        labels = jnp.pad(labels, (0, npad - n), constant_values=ignored)
    bv = _chunk(v)
    nv = -(-v // bv)
    kern = functools.partial(_fwd_kernel, v=v, bv=bv, nv=nv,
                             ignored=ignored)
    loss, lse = pl.pallas_call(
        kern,
        name="hetu_softmax_ce_fwd",
        interpret=interpret(),
        grid=(npad // _BN, nv),
        in_specs=[
            pl.BlockSpec((_BN, bv), lambda i, j: (i, j)),
            _row_spec(),
        ],
        out_specs=[_row_spec(), _row_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((npad, 1), jnp.float32),
            jax.ShapeDtypeStruct((npad, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((_BN, 1), jnp.float32),
            pltpu.VMEM((_BN, 1), jnp.float32),
            pltpu.VMEM((_BN, 1), jnp.float32),
        ])(logits, labels.astype(jnp.int32).reshape(npad, 1))
    return loss[:n, 0], lse[:n, 0]


def _bwd(logits, labels, lse, g, ignored):
    n, v = logits.shape
    npad = _pad_rows(n)
    if npad != n:
        logits = jnp.pad(logits, ((0, npad - n), (0, 0)))
        labels = jnp.pad(labels, (0, npad - n), constant_values=ignored)
        lse = jnp.pad(lse, (0, npad - n))
        g = jnp.pad(g, (0, npad - n))
    bv = _chunk(v)
    nv = -(-v // bv)
    kern = functools.partial(_bwd_kernel, v=v, bv=bv, ignored=ignored)
    dx = pl.pallas_call(
        kern,
        name="hetu_softmax_ce_bwd",
        interpret=interpret(),
        grid=(npad // _BN, nv),
        in_specs=[
            pl.BlockSpec((_BN, bv), lambda i, j: (i, j)),
            _row_spec(), _row_spec(), _row_spec(),
        ],
        out_specs=pl.BlockSpec((_BN, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((npad, v), logits.dtype),
    )(logits, labels.astype(jnp.int32).reshape(npad, 1),
      lse.reshape(npad, 1), g.reshape(npad, 1))
    return dx[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _ce(logits, labels, ignored):
    return _fwd(logits, labels, ignored)[0]


def _ce_fwd(logits, labels, ignored):
    loss, lse = _fwd(logits, labels, ignored)
    return loss, (logits, labels, lse)


def _ce_bwd(ignored, res, g):
    logits, labels, lse = res
    dx = _bwd(logits, labels, lse, g.astype(jnp.float32), ignored)
    return dx, None


_ce.defvjp(_ce_fwd, _ce_bwd)


def _chunk(v):
    """Vocabulary lanes a program reads: ``_BV``, or the whole of a narrower
    vocabulary in lane tiles (a byte-level model's 320 classes: 384 lanes,
    not 2,048 of which 84% would be padding)."""
    return min(_BV, -(-v // 128) * 128)


def unsupported(y):
    """Why the kernel does not take logits of this shape, or None when it
    does: below 1024 classes or 8 rows the jnp form is one small fusion
    and a kernel launch buys nothing, unless the rows are many (a byte-level
    model's eight heads: 65,536 rows of 320 classes, 84 MB of f32 logits)."""
    if y.ndim < 2:
        return "rank<2"
    if y.shape[-1] < 1024 and (y.shape[-1] < 128 or int(
            np.prod(y.shape[:-1])) < _NARROW_ROWS):
        return "vocab<1024"
    if int(np.prod(y.shape[:-1])) < 8:
        return "rows<8"
    return None


def fused_softmax_ce_sparse(y, labels, ignored_index=-1):
    """Per-row CE losses (f32), any vocab size; returns None when the
    shape isn't worth the kernel so callers fall back to jnp."""
    if unsupported(y) is not None:
        return None
    v = y.shape[-1]
    n = int(np.prod(y.shape[:-1]))
    out = _ce(y.reshape(n, v), labels.reshape(n), int(ignored_index))
    return out.reshape(y.shape[:-1])


def sharded_softmax_ce_sparse(mesh, y, labels, ignored_index=-1,
                              row_axes=("dp",)):
    """:func:`fused_softmax_ce_sparse` inside a GSPMD mesh program.

    ``pallas_call`` does not partition, and the loss is local to a row,
    so each device runs the kernel on its own rows under ``shard_map``:
    the leading dim of ``y [N, V]`` / ``labels [N]`` splits over
    ``row_axes`` (it must divide) and the vocabulary stays whole on every
    device.  Logits whose vocabulary is itself sharded do not fit this
    layout; their callers keep the jnp form, which GSPMD partitions."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    rows = tuple(row_axes)

    def local(y, labels):
        return fused_softmax_ce_sparse(y, labels, ignored_index)

    # pallas out_shapes carry no varying-axes annotations
    return shard_map(local, mesh=mesh,
                     in_specs=(P(rows, None), P(rows)),
                     out_specs=P(rows), check_vma=False)(y, labels)
