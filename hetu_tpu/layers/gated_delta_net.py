"""Gated DeltaNet mixer: the linear-attention layer of Qwen3-Next (three of
every four layers; HF ``Qwen3NextGatedDeltaNet``).

    qkvz = x W_qkvz  viewed [.., key heads, d_k + d_k + r d_v + r d_v]
                     (r value heads a key head): q, k, v, z per key head
    ba   = x W_ba    viewed [.., key heads, r + r]: b, a per value head
    [q | k | v] flattened -> depthwise causal convolution (width 4, left
                     padding; this model's has no bias) -> SiLU -> split back
                     (ops/causal_conv.py, shared with layers/mamba2.py)
    beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)      (f32)
    q, k L2-normalised over d_k, repeated for their value heads,
                     q scaled by d_k^-1/2
    o = gated delta rule(q, k, v, g, beta)        (ops/gated_delta.py)
    o = o / rms(o) * w_n * silu(z)  per head;  y = o W_out

Four graph nodes, each under a ``jax.named_scope`` that the device trace's
readers find in the compiled step's ``op_name``: ``hetu_gdn_proj`` (the two
projections and the split), ``hetu_gdn_conv`` (``ops/causal_conv.py ConvOp``:
on a TPU the Pallas kernels ``hetu_conv_fwd`` and ``hetu_conv_bwd``),
``hetu_gdn_scan`` (the gates and the chunked delta rule, whose kernels read
``q~ | k~ | v`` where the convolution wrote them and normalise in VMEM,
``ops/gated_delta.py chunk_gated_delta_rule_in_place``; where that refuses,
the normalisation and the copies a value head in ``jax.numpy`` as well) and ``hetu_gdn_out`` (the gated
norm and the output projection: ``ops/gated_norm.py OutOp``, which reads ``z``
out of ``qkvz`` itself; on a TPU the norm and the gate are the Pallas kernels
``hetu_gated_norm_fwd`` and ``hetu_gated_norm_bwd`` on the scan's ``[B, S,
value heads x d_v]``, ``z`` a key head's 2 d_v lanes of ``qkvz`` read in
place, and ``_out`` below is the ``jax.numpy`` form they are held to; under a
mesh and on any other platform ``_out`` runs).  A decode step and the
recurrent state in a serving cache are not here (ROADMAP Queue 2, M7).
"""

from __future__ import annotations

import numpy as np

from . import recurrent
from .base import BaseLayer, fresh_name, project
from .. import initializers as init
from ..graph.node import VariableOp
from ..ops import gated_delta
from ..ops.base import KernelOp, ScopedOp as _Scoped
from ..ops.causal_conv import ConvOp, causal_conv      # noqa: F401
from ..ops.gated_norm import OutOp, Window


def _mixed(qkvz, *, key_heads, dk, dv, rep):
    """``[B, S, 2 key_dim + 2 value_dim]`` in key-head-major order (``q, k, v,
    z`` a key head) -> ``(q | k | v) [B, S, 2 key_dim + value_dim]``; ``z``,
    the last ``rep dv`` lanes of a key head, is the output node's to read."""
    import jax.numpy as jnp
    B, S, _ = qkvz.shape
    x = qkvz.reshape(B, S, key_heads, 2 * dk + 2 * rep * dv)
    q, k, v, _ = jnp.split(x, [dk, 2 * dk, 2 * dk + rep * dv], axis=-1)
    return jnp.concatenate([t.reshape(B, S, -1) for t in (q, k, v)], -1)


def _scan(mixed, ba, a_log, dt_bias, *, key_heads, dk, dv, rep, rule=None):
    """Gates and the rule: from ``mixed`` as the convolution wrote it where
    the rule's kernels read it in place (``rule`` None and
    ``chunk_gated_delta_rule_in_place`` takes the operands), else the norms
    and the copies a value head here, around ``chunk_gated_delta_rule``."""
    import jax
    import jax.numpy as jnp
    B, S, _ = mixed.shape
    f32 = jnp.float32
    b, a = jnp.split(ba.reshape(B, S, key_heads, 2 * rep), 2, axis=-1)
    beta = jax.nn.sigmoid(b.reshape(B, S, -1).astype(f32))
    g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
        a.reshape(B, S, -1).astype(f32) + dt_bias.astype(f32))
    if rule is None:
        o = gated_delta.chunk_gated_delta_rule_in_place(
            mixed, g, beta, key_heads=key_heads, dk=dk, dv=dv, rep=rep)
        if o is not None:
            return o
    kd = key_heads * dk
    q, k, v = (mixed[..., :kd], mixed[..., kd:2 * kd], mixed[..., 2 * kd:])

    def unit(t):            # L2 norm over a head, then one copy a value head
        t = recurrent.unit_heads(t, key_heads)
        # the copies side by side along the lanes: [.., key heads, rep x dk]
        # is the layout of the [B, S, value heads x dk] the rule's kernels
        # read, where jnp.repeat's [.., key heads, rep, dk] is copied again
        return jnp.concatenate([t] * rep, -1).reshape(B, S, key_heads * rep,
                                                      dk)
    q = (unit(q) * dk ** -0.5).astype(mixed.dtype)
    k = unit(k).astype(mixed.dtype)
    v = v.reshape(B, S, key_heads * rep, dv)
    o = (rule or gated_delta.chunk_gated_delta_rule)(q, k, v, g, beta)[0]
    return o.reshape(B, S, -1)        # as the rule's kernels wrote it


def _out(o, z, w_norm, w_out, *, eps):
    """``o``, ``z [B, S, value heads x dv]``: RMSNorm over each head's ``dv``
    scaled by ``w_norm`` (about one, not zero-centred), gated by ``silu(z)``,
    then the output projection."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    of, zf = (t.reshape(t.shape[:2] + (-1, w_norm.shape[0])).astype(f32)
              for t in (o, z))
    of = of * jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + eps)
    y = (w_norm * of.astype(o.dtype)).astype(f32) * jax.nn.silu(zf)
    return y.astype(o.dtype).reshape(o.shape) @ w_out


class GatedDeltaNet(BaseLayer):
    def __init__(self, hidden_size, num_k_heads, num_v_heads, head_k_dim,
                 head_v_dim, conv_kernel=4, eps=1e-6, name=None):
        name = fresh_name(name or "gdn")
        assert num_v_heads % num_k_heads == 0
        self.dims = dict(key_heads=num_k_heads, dk=head_k_dim, dv=head_v_dim,
                         rep=num_v_heads // num_k_heads)
        self.eps = eps
        key_dim, value_dim = (num_k_heads * head_k_dim,
                              num_v_heads * head_v_dim)
        self.in_proj_qkvz = VariableOp(
            f"{name}_qkvz_weight", (hidden_size, 2 * key_dim + 2 * value_dim),
            init.xavier_normal())
        self.in_proj_ba = VariableOp(
            f"{name}_ba_weight", (hidden_size, 2 * num_v_heads),
            init.xavier_normal())
        # torch's Conv1d default: uniform within 1 / sqrt(fan_in = kernel)
        bound = 1.0 / np.sqrt(conv_kernel)
        self.conv = VariableOp(
            f"{name}_conv_weight", (conv_kernel, 2 * key_dim + value_dim),
            init.uniform(-bound, bound))
        # A ~ U(0, 16) and A_log = log A, dt_bias ones: as HF initialises
        self.a_log = VariableOp(f"{name}_a_log", (num_v_heads,),
                                recurrent.log_uniform(1e-3, 16.0))
        self.dt_bias = VariableOp(f"{name}_dt_bias", (num_v_heads,),
                                  init.ones())
        self.norm = VariableOp(f"{name}_norm_scale", (head_v_dim,),
                               init.ones())
        self.out_proj = VariableOp(f"{name}_out_weight",
                                   (value_dim, hidden_size),
                                   init.xavier_normal())

    def __call__(self, x):
        qkvz = _Scoped(project, "hetu_gdn_proj", x, self.in_proj_qkvz)
        ba = _Scoped(project, "hetu_gdn_proj", x, self.in_proj_ba)
        mixed = _Scoped(_mixed, "hetu_gdn_proj", qkvz, **self.dims)
        mixed = ConvOp("hetu_gdn_conv", mixed, self.conv)
        o = KernelOp(_scan, "hetu_gdn_scan", mixed, ba, self.a_log,
                     self.dt_bias, kernel="gated_delta", form=lambda:
                     gated_delta.chunk_gated_delta_rule_jnp, **self.dims)
        # z where ``_mixed`` leaves it: the last ``rep dv`` lanes of a key head
        dk, dv, rep = (self.dims[n] for n in ("dk", "dv", "rep"))
        return OutOp(_out, "hetu_gdn_out", o, qkvz, self.norm, self.out_proj,
                     window=Window(2 * dk + rep * dv, rep * dv,
                                   2 * dk + 2 * rep * dv),
                     width=dv, gate_first=False, eps=self.eps)

