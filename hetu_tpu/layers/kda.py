"""Kimi Delta Attention mixer (Kimi Linear, arXiv:2510.26692; the
linear-attention layers of Ling-3.0): a delta rule whose state decays every
key channel by a factor of its own.

    [q~ | k~ | v~ | f | z] = x W_in          (five blocks of heads x d)
    q~, k~, v~ -> depthwise causal convolution (width 4, no bias) -> SiLU
                     (ops/causal_conv.py, a window of the projection)
    q = l2norm(q~) / sqrt(d),  k = l2norm(k~)            per head
    g = lower_bound * sigmoid(exp(A_log_h) * (f + dt_bias))    in [lower_bound, 0)
                     (f32; flash-linear-attention's lower-bound "safe" gate:
                     a bound below is what lets the chunked rule take
                     exponents sub-chunk by sub-chunk, ops/kda.py)
    beta = sigmoid(x w_beta)                             one number a head
    o = KDA(q, k, v, g, beta)                            (ops/kda.py)
    y = [ o / rms(o) * w_n * sigmoid(z) ] W_out          per head

The decay and output-gate projections are single full-rank matrices (the
configuration's ``no_kda_lora``), so all five projections of ``x`` are one
product.  Four graph nodes under the scopes ``hetu_kda_proj``,
``hetu_kda_conv`` (``ConvOp``: on a TPU ``hetu_conv_fwd`` / ``hetu_conv_bwd``),
``hetu_kda_scan`` and ``hetu_kda_out``.  Where the rule's kernels apply (a
TPU, no mesh, heads of a multiple of 128, bf16 or f32: ``ops/kda.py
chunk_kda_in_place``) the scan node is ``hetu_kda_fwd`` / ``hetu_kda_bwd``
reading the convolution's output and the projection's ``f`` and ``z`` windows
in place, with the norms, the gate and the gated norm of ``o`` taken a head
on the chunk in VMEM: it hands ``hetu_kda_out`` the normalised, gated ``[B,
S, heads d]``, that node is the product alone, and no ``[B, S, heads, d]``
view is formed (PR 41; the backward keeps ``mixed``, ``proj``, ``beta`` and
the chunk-start states).  Elsewhere the scan node is the ``jax.numpy`` norms
and gate around ``chunk_kda`` and ``hetu_kda_out`` norms and gates the 4-D
``o`` it is handed.  A decode step and the recurrent state in a serving cache
are not here (ROADMAP Queue 2, M7).
"""

from __future__ import annotations

import numpy as np

from . import recurrent
from .base import BaseLayer, fresh_name, project
from .. import initializers as init
from ..graph.node import VariableOp
from ..ops import kda
from ..ops.base import KernelOp, ScopedOp as _Scoped
from ..ops.causal_conv import ConvOp


def gate(f, a_log, dt_bias, lower_bound):
    """The decay's logarithm, ``[.., heads, d]`` f32 in ``[lower_bound, 0)``
    from the projection ``f [.., heads, d]``."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    rate = jnp.exp(a_log.astype(f32))[:, None]
    return lower_bound * jax.nn.sigmoid(
        rate * (f.astype(f32) + dt_bias.astype(f32).reshape(rate.shape[0],
                                                            -1)))


def _scan(proj, mixed, beta_lin, a_log, dt_bias, w_norm, *, heads, d,
          lower_bound, eps, rule=None):
    """Norms, gates and the rule: where the kernels apply (``rule`` None
    and ``chunk_kda_in_place`` takes the operands) the already normalised
    and gated ``y [B, S, heads d]``, else ``o [B, S, heads, d]`` for
    ``_out``'s norm and gate."""
    import jax
    import jax.numpy as jnp
    B, S, _ = mixed.shape
    f32 = jnp.float32
    hd = heads * d
    beta = jax.nn.sigmoid(beta_lin.astype(f32))
    if rule is None:
        y = kda.chunk_kda_in_place(mixed, proj, beta, a_log, dt_bias, w_norm,
                                   heads=heads, lower_bound=lower_bound,
                                   eps=eps)
        if y is not None:
            return y
    unit = recurrent.unit_heads
    q = (unit(mixed[..., :hd], heads) * d ** -0.5).astype(mixed.dtype)
    k = unit(mixed[..., hd:2 * hd], heads).astype(mixed.dtype)
    v = mixed[..., 2 * hd:].reshape(B, S, heads, d)
    g = gate(proj[..., 3 * hd:4 * hd].reshape(B, S, heads, d), a_log,
             dt_bias, lower_bound)
    return (rule or kda.chunk_kda)(q, k, v, g, beta)[0]


def _out(o, proj, w_norm, w_out, *, eps):
    """RMSNorm over each head's ``d`` scaled by ``w_norm``, gated channel by
    channel by ``sigmoid(z)`` (the last block of the projection), then the
    output projection; the product alone where the scan node hands over ``y
    [B, S, heads d]``, normalised and gated in its kernel."""
    import jax
    import jax.numpy as jnp
    if o.ndim == 3:
        return o @ w_out
    f32 = jnp.float32
    B, S, heads, d = o.shape
    z = proj[..., 4 * heads * d:].reshape(o.shape)
    of = o.astype(f32)
    of = of * jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + eps)
    y = (w_norm * of.astype(o.dtype)).astype(f32) * jax.nn.sigmoid(
        z.astype(f32))
    return y.astype(o.dtype).reshape(B, S, -1) @ w_out


class KimiDeltaAttention(BaseLayer):
    def __init__(self, hidden_size, num_heads, head_dim, conv_kernel=4,
                 lower_bound=-5.0, eps=1e-6, name=None):
        name = fresh_name(name or "kda")
        self.dims = dict(heads=num_heads, d=head_dim)
        self.lower_bound, self.eps = float(lower_bound), eps
        hd = num_heads * head_dim
        #: q, k, v (convolved), the decay's projection, the output gate
        self.in_proj = VariableOp(f"{name}_in_weight", (hidden_size, 5 * hd),
                                  init.xavier_normal())
        self.beta_proj = VariableOp(f"{name}_beta_weight",
                                    (hidden_size, num_heads),
                                    init.xavier_normal())
        # torch's Conv1d default: uniform within 1 / sqrt(fan_in = kernel)
        bound = 1.0 / np.sqrt(conv_kernel)
        self.conv = VariableOp(f"{name}_conv_weight", (conv_kernel, 3 * hd),
                               init.uniform(-bound, bound))
        # A ~ U(1, 16) a head and dt log-uniform in [0.001, 0.1] a channel:
        # as the Mamba family and flash-linear-attention's KDA initialise
        self.a_log = VariableOp(f"{name}_a_log", (num_heads,),
                                recurrent.log_uniform(1.0, 16.0))
        self.dt_bias = VariableOp(f"{name}_dt_bias", (hd,),
                                  recurrent.dt_bias(1e-3, 1e-1, 1e-4))
        self.norm = VariableOp(f"{name}_norm_scale", (head_dim,), init.ones())
        self.out_proj = VariableOp(f"{name}_out_weight", (hd, hidden_size),
                                   init.xavier_normal())

    def __call__(self, x):
        hd = self.dims["heads"] * self.dims["d"]
        proj = _Scoped(project, "hetu_kda_proj", x, self.in_proj)
        beta = _Scoped(project, "hetu_kda_proj", x, self.beta_proj)
        mixed = ConvOp("hetu_kda_conv", proj, self.conv, window=(0, 3 * hd))
        o = KernelOp(_scan, "hetu_kda_scan", proj, mixed, beta, self.a_log,
                     self.dt_bias, self.norm, kernel="kda",
                     form=lambda: kda.chunk_kda_jnp,
                     lower_bound=self.lower_bound, eps=self.eps, **self.dims)
        return _Scoped(_out, "hetu_kda_out", o, proj, self.norm,
                       self.out_proj, eps=self.eps)
