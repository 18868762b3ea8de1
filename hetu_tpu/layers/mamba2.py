"""Mamba-2 mixer: the state-space layer of Nemotron-H (the ``M`` blocks; HF
``NemotronHMamba2Mixer``: 64 heads in 8 groups) and of Granite 4.0-H (the
``mamba`` layers; HF ``GraniteMoeHybridMambaLayer``: 64 heads in ONE group, so
every head reads the same ``B`` and ``C`` and the gated norm below runs over
all ``d`` = 4,096 channels).  ``H`` heads of ``P`` channels (``d = H P``),
``G`` groups, state size ``N``:

    [z | xBC | dt] = x W_in      widths d, d + 2 G N, H; no bias
    xBC = silu(causal_conv(xBC, w [K, d + 2 G N]) + b)     (ops/causal_conv.py)
    xBC -> x [.., H, P], B [.., G, N], C [.., G, N]; head h reads group
                                 h // (H / G)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)                      (f32)
    S_t = exp(dt_t A) S_(t-1) + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
                                 (ops/ssd.py, chunks of ``chunk`` positions)
    y = y silu(z);  y = y / rms(y over each group's d / G channels) * w
    out = y W_out

``dt`` is not clamped (the family's ``time_step_limit`` is ``(0, inf)``).

Four graph nodes, each under a ``jax.named_scope`` that the device trace's
readers find in the compiled step's ``op_name``: ``hetu_ssm_proj`` (the
input projection and its parts), ``hetu_ssm_conv`` (``ops/causal_conv.py
ConvOp``, which reads ``xBC`` out of the projection's output itself: on a TPU
the Pallas kernels ``hetu_conv_fwd`` and ``hetu_conv_bwd``), ``hetu_ssm_scan``
(the gates, the chunked scan and the skip) and ``hetu_ssm_out`` (the gate,
the grouped norm and the output projection: ``ops/gated_norm.py OutOp``, which
reads ``z`` out of the projection's output itself; on a TPU the gate and the
norm are the Pallas kernels ``hetu_gated_norm_fwd`` and ``hetu_gated_norm_bwd``
over blocks of whole groups, and ``_out`` below is the ``jax.numpy`` form they
are held to; under a mesh and on any other platform ``_out`` runs).  The scan is
``ops/ssd.py``'s: on a TPU the Pallas kernels ``hetu_ssd_fwd`` and
``hetu_ssd_bwd`` where their rule takes the operands (a group of more than
eight heads as blocks of heads, ``ops/pallas/ssd.py``).  The node asks
``chunk_ssd_in_place`` first: the kernels then read ``x``, ``B`` and ``C``
out of ``xBC`` where the convolution wrote them and add the skip ``D x``
themselves (no slice of ``xBC`` and no f32 pass behind the scan in HBM), and
only the softplus, ``-exp(A_log)``, ``a = dt A`` and ``dt``'s turn to a chunk
along the lanes stay XLA's under the same scope.  Where that entry's rule
refuses (``B``'s window not at a whole block, a length that would be padded)
``_scan`` slices and adds the skip around ``chunk_ssd`` and the same kernels;
under a mesh and on any other platform around the ``jax.numpy`` form (the
node is an ``ops/base.py KernelOp``).  A decode step,
and the state ``[H, P, N]`` with the convolution's last ``K - 1`` inputs in a
serving cache, are not here (ROADMAP Queue 2).
"""

from __future__ import annotations

import numpy as np

from . import recurrent
from .base import BaseLayer, fresh_name, project
from .. import initializers as init
from ..graph.node import VariableOp
from ..ops import ssd
from ..ops.base import KernelOp, ScopedOp as _Scoped
from ..ops.causal_conv import ConvOp
from ..ops.gated_norm import OutOp, Window


def _part(zxbcdt, *, lo, hi):
    return zxbcdt[..., lo:hi]


def _scan(xbc, dt, dt_bias, a_log, d_skip, *, heads, head_dim, groups,
          state, chunk, rule=None):
    """Gates, the scan and the skip: from ``xbc`` as the convolution wrote it
    where the scan's kernels read it in place and add the skip themselves
    (``rule`` None and ``chunk_ssd_in_place`` takes the operands), else the
    three slices and the skip here, around ``chunk_ssd``."""
    import jax
    import jax.numpy as jnp
    B, S, _ = xbc.shape
    f32 = jnp.float32
    d, gn = heads * head_dim, groups * state
    dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    A = -jnp.exp(a_log.astype(f32))
    if rule is None:
        y = ssd.chunk_ssd_in_place(xbc, dt, A, d_skip, heads=heads,
                                   head_dim=head_dim, groups=groups,
                                   state=state, chunk=chunk)
        if y is not None:
            return y
    x = xbc[..., :d].reshape(B, S, heads, head_dim)
    Bm = xbc[..., d:d + gn].reshape(B, S, groups, state)
    Cm = xbc[..., d + gn:].reshape(B, S, groups, state)
    y, _ = (rule or ssd.chunk_ssd)(x, dt, A, Bm, Cm, chunk=chunk)
    y = y.astype(f32) + d_skip.astype(f32)[:, None] * x.astype(f32)
    return y.astype(xbc.dtype).reshape(B, S, d)


def _out(y, z, w_norm, w_out, *, groups, eps):
    """``y silu(z)``, RMSNorm over each of ``groups`` runs of channels scaled
    by ``w_norm`` (HF ``MambaRMSNormGated``, the gate before the norm), then
    the output projection."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    g = y.astype(f32) * jax.nn.silu(z.astype(f32))
    g = g.reshape(g.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + eps)
    g = g.reshape(y.shape) * w_norm.astype(f32)
    return g.astype(y.dtype) @ w_out


class Mamba2(BaseLayer):
    """``out_scale`` divides the initial output projection (the family's
    ``rescale_prenorm_residual``: by the square root of the model's depth)."""

    def __init__(self, hidden_size, num_heads, head_dim, n_groups, state_size,
                 conv_kernel=4, chunk=128, eps=1e-5, dt_min=1e-3, dt_max=0.1,
                 dt_floor=1e-4, out_scale=1.0, name=None):
        name = fresh_name(name or "mamba2")
        assert num_heads % n_groups == 0, (num_heads, n_groups)
        d = num_heads * head_dim
        conv_dim = d + 2 * n_groups * state_size
        self.dims = dict(heads=num_heads, head_dim=head_dim, groups=n_groups,
                         state=state_size, chunk=chunk)
        self.parts = {"z": (0, d), "xbc": (d, d + conv_dim),
                      "dt": (d + conv_dim, d + conv_dim + num_heads)}
        self.groups, self.eps = n_groups, eps
        self.in_proj = VariableOp(
            f"{name}_in_weight", (hidden_size, d + conv_dim + num_heads),
            init.xavier_normal())
        # torch's Conv1d default: uniform within 1 / sqrt(fan_in = kernel)
        bound = 1.0 / np.sqrt(conv_kernel)
        self.conv = VariableOp(f"{name}_conv_weight", (conv_kernel, conv_dim),
                               init.uniform(-bound, bound))
        self.conv_bias = VariableOp(f"{name}_conv_bias", (conv_dim,),
                                    init.uniform(-bound, bound))
        self.dt_bias = VariableOp(f"{name}_dt_bias", (num_heads,),
                                  recurrent.dt_bias(dt_min, dt_max, dt_floor))
        self.a_log = VariableOp(f"{name}_a_log", (num_heads,),
                                recurrent.log_uniform(1.0, 16.0))
        self.d_skip = VariableOp(f"{name}_d", (num_heads,), init.ones())
        self.norm = VariableOp(f"{name}_norm_scale", (d,), init.ones())
        bound = out_scale / np.sqrt(d)      # kaiming_uniform(a=sqrt(5))
        self.out_proj = VariableOp(f"{name}_out_weight", (d, hidden_size),
                                   init.uniform(-bound, bound))

    def __call__(self, x):
        zxbcdt = _Scoped(project, "hetu_ssm_proj", x, self.in_proj)
        lo, hi = self.parts["dt"]
        dt = _Scoped(_part, "hetu_ssm_proj", zxbcdt, lo=lo, hi=hi)
        # the convolution reads its channels in place where it can
        xbc = ConvOp("hetu_ssm_conv", zxbcdt, self.conv, self.conv_bias,
                     window=self.parts["xbc"])
        y = KernelOp(_scan, "hetu_ssm_scan", xbc, dt, self.dt_bias,
                     self.a_log, self.d_skip, kernel="ssd",
                     form=lambda: ssd.chunk_ssd_jnp, **self.dims)
        d = self.parts["z"][1]
        return OutOp(_out, "hetu_ssm_out", y, zxbcdt, self.norm,
                     self.out_proj, window=Window(0, d, d),
                     width=d // self.groups, gate_first=True,
                     groups=self.groups, eps=self.eps)
