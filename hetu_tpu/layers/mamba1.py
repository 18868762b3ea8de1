"""Mamba-1 mixer and the Gated Memory Unit that reads its scan: the
state-space layers of the SambaY decoders (Phi-4-mini-flash-reasoning,
arXiv:2507.06607; Mamba, arXiv:2312.00752).  ``d`` inner channels, ``N``
states a channel, ``R`` the rank of the step's projection, ``K`` taps:

    [x | z] = u W_in                                  widths d, d; no bias
    xc = silu(causal_conv(x, w [K, d]) + b)           (ops/causal_conv.py)
    [r | B | C] = xc W_x                              widths R, N, N; no bias
    delta = softplus(r W_dt + b_dt)  [.., d];  A = -exp(A_log)  [d, N]   (f32)
    h_t = exp(delta_t A) h_(t-1) + (delta_t xc_t) B_t;  y_t = h_t C_t + D xc_t
                                                      (ops/selective_scan.py)
    out = (y silu(z)) W_out

``hand_out_scan=True`` keeps ``y`` (the scan's output with its skip, BEFORE the
gate) as ``self.memory`` after a call: the value a ``GatedMemoryUnit`` of a
later layer reads,

    g = u W_g;   out = (memory * silu(g)) W_o          widths d, hidden

which has no convolution and no scan of its own.

Graph nodes, each under the block the device trace's readers find:
``hetu_ssm_proj`` (the three projections), ``hetu_ssm_conv`` (``ConvOp``, which
reads ``x`` out of the projection's output itself: on a TPU the Pallas kernels
``hetu_conv_fwd`` / ``hetu_conv_bwd``), ``hetu_ssm_scan`` (the softplus,
``-exp(A_log)``, the scan and the skip: on a TPU the Pallas kernels
``hetu_s6_fwd`` / ``hetu_s6_bwd`` where their rule takes the operands, under a
mesh and on any other platform the chunked ``jax.numpy`` form; the node is an
``ops/base.py KernelOp``), ``hetu_ssm_out`` (the gate and the output
projection) and ``hetu_gmu`` (the whole unit).  How many layers read a handed
out value is counted where a model wires them
(``hetu_shared_value_readers{value}``, ``models/phi4flash.py``).  A decode step
and the state ``[d, N]`` with the convolution's last ``K - 1`` inputs in a serving
cache are not here (ROADMAP Queue 2).
"""

from __future__ import annotations

import numpy as np

from . import recurrent
from .base import BaseLayer, fresh_name, project
from .. import initializers as init
from ..graph.node import VariableOp
from ..ops import selective_scan as s6
from ..ops.base import KernelOp, ScopedOp
from ..ops.causal_conv import ConvOp


def _step_proj(dbc, w_dt, *, rank):
    return dbc[..., :rank] @ w_dt


def _scan(xc, dt, dbc, dt_bias, a_log, d_skip, *, rank, state, rule=None):
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    Bm = dbc[..., rank:rank + state]
    Cm = dbc[..., rank + state:rank + 2 * state]
    y = (rule or s6.selective_scan)(xc, delta, -jnp.exp(a_log.astype(f32)),
                                    Bm, Cm)
    y = y.astype(f32) + d_skip.astype(f32) * xc.astype(f32)
    return y.astype(xc.dtype)


def _gated(y, g, lo=0):
    """``y silu(g[..., lo:lo + width of y])`` in f32, one rounding."""
    import jax
    import jax.numpy as jnp
    g = g[..., lo:lo + y.shape[-1]].astype(jnp.float32)
    return (y.astype(jnp.float32) * jax.nn.silu(g)).astype(y.dtype)


def _out(y, xz, w_out, *, lo):
    return _gated(y, xz, lo) @ w_out


def _gmu(u, memory, w_g, w_o):
    return _gated(memory, u @ w_g) @ w_o


class Mamba1(BaseLayer):
    def __init__(self, hidden_size, expand=2, state_size=16, dt_rank=None,
                 conv_kernel=4, dt_min=1e-3, dt_max=0.1, dt_floor=1e-4,
                 hand_out_scan=False, name=None):
        name = fresh_name(name or "mamba1")
        d = expand * hidden_size
        rank = dt_rank or -(-hidden_size // 16)
        self.inner, self.rank, self.state = d, rank, state_size
        self.hand_out_scan = hand_out_scan
        #: the scan's output with its skip, before the gate, of the last call
        self.memory = None
        normal = init.normal(0.0, 0.02)
        self.in_proj = VariableOp(f"{name}_in_weight", (hidden_size, 2 * d),
                                  normal)
        # torch's Conv1d default: uniform within 1 / sqrt(fan_in = kernel)
        bound = 1.0 / np.sqrt(conv_kernel)
        self.conv = VariableOp(f"{name}_conv_weight", (conv_kernel, d),
                               init.uniform(-bound, bound))
        self.conv_bias = VariableOp(f"{name}_conv_bias", (d,),
                                    init.uniform(-bound, bound))
        self.x_proj = VariableOp(f"{name}_x_weight",
                                 (d, rank + 2 * state_size), normal)
        bound = rank ** -0.5                # Mamba-1's dt_init "random"
        self.dt_proj = VariableOp(f"{name}_dt_weight", (rank, d),
                                  init.uniform(-bound, bound))
        self.dt_bias = VariableOp(f"{name}_dt_bias", (d,),
                                  recurrent.dt_bias(dt_min, dt_max, dt_floor))
        self.a_log = VariableOp(f"{name}_a_log", (d, state_size),
                                recurrent.log_arange)
        self.d_skip = VariableOp(f"{name}_d", (d,), init.ones())
        self.out_proj = VariableOp(f"{name}_out_weight", (d, hidden_size),
                                   normal)

    def __call__(self, u):
        d = self.inner
        xz = ScopedOp(project, "hetu_ssm_proj", u, self.in_proj)
        # the convolution reads its channels in place where it can
        xc = ConvOp("hetu_ssm_conv", xz, self.conv, self.conv_bias,
                    window=(0, d))
        dbc = ScopedOp(project, "hetu_ssm_proj", xc, self.x_proj)
        dt = ScopedOp(_step_proj, "hetu_ssm_proj", dbc, self.dt_proj,
                      rank=self.rank)
        y = KernelOp(_scan, "hetu_ssm_scan", xc, dt, dbc, self.dt_bias,
                     self.a_log, self.d_skip, kernel="selective_scan",
                     form=lambda: s6.selective_scan_xla, rank=self.rank,
                     state=self.state)
        if self.hand_out_scan:
            self.memory = y
        return ScopedOp(_out, "hetu_ssm_out", y, xz, self.out_proj, lo=d)


class GatedMemoryUnit(BaseLayer):
    """``(memory * silu(u W_g)) W_o``; called ``(u, memory)``."""

    def __init__(self, hidden_size, memory_size, name=None):
        name = fresh_name(name or "gmu")
        normal = init.normal(0.0, 0.02)
        self.in_proj = VariableOp(f"{name}_in_weight",
                                  (hidden_size, memory_size), normal)
        self.out_proj = VariableOp(f"{name}_out_weight",
                                   (memory_size, hidden_size), normal)

    def __call__(self, u, memory):
        return ScopedOp(_gmu, "hetu_gmu", u, memory, self.in_proj,
                        self.out_proj)
