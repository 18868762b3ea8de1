"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
Hyper-Connections, arXiv:2409.19606; the residual path of Xing4.0): the
residual is ``n`` streams a token, and each sublayer mixes them with three maps
computed from the streams themselves, one of them made doubly stochastic by
Sinkhorn rounds.  A token at a time, the maps in float32::

    v      = vec(X) in R^{nC};   v' = v / sqrt(mean(v^2) + eps)      no weight
    Hpre~  = a_pre  * (v' phi_pre)     + b_pre     in R^{n}
    Hpost~ = a_post * (v' phi_post)    + b_post    in R^{n}
    Hres~  = a_res  * mat(v' phi_res)  + b_res     in R^{n x n}
    Hpre   = sigmoid(Hpre~);   Hpost = 2 sigmoid(Hpost~)
    M_0    = exp(clip(Hres~, clamp));   M_t = rows(cols(M_{t-1})), t = 1..iters
             cols(M) = M / (1^T M + eps),  rows(M) = M / (M 1 + eps);  Hres = M_iters
    u      = Hpre X                        the sublayer's one C-wide input
    X'     = Hres X + Hpost^T F(norm(u))   F's output into every stream, scaled

The streams are carried as ``[B, S, n C]``, stream ``i`` the lanes ``[i C, (i +
1) C)``: the row-major ``[B, S, n, C]``, which is ``vec(X)`` as it lies, so
``v' phi`` is one product and a stream is a slice of whole lane tiles (a
second-minor dimension of ``n = 4`` would be padded to a tile's sublanes).
``v' phi`` is ``(v phi) / rms`` (the norm has no weight): the product runs on
the streams in the compute type with a float32 result, and everything after
it, the Sinkhorn rounds included, is float32.

``phi`` is one ``[n C, 2 n + n^2]`` variable (columns: pre, post, res row by
row), ``b`` one ``[2 n + n^2]`` and ``alpha`` the three gains.  Initial values
(not published; this file's choice): ``phi`` N(0, 0.02), ``alpha`` 0.01,
``b_pre = -ln(n - 1)`` (``Hpre = 1 / n``), ``b_post = 0`` (``Hpost = 1``),
``b_res = 4 I`` (``Hres`` near the identity, and doubly stochastic whatever it
is): while the streams are equal, as ``expand`` makes them, a fresh model is
the plain pre-norm residual ``x + F(norm(x))`` on every stream.

Every op stands under the one scope ``hetu_hc`` (maps, Sinkhorn, both mixes,
forward and backward).  What runs them: on a TPU two Pallas kernel pairs
(``ops/pallas/hyper_connection.py``, PR 57: ``hetu_hc_pre_fwd`` / ``_bwd``
before the sublayer's function, the maps, ``u`` and ``R = Hres X`` from one
read of the streams; ``hetu_hc_mix_fwd`` / ``_bwd`` behind it, ``X' = R +
Hpost^T y``), where ``dispatch.take("hc_mix", ..)`` and the kernels'
``unsupported`` say so (a stream of whole lane tiles, bf16 or f32, no mesh);
everywhere else the ``jax.numpy`` functions of this file (``_maps``, ``_pre``,
``_mix``), which are also what the tests hold the kernels to.  Each choice is
counted at trace time in ``hetu_kernel_choice_total{kernel="hc_mix"}``;
``hetu_hc_entry_total{path}`` counts the sublayers built by what the layer's
own arguments and the platform say will run the mixes (``pallas`` / ``xla``).
The readers' bytes due are stated once a sublayer application whatever
implements it (``chipbench/flops_xing4.py``).
"""

from __future__ import annotations

import math

from .base import BaseLayer, fresh_name
from .. import initializers as init, telemetry
from ..graph.node import VariableOp
from ..ops.base import ScopedOp as _Scoped
from ..ops.pallas import dispatch, hyper_connection as kernels

_SCOPE = "hetu_hc"


def _stream(x, i, n):
    c = x.shape[-1] // n
    return x[..., i * c:(i + 1) * c]


def _expand(x, *, n):
    """``[B, S, C]`` -> ``[B, S, n C]``: every stream the embedding."""
    import jax.numpy as jnp
    return jnp.concatenate([x] * n, -1)


def _collapse(x, *, n):
    """``[B, S, n C]`` -> ``[B, S, C]``: the streams' sum, in f32."""
    import jax.numpy as jnp
    return sum(_stream(x, i, n).astype(jnp.float32)
               for i in range(n)).astype(x.dtype)


def sinkhorn(logits, iters, eps, clamp):
    """``[..., n, n]`` f32 logits -> the matrix after ``iters`` rounds of
    columns then rows."""
    import jax.numpy as jnp
    m = jnp.exp(jnp.clip(logits, clamp[0], clamp[1]))
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + eps)
        m = m / (m.sum(-1, keepdims=True) + eps)
    return m


def _maps(x, phi, b, alpha, *, n, iters, eps, clamp):
    """``[B, S, 2 n + n^2]`` f32: ``Hpre``, ``Hpost`` and ``Hres`` row by row,
    each final."""
    import jax
    import jax.numpy as jnp
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    z = jnp.matmul(x, phi, preferred_element_type=jnp.float32) * inv
    a, b = alpha.astype(jnp.float32), b.astype(jnp.float32)
    pre = jax.nn.sigmoid(a[0] * z[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[..., n:2 * n] + b[n:2 * n])
    res = sinkhorn((a[2] * z[..., 2 * n:] + b[2 * n:]).reshape(
        z.shape[:-1] + (n, n)), iters, eps, clamp)
    return jnp.concatenate(
        [pre, post, res.reshape(z.shape[:-1] + (n * n,))], -1)


def _pre(x, maps, *, n):
    """``u = Hpre X``: ``[B, S, C]`` in the streams' type."""
    import jax.numpy as jnp
    return sum(maps[..., i:i + 1] * _stream(x, i, n).astype(jnp.float32)
               for i in range(n)).astype(x.dtype)


def _mix(x, maps, y, *, n):
    """``X' = Hres X + Hpost^T y``: ``[B, S, n C]`` in the streams' type."""
    import jax.numpy as jnp
    xs = [_stream(x, j, n).astype(jnp.float32) for j in range(n)]
    yf = y.astype(jnp.float32)
    return jnp.concatenate(
        [(sum(maps[..., 2 * n + i * n + j, None] * xs[j] for j in range(n))
          + maps[..., n + i, None] * yf).astype(x.dtype)
         for i in range(n)], -1)


def _res(maps, *, n):
    """``Hres [B, S, n, n]`` out of the maps (a comparison fetches it)."""
    return maps[..., 2 * n:2 * n + n * n].reshape(maps.shape[:-1] + (n, n))


class _Before(_Scoped):
    """``(x, phi, b, alpha) -> (u, maps, R)``: what stands before the
    sublayer's function.  Through the kernels ``maps`` is as wide as they keep
    it (the layer's maps its first lanes) and ``R = Hres X`` is what
    ``_Behind`` adds to; in the ``jax.numpy`` form ``R`` is None and
    ``_Behind`` mixes ``x`` itself."""

    def _compute(self, input_vals, ctx):
        x, phi, b, alpha = input_vals
        if dispatch.take("hc_mix", ctx.mesh,
                         kernels.unsupported(x, self.attrs["n"])):
            return kernels.pre(x, phi, b, alpha, **self.attrs)
        maps = _maps(x, phi, b, alpha, **self.attrs)
        return _pre(x, maps, n=self.attrs["n"]), maps, None


def _item(before, *, index):
    return before[index]


def _behind(before, x, y, *, n):
    """``X'`` from what ``_Before`` left, the streams and the function's
    output."""
    _, maps, r = before
    if r is None:
        return _mix(x, maps, y, n=n)
    return kernels.mix(r, maps, y, n=n)


def _initial_bias(n):
    """``b`` at the start: ``Hpre = 1 / n``, ``Hpost = 1``, ``Hres~ = 4 I``."""
    import numpy as np
    pre = np.full((n,), -math.log(n - 1) if n > 1 else 30.0)
    return init.NumpyInit(np.concatenate(
        [pre, np.zeros((n,)), 4.0 * np.eye(n).reshape(-1)]).astype(np.float32))


def expand(x, n):
    """The streams the first layer reads: ``n`` copies of ``x [B, S, C]``."""
    return _Scoped(_expand, _SCOPE, x, n=n)


def collapse(x, n):
    """What the final norm reads: the streams' sum."""
    return _Scoped(_collapse, _SCOPE, x, n=n)


class HyperConnection(BaseLayer):
    """The maps of ONE sublayer.  ``sublayer(X, norm, f)`` is the
    hyper-connected form of ``models/llama.py residual_sublayer``; ``hres`` is
    the ``[B, S, n, n]`` node of the last call's ``Hres``."""

    def __init__(self, hidden_size, n=4, iters=20, eps=1e-6,
                 clamp=(-30.0, 30.0), name=None):
        name = fresh_name(name or "hc")
        self.n, self.iters, self.eps = n, iters, eps
        self.hidden_size = hidden_size
        self.clamp = (float(clamp[0]), float(clamp[1]))
        width = 2 * n + n * n
        self.phi = VariableOp(f"{name}_phi", (n * hidden_size, width),
                              init.normal(0.0, 0.02))
        self.b = VariableOp(f"{name}_b", (width,), _initial_bias(n))
        self.alpha = VariableOp(f"{name}_alpha", (3,), init.constant(0.01))
        self.hres = None

    def expand(self, x):
        return expand(x, self.n)

    def collapse(self, x):
        return collapse(x, self.n)

    def path(self):
        """What will run a sublayer's mixes, as far as the platform and the
        layer's own arguments say (the streams' type and a mesh are the
        trace's to see: ``hetu_kernel_choice_total`` has those)."""
        import jax
        import jax.numpy as jnp
        streams = jax.ShapeDtypeStruct((self.n * self.hidden_size,),
                                       jnp.bfloat16)
        took = dispatch.mosaic() and kernels.unsupported(
            streams, self.n) is None
        return "pallas" if took else "xla"

    def sublayer(self, x, norm, f):
        from ..graph.node import scope
        telemetry.get_registry().counter(
            "hetu_hc_entry_total",
            "Hyper-connected sublayers built, by what runs the two mixes "
            "(xla: the jax.numpy form; pallas: a kernel)", labels=("path",),
        ).labels(path=self.path()).inc()
        before = _Before(None, _SCOPE, x, self.phi, self.b, self.alpha,
                         n=self.n, iters=self.iters, eps=self.eps,
                         clamp=self.clamp)
        self.hres = _Scoped(_res, _SCOPE, _Scoped(_item, _SCOPE, before,
                                                  index=1), n=self.n)
        with scope("hetu_norm"):
            h = norm(_Scoped(_item, _SCOPE, before, index=0))
        return _Scoped(_behind, _SCOPE, before, x, f(h), n=self.n)
