"""The initialisers the recurrent mixers (``gated_delta_net.py``,
``mamba2.py``, ``mamba1.py``, ``kda.py``) share, of their decays and steps,
and the delta rules' L2 norm a head (``unit_heads``).  Their nodes are shared
too: a projection is ``ScopedOp(project, ..)`` (``base.py``), the scan
``ops/base.py KernelOp``, the convolution ``ops/causal_conv.py ConvOp``, the
scalar mixers' output ``ops/gated_norm.py OutOp``; the data flow between the
nodes is each layer's own."""

from __future__ import annotations

import numpy as np


def unit_heads(t, heads):
    """``t [B, S, heads d] -> [B, S, heads, d]`` f32, each head over its norm
    (1e-6 under the root): q and k of the delta rules' ``jax.numpy`` prologue,
    where their kernels do not take the convolution's output in place
    (``ops/pallas/common.py unit`` is this on a chunk in VMEM)."""
    import jax
    import jax.numpy as jnp
    t = t.reshape(t.shape[:2] + (heads, -1)).astype(jnp.float32)
    return t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)


def log_uniform(lo, hi):
    """``log U(lo, hi)``: an initial ``A_log``."""
    def draw(key, shape, dtype=np.float32):
        import jax
        import jax.numpy as jnp
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, lo, hi)
                       ).astype(dtype)
    return draw


def log_arange(key, shape, dtype=np.float32):
    """``log(1 .. N)`` along the last axis, the same a row: Mamba-1's initial
    ``A_log [channels, N]`` (S4D-real)."""
    import jax.numpy as jnp
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1,
                                               dtype=jnp.float32)),
                            shape).astype(dtype)


def dt_bias(dt_min, dt_max, floor):
    """The inverse softplus of a log-uniform draw in ``[dt_min, dt_max]``
    floored at ``floor``: the softplus of the initial bias is the step."""
    def draw(key, shape, dtype=np.float32):
        import jax
        import jax.numpy as jnp
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.exp(u * (np.log(dt_max) - np.log(dt_min)) + np.log(dt_min))
        dt = jnp.maximum(dt, floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return draw
