"""The short causal convolution in front of a recurrent mixer (Gated
DeltaNet, Mamba-2): depthwise over the sequence, then SiLU."""

from __future__ import annotations


def causal_conv(x, w, b=None):
    """Depthwise causal convolution over the sequence: ``x [B, S, C]``,
    ``w [K, C]``, ``y_t = sum_j w_j x_(t - K + 1 + j)`` (``+ b [C]`` where the
    model has a bias) with zeros before the first position; then SiLU.  ``K``
    shifted products: no im2col, no transposition of the channels."""
    import jax
    import jax.numpy as jnp
    K, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = sum(xp[:, j:j + S].astype(jnp.float32) * w[j].astype(jnp.float32)
            for j in range(K))
    if b is not None:
        y = y + b.astype(jnp.float32)
    return jax.nn.silu(y).astype(x.dtype)
