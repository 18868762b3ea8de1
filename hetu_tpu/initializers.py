"""Variable initializers (reference: /root/reference/python/hetu/initializers.py).

Each initializer is a callable ``(key, shape, dtype) -> jax.Array``; Variables
hold one and the executor materializes values at construction time.  The
reference's curand kernels (src/ops/Initializers.cu) become jax.random calls;
``init_on_ps`` (PS-side init) has its TPU equivalent in ps/ (host store init).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


class Initializer:
    def __call__(self, key, shape, dtype=jnp.float32):
        raise NotImplementedError


class ConstantInit(Initializer):
    def __init__(self, constant=0.0):
        self.constant = constant

    def __call__(self, key, shape, dtype=jnp.float32):
        return jnp.full(shape, self.constant, dtype=dtype)


class ZerosInit(ConstantInit):
    def __init__(self):
        super().__init__(0.0)


class OnesInit(ConstantInit):
    def __init__(self):
        super().__init__(1.0)


class UniformInit(Initializer):
    def __init__(self, low=-1.0, high=1.0):
        self.low, self.high = low, high

    def __call__(self, key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype=dtype,
                                  minval=self.low, maxval=self.high)


class NormalInit(Initializer):
    def __init__(self, mean=0.0, stddev=1.0):
        self.mean, self.stddev = mean, stddev

    def __call__(self, key, shape, dtype=jnp.float32):
        return self.mean + self.stddev * jax.random.normal(key, shape, dtype=dtype)


class TruncatedNormalInit(Initializer):
    """Drawn again outside ``cutoff`` deviations of the mean."""

    def __init__(self, mean=0.0, stddev=1.0, cutoff=2.0):
        self.mean, self.stddev, self.cutoff = mean, stddev, cutoff

    def __call__(self, key, shape, dtype=jnp.float32):
        return self.mean + self.stddev * jax.random.truncated_normal(
            key, -self.cutoff, self.cutoff, shape, dtype=dtype)


def _fans(shape):
    if len(shape) == 0:
        return 1, 1
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    # conv kernels (O, I, H, W) layout
    receptive = int(np.prod(shape[2:]))
    return shape[1] * receptive, shape[0] * receptive


class XavierNormalInit(Initializer):
    def __init__(self, gain=1.0):
        self.gain = gain

    def __call__(self, key, shape, dtype=jnp.float32):
        fan_in, fan_out = _fans(shape)
        std = self.gain * math.sqrt(2.0 / (fan_in + fan_out))
        return std * jax.random.normal(key, shape, dtype=dtype)


class XavierUniformInit(Initializer):
    def __init__(self, gain=1.0):
        self.gain = gain

    def __call__(self, key, shape, dtype=jnp.float32):
        fan_in, fan_out = _fans(shape)
        limit = self.gain * math.sqrt(6.0 / (fan_in + fan_out))
        return jax.random.uniform(key, shape, dtype=dtype,
                                  minval=-limit, maxval=limit)


class HeNormalInit(Initializer):
    def __call__(self, key, shape, dtype=jnp.float32):
        fan_in, _ = _fans(shape)
        return math.sqrt(2.0 / fan_in) * jax.random.normal(key, shape, dtype=dtype)


class HeUniformInit(Initializer):
    def __call__(self, key, shape, dtype=jnp.float32):
        fan_in, _ = _fans(shape)
        limit = math.sqrt(6.0 / fan_in)
        return jax.random.uniform(key, shape, dtype=dtype,
                                  minval=-limit, maxval=limit)


class LecunNormalInit(Initializer):
    def __call__(self, key, shape, dtype=jnp.float32):
        fan_in, _ = _fans(shape)
        return math.sqrt(1.0 / fan_in) * jax.random.normal(key, shape, dtype=dtype)


class NumpyInit(Initializer):
    """Wraps a concrete numpy array (reference: provided-value Variables)."""

    def __init__(self, value):
        self.value = np.asarray(value)

    def __call__(self, key, shape, dtype=jnp.float32):
        assert tuple(shape) == tuple(self.value.shape), \
            f"shape mismatch {shape} vs {self.value.shape}"
        return jnp.asarray(self.value, dtype=dtype)


# functional aliases matching the reference's API names
def zeros(): return ZerosInit()
def ones(): return OnesInit()
def constant(c=0.0): return ConstantInit(c)
def uniform(low=-1.0, high=1.0): return UniformInit(low, high)
def normal(mean=0.0, stddev=1.0): return NormalInit(mean, stddev)
def truncated_normal(mean=0.0, stddev=1.0, cutoff=2.0): return TruncatedNormalInit(mean, stddev, cutoff)
def xavier_normal(gain=1.0): return XavierNormalInit(gain)
def xavier_uniform(gain=1.0): return XavierUniformInit(gain)
def he_normal(): return HeNormalInit()
def he_uniform(): return HeUniformInit()
def lecun_normal(): return LecunNormalInit()
