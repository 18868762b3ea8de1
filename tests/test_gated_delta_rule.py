"""The gated delta rule (``hetu_tpu/ops/gated_delta.py``): the chunked form
against the token-by-token recurrence, forward and gradient, at lengths that
are and are not a multiple of the chunk; the recurrence against the plain
reference's; a bf16 state is seen; the DeltaNet layer's convolution is
causal, in the program and in the reference."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.layers.gated_delta_net import causal_conv
from hetu_tpu.ops.gated_delta import (chunk_gated_delta_rule,
                                      recurrent_gated_delta_rule)

from chipbench.reference import qwen3_next as ref


def delta_inputs(T, seed=0, Bh=(2, 3), dk=16, dv=8):
    r = np.random.default_rng(seed)
    b, h = Bh
    q, k = (r.normal(size=(b, T, h, dk)) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(b, T, h, dv))
    g = -np.exp(r.normal(size=(b, T, h))) * 0.3
    beta = 1 / (1 + np.exp(-r.normal(size=(b, T, h))))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


@pytest.mark.parametrize("T,chunk", [(64, 64), (128, 64), (100, 64), (7, 64),
                                     (40, 16)])
def test_chunked_delta_rule_is_the_recurrence(T, chunk):
    """Outputs, final state and the gradient of every operand, at lengths
    that are and are not a multiple of the chunk."""
    x = delta_inputs(T)
    o1, s1 = recurrent_gated_delta_rule(*x)
    o2, s2 = jax.jit(lambda *a: chunk_gated_delta_rule(*a, chunk=chunk))(*x)
    np.testing.assert_allclose(o2, o1, atol=2e-6)
    np.testing.assert_allclose(s2, s1, atol=5e-6)

    def scalar(fn):
        def f(*a):
            o, s = fn(*a)
            return jnp.sum(jnp.sin(o)) + jnp.sum(s ** 2)
        return jax.grad(f, argnums=range(5))
    want = scalar(recurrent_gated_delta_rule)(*x)
    got = jax.jit(scalar(lambda *a: chunk_gated_delta_rule(
        *a, chunk=chunk)))(*x)
    for g, w in zip(got, want):
        assert np.abs(g - w).max() < 1e-5 * np.abs(w).max()


def test_recurrence_is_the_references():
    """The program's token-by-token form and the reference's are the same
    function (they share no code): outputs and last state."""
    x = delta_inputs(50, seed=3)
    for got, want in zip(recurrent_gated_delta_rule(*x), ref.delta_rule(*x)):
        np.testing.assert_allclose(got, want, atol=1e-6)


def test_a_bf16_state_is_seen():
    x = delta_inputs(128, seed=1)
    exact = recurrent_gated_delta_rule(*x)[0]
    low = recurrent_gated_delta_rule(*x, state_dtype=jnp.bfloat16)[0]
    assert np.abs(np.asarray(low - exact)).max() > 1e-4


@pytest.mark.parametrize("conv", [causal_conv, ref.causal_conv],
                         ids=["program", "reference"])
def test_convolution_is_causal(conv):
    """The output at ``t`` is unchanged by inputs after ``t``, and does
    change with the input at ``t`` and at ``t - 3``."""
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(2, 12, 6)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 6)), jnp.float32)
    y = conv(x, w)
    t = 7
    later = x.at[:, t + 1:].set(r.normal(size=(2, 12 - t - 1, 6)))
    np.testing.assert_array_equal(conv(later, w)[:, :t + 1], y[:, :t + 1])
    for back in (0, 3):
        moved = conv(x.at[:, t - back].add(1.0), w)
        assert np.abs(np.asarray(moved - y))[:, t].min() > 1e-3
    assert np.abs(np.asarray(conv(x.at[:, t - 4].add(1.0), w) - y)[:, t]
                  ).max() == 0.0


def test_program_and_reference_convolve_alike():
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(2, 9, 5)), jnp.float32)
    w = jnp.asarray(r.normal(size=(4, 5)), jnp.float32)
    np.testing.assert_allclose(causal_conv(x, w), ref.causal_conv(x, w),
                               atol=1e-6)
