"""Manifold-constrained hyper-connections (``layers/hyper_connection.py``)
against a twenty-line ``jax.numpy`` copy of the equations, forward and
``jax.grad``; ``Hres`` is doubly stochastic after 20 Sinkhorn rounds and not
after 2; one stream with fixed maps is the plain pre-norm residual; the clamp
holds at logits of +-100; a fresh layer on equal streams is the plain
residual too."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.layers import RMSNorm
from hetu_tpu.layers.hyper_connection import (HyperConnection, collapse,
                                              expand, sinkhorn)
from hetu_tpu.models.llama import LlamaMLP, residual_sublayer

B, S, C, N, ITERS, EPS = 2, 12, 16, 4, 20, 1e-6
CLAMP = (-30.0, 30.0)


def plain(X, phi, b, alpha, f, iters=ITERS, clamp=CLAMP):
    """The equations, a token at a time over ``X [B, S, n, C]``: ``(X',
    Hres)``."""
    n = X.shape[2]
    v = X.reshape(B, S, -1)
    v = v / jnp.sqrt(jnp.mean(v * v, -1, keepdims=True) + EPS)
    z = v @ phi
    pre = jax.nn.sigmoid(alpha[0] * z[..., :n] + b[:n])
    post = 2 * jax.nn.sigmoid(alpha[1] * z[..., n:2 * n] + b[n:2 * n])
    m = jnp.exp(jnp.clip((alpha[2] * z[..., 2 * n:] + b[2 * n:]).reshape(
        B, S, n, n), *clamp))
    for _ in range(iters):
        m = m / (m.sum(-2, keepdims=True) + EPS)       # 1^T M: columns
        m = m / (m.sum(-1, keepdims=True) + EPS)       # M 1: rows
    u = jnp.einsum("bsn,bsnc->bsc", pre, X)
    return (jnp.einsum("bsij,bsjc->bsic", m, X)
            + post[..., None] * f(u)[:, :, None, :]), m


def draws(n=N, seed=0):
    r = np.random.default_rng(seed)
    return (r.normal(0, (n * C) ** -0.5, (n * C, 2 * n + n * n)),
            r.normal(0, 0.5, 2 * n + n * n), r.uniform(0.5, 1.0, 3),
            r.normal(0, 1, (B, S, n, C)),
            r.normal(0, 0.3, (C, C)), r.normal(1, 0.2, (C,)))


def program(name, n=N, iters=ITERS):
    """One hyper-connected sublayer around ``N(u; w_n) W``: its executor,
    the placeholder of ``X [B, S, n C]`` and the variables by role."""
    hc = HyperConnection(C, n, iters, EPS, CLAMP, name=f"{name}_hc")
    norm = RMSNorm(C, eps=EPS, name=f"{name}_norm")
    w = ht.Variable(f"{name}_w", shape=(C, C),
                    initializer=ht.init.normal(0.0, 0.1))
    x = ht.placeholder_op(f"{name}_x", (B, S, n * C))
    y = hc.sublayer(x, norm, lambda h: ht.matmul_op(h, w))
    loss = ht.reduce_sum_op(y * y, axes=None)
    variables = [hc.phi, hc.b, hc.alpha, norm.scale, w]
    ex = ht.Executor({"forward": [y, hc.hres],
                      "grads": [loss] + ht.gradients(loss, variables)},
                     seed=0)
    return ex, x, variables


def loaded(name, n=N, iters=ITERS, seed=0, b_res=None):
    ex, x, variables = program(name, n, iters)
    phi, b, alpha, X, w, scale = draws(n, seed)
    if b_res is not None:
        b[2 * n:] = b_res
    for var, value in zip(variables, (phi, b, alpha, scale, w)):
        ex.params[var.name] = jnp.asarray(value, jnp.float32)
    return ex, x, variables, (phi, b, alpha, X, w, scale)


def plain_f(w, scale):
    return lambda u: (u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True) + EPS)
                      * scale) @ w


def test_the_sublayer_is_the_equations_forward_and_backward():
    ex, x, variables, (phi, b, alpha, X, w, scale) = loaded("hc_eq")
    feed = {x: X.reshape(B, S, -1).astype(np.float32)}
    got, hres = ex.run("forward", feed_dict=feed,
                       convert_to_numpy_ret_vals=True)
    with jax.default_matmul_precision("highest"):
        want, m = plain(jnp.asarray(X), phi, b, alpha, plain_f(w, scale))
        grads = jax.grad(lambda p: jnp.sum(plain(
            jnp.asarray(X), p[0], p[1], p[2], plain_f(p[4], p[3]))[0] ** 2))(
                [jnp.asarray(t) for t in (phi, b, alpha, scale, w)])
    assert np.abs(got.reshape(B, S, N, C) - np.asarray(want)).max() < 2e-5
    assert np.abs(hres - np.asarray(m)).max() < 1e-6
    mine = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)[1:]
    for var, g, wnt in zip(variables, mine, grads):
        wnt = np.asarray(wnt)
        assert np.abs(wnt).max() > 0, var.name
        assert np.abs(g - wnt).max() < 2e-4 * np.abs(wnt).max(), var.name


@pytest.mark.parametrize("iters,stochastic", [(20, True), (2, False)])
def test_hres_is_doubly_stochastic_after_twenty_rounds_not_after_two(
        iters, stochastic):
    logits = jnp.asarray(
        np.random.default_rng(3).normal(0, 0.75, (256, N, N)), jnp.float32)
    m = np.asarray(sinkhorn(logits, iters, EPS, CLAMP), np.float64)
    off = max(np.abs(m.sum(-1) - 1).max(), np.abs(m.sum(-2) - 1).max())
    assert (off < 1e-4) == stochastic, off
    assert m.min() > 0


def test_the_layers_hres_node_is_the_sinkhorn_of_its_logits():
    ex, x, _, (_, _, _, X, _, _) = loaded("hc_node")
    _, hres = ex.run("forward", feed_dict={x: X.reshape(B, S, -1)},
                     convert_to_numpy_ret_vals=True)
    assert hres.shape == (B, S, N, N)
    assert np.abs(hres.sum(-1) - 1).max() < 1e-4
    assert np.abs(hres.sum(-2) - 1).max() < 1e-4
    assert hres.std() > 0.05          # no map near a constant


def test_one_stream_with_fixed_maps_is_the_residual_sublayer():
    """``n = 1``, ``phi = 0``, ``b = (30, 0, 0)``: ``Hpre = sigmoid(30)``,
    ``Hpost = 1``, ``Hres = 1``: ``x + F(N(x))`` to f32's rounding of
    ``sigmoid(30)``."""
    hc = HyperConnection(C, 1, ITERS, EPS, CLAMP, name="hc_one")
    norm = RMSNorm(C, eps=EPS, name="hc_one_norm")
    mlp = LlamaMLP(C, 2 * C, name="hc_one_mlp")
    x = ht.placeholder_op("hc_one_x", (B, S, C))
    ex = ht.Executor([hc.sublayer(x, norm, mlp),
                      residual_sublayer(x, norm, mlp)], seed=4)
    ex.params[hc.phi.name] = jnp.zeros(hc.phi.shape, jnp.float32)
    ex.params[hc.b.name] = jnp.asarray([30.0, 0.0, 0.0], jnp.float32)
    xv = np.random.default_rng(4).normal(0, 1, (B, S, C)).astype(np.float32)
    got, want = ex.run(feed_dict={x: xv}, convert_to_numpy_ret_vals=True)
    assert np.abs(want - xv).max() > 1e-3
    assert np.abs(got - want).max() < 1e-5


@pytest.mark.parametrize("at", [100.0, -100.0])
def test_the_clamp_holds_at_logits_of_a_hundred(at):
    """One logit at +-100: with the clamp ``exp`` stays finite (``exp(100)``
    is not, in f32), the matrix is the clamped logits' and the output and
    every gradient are finite."""
    b_res = np.zeros(N * N)
    b_res[5] = at
    ex, x, variables, (phi, b, alpha, X, w, scale) = loaded(
        f"hc_clamp_{int(at > 0)}", b_res=b_res)
    ex.params[variables[0].name] = jnp.zeros(phi.shape, jnp.float32)
    feed = {x: X.reshape(B, S, -1)}
    got, hres = ex.run("forward", feed_dict=feed,
                       convert_to_numpy_ret_vals=True)
    assert np.isfinite(got).all() and np.isfinite(hres).all()
    clamped = np.clip(b_res, *CLAMP).reshape(N, N)
    want = np.asarray(sinkhorn(jnp.asarray(clamped, jnp.float32), ITERS, EPS,
                               (-1e9, 1e9)))
    assert np.abs(hres - want).max() < 1e-6
    unclamped = np.asarray(sinkhorn(jnp.asarray(b_res.reshape(N, N),
                                                jnp.float32), ITERS, EPS,
                                    (-1e9, 1e9)))
    assert (at > 0) == (not np.isfinite(unclamped).all())
    grads = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert all(np.isfinite(g).all() for g in grads)


def test_a_fresh_layer_on_equal_streams_is_the_plain_residual():
    """At its initial values, on the streams ``expand`` makes: every stream
    of ``X'`` is ``x + F(N(x))`` and their sum ``n`` times it."""
    hc = HyperConnection(C, N, ITERS, EPS, CLAMP, name="hc_fresh")
    norm = RMSNorm(C, eps=EPS, name="hc_fresh_norm")
    mlp = LlamaMLP(C, 2 * C, name="hc_fresh_mlp")
    x = ht.placeholder_op("hc_fresh_x", (B, S, C))
    streams = hc.sublayer(expand(x, N), norm, mlp)
    ex = ht.Executor([streams, collapse(streams, N),
                      residual_sublayer(x, norm, mlp)], seed=5)
    ex.params[hc.phi.name] = jnp.zeros(hc.phi.shape, jnp.float32)
    xv = np.random.default_rng(5).normal(0, 1, (B, S, C)).astype(np.float32)
    got, total, want = ex.run(feed_dict={x: xv},
                              convert_to_numpy_ret_vals=True)
    assert got.shape == (B, S, N * C)
    for i in range(N):
        assert np.abs(got[..., i * C:(i + 1) * C] - want).max() < 1e-5
    assert np.abs(total - N * want).max() < 1e-4
    assert len(graph_variables([streams], trainable_only=True)) == 3 + 1 + 3


def test_the_entry_counter_counts_a_sublayer_built():
    from hetu_tpu import telemetry
    from hetu_tpu.ops.pallas import dispatch
    telemetry.enable()
    try:
        before = dict((lab["path"], n) for lab, n in dispatch.counted(
            "hetu_hc_entry_total"))
        program("hc_count")
        after = dict((lab["path"], n) for lab, n in dispatch.counted(
            "hetu_hc_entry_total"))
        assert after["xla"] == before.get("xla", 0) + 1
        assert "hetu_hc" in ht.scopes()
    finally:
        telemetry.disable()
