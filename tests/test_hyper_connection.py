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
from hetu_tpu.layers import RMSNorm, hyper_connection as forms
from hetu_tpu.layers.hyper_connection import (HyperConnection, collapse,
                                              expand, sinkhorn)
from hetu_tpu.models.llama import LlamaMLP, residual_sublayer
from hetu_tpu.ops.pallas import dispatch, hyper_connection as kernels

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


# -- the Pallas kernel pairs (``ops/pallas/hyper_connection.py``) ------------------
# In interpret mode on the CPU, against ``_maps`` / ``_pre`` / ``_mix``: what
# Mosaic makes of them is compiled in ``tests/test_flash_attention.py``.

#: fewer rounds than the layer's 20 where a case does not need them: a round
#: is 80 traced operations and their transposes, in interpret mode
FEW = 4


def operands(n, c, tokens, dtype, seed=0, b_res=None):
    """``x [2, tokens / 2, n c]``, ``phi``, ``b``, ``alpha``, ``y``."""
    r = np.random.default_rng(seed)
    k = 2 * n + n * n
    b = r.normal(0, 0.5, k)
    if b_res is not None:
        b[2 * n:] = b_res
    return (jnp.asarray(r.normal(0, 1, (2, tokens // 2, n * c)), dtype),
            jnp.asarray(r.normal(0, (n * c) ** -0.5, (n * c, k)), dtype),
            jnp.asarray(b, jnp.float32),
            jnp.asarray(r.uniform(0.5, 1.0, 3), jnp.float32),
            jnp.asarray(r.normal(0, 1, (2, tokens // 2, c)), dtype))


def by_forms(x, phi, b, alpha, y, n, iters):
    maps = forms._maps(x, phi, b, alpha, n=n, iters=iters, eps=EPS,
                       clamp=CLAMP)
    u = forms._pre(x, maps, n=n)
    return u, maps, forms._mix(x, maps, y + u, n=n)


def by_kernels(x, phi, b, alpha, y, n, iters):
    u, maps, r = kernels.pre(x, phi, b, alpha, n=n, iters=iters, eps=EPS,
                             clamp=CLAMP)
    return u, maps[..., :2 * n + n * n], kernels.mix(r, maps, y + u, n=n)


def values_and_cotangents(f, args, n, iters, seed=1):
    """``f``'s three outputs and the gradient, by every operand, of their
    inner product with fixed draws."""
    r = np.random.default_rng(seed)
    shapes = jax.eval_shape(lambda *a: f(*a, n, iters), *args)
    draws = [jnp.asarray(r.normal(0, 1, s.shape), jnp.float32) for s in shapes]

    def loss(*a):
        return sum(jnp.sum(o.astype(jnp.float32) * d)
                   for o, d in zip(f(*a, n, iters), draws))
    with jax.default_matmul_precision("highest"):
        return (f(*args, n, iters),
                jax.grad(loss, argnums=tuple(range(5)))(*args))


def gap(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


@pytest.mark.parametrize("n,c,tokens,dtype,iters,tol", [
    (4, 128, 200, "float32", ITERS, 2e-5),  # a tile and 72 tokens of the next
    (1, 256, 128, "float32", ITERS, 2e-5),  # one stream of two lane tiles
    (2, 128, 24, "float32", ITERS, 2e-5),   # fewer tokens than a tile
    (4, 256, 256, "bfloat16", FEW, 2e-2),   # the cell's type, two tiles
])
def test_the_kernel_pairs_are_the_jnp_forms(n, c, tokens, dtype, iters, tol):
    """``u``, the maps and ``X'``, and ``dX``, ``dphi``, ``db``, ``dalpha``,
    ``dy``: the kernels against the layer's own functions."""
    args = operands(n, c, tokens, jnp.dtype(dtype))
    want, dwant = values_and_cotangents(by_forms, args, n, iters)
    got, dgot = values_and_cotangents(by_kernels, args, n, iters)
    for name, g, w in zip(("u", "maps", "out"), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert gap(g, w) < tol, (name, gap(g, w))
    for name, g, w in zip(("dx", "dphi", "db", "dalpha", "dy"), dgot, dwant):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert np.abs(np.asarray(w, np.float32)).max() > 0, name
        assert gap(g, w) < tol, (name, gap(g, w))


@pytest.mark.parametrize("at", [100.0, -100.0])
def test_the_kernels_clamp_holds_at_logits_of_a_hundred(at):
    """One logit of ``Hres`` at +-100 and ``phi = 0``: the kernels' maps are
    the clamped logits' Sinkhorn, and every value and cotangent is finite and
    the forms'."""
    b_res = np.zeros(N * N)
    b_res[5] = at
    x, phi, b, alpha, y = operands(N, 128, 128, jnp.float32, b_res=b_res)
    args = (x, jnp.zeros_like(phi), b, alpha, y)
    want, dwant = values_and_cotangents(by_forms, args, N, FEW)
    got, dgot = values_and_cotangents(by_kernels, args, N, FEW)
    clamped = jnp.asarray(np.clip(b_res, *CLAMP).reshape(N, N), jnp.float32)
    hres = np.asarray(got[1])[0, 0, 2 * N:].reshape(N, N)
    assert np.abs(hres - np.asarray(sinkhorn(clamped, FEW, EPS,
                                             (-1e9, 1e9)))).max() < 1e-6
    for g, w in zip(got + dgot, want + dwant):
        assert np.isfinite(np.asarray(g, np.float32)).all()
        assert np.abs(np.asarray(g, np.float32)
                      - np.asarray(w, np.float32)).max() < 2e-5 * max(
                          1.0, np.abs(np.asarray(w, np.float32)).max())


@pytest.mark.parametrize("shape,dtype,n,why", [
    ((1, 8, 512), "bfloat16", 4, None),
    ((1, 8, 14336), "bfloat16", 4, None),               # the cell's
    ((1, 8, 64), "float32", 4, "stream_not_whole_lane_tiles"),
    ((1, 8, 384), "float32", 2, "stream_not_whole_lane_tiles"),   # 192 a stream
    ((1, 8, 512), "float16", 4, "dtype:float16"),
    ((1, 8, 1280), "float32", 10, "maps_wider_than_a_lane_tile"),
    ((1, 8, 4 * 16384), "float32", 4, "rows_exceed_vmem"),
])
def test_unsupported_says_why(shape, dtype, n, why):
    assert kernels.unsupported(
        jax.ShapeDtypeStruct(shape, jnp.dtype(dtype)), n) == why


@pytest.fixture
def asked(monkeypatch):
    """The layer asks for its kernels as it does on a TPU, and gets them in
    interpret mode (``dispatch.take(asked=True)``)."""
    import types
    monkeypatch.setattr(forms, "dispatch", types.SimpleNamespace(
        mosaic=lambda: True,
        take=lambda kernel, mesh, why:
        dispatch.take(kernel, mesh, why, asked=True)))


def wide_program(name, c=128):
    """``program`` at a stream of one whole lane tile, with its feed."""
    hc = HyperConnection(c, N, FEW, EPS, CLAMP, name=f"{name}_hc")
    norm = RMSNorm(c, eps=EPS, name=f"{name}_norm")
    w = ht.Variable(f"{name}_w", shape=(c, c),
                    initializer=ht.init.normal(0.0, 0.1))
    x = ht.placeholder_op(f"{name}_x", (B, S, N * c))
    y = hc.sublayer(x, norm, lambda h: ht.matmul_op(h, w))
    loss = ht.reduce_sum_op(y * y, axes=None)
    variables = [hc.phi, hc.b, hc.alpha, norm.scale, w]
    ex = ht.Executor({"forward": [y, hc.hres],
                      "grads": [loss] + ht.gradients(loss, variables)},
                     seed=0)
    r = np.random.default_rng(7)
    ex.params[hc.phi.name] = jnp.asarray(
        r.normal(0, (N * c) ** -0.5, hc.phi.shape), jnp.float32)
    ex.params[hc.b.name] = jnp.asarray(r.normal(0, 0.5, hc.b.shape),
                                       jnp.float32)
    ex.params[hc.alpha.name] = jnp.asarray(r.uniform(0.5, 1.0, 3),
                                           jnp.float32)
    ex.params[w.name] = jnp.asarray(r.normal(0, 0.1, (c, c)), jnp.float32)
    return ex, {x: r.normal(0, 1, (B, S, N * c)).astype(np.float32)}


def test_the_layer_through_the_kernels_is_the_layer(asked, live_registry):
    """One sublayer through the executor, forward and every gradient, with
    the kernels asked for and without: the same numbers; one ``pallas`` choice
    a traced program, ``path="pallas"`` once a sublayer built."""
    def counts():
        return (dict((lab["path"], n) for lab, n in dispatch.counted(
            "hetu_hc_entry_total")),
            {k[1:]: n for k, n in dispatch.choices().items()
             if k[0] == "hc_mix"})
    built, chosen = counts()
    ex, feed = wide_program("hc_asked")
    got = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    dgot = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    after, taken = counts()
    assert after.get("pallas", 0) == built.get("pallas", 0) + 1
    assert after.get("xla", 0) == built.get("xla", 0)
    assert taken.get(("pallas", ""), 0) >= chosen.get(("pallas", ""), 0) + 2
    assert set(taken) == {("pallas", "")} | set(chosen)
    forms.dispatch = dispatch             # the fixture puts its own back
    ex, feed = wide_program("hc_unasked")
    want = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    dwant = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert counts()[1] == taken           # off a TPU, unasked: nothing recorded
    assert counts()[0].get("xla", 0) == built.get("xla", 0) + 1
    assert got[1].shape == (B, S, N, N)
    for g, w in zip(got + dgot, want + dwant):
        assert np.abs(g - w).max() < 2e-5 * max(1.0, np.abs(w).max())


def test_a_narrow_stream_asked_for_says_why_and_runs_the_forms(asked,
                                                               live_registry):
    """A stream of 16 lanes: the layer is built ``xla``, and asked for the
    kernels at trace time it records the refusal and runs ``_maps``, ``_pre``
    and ``_mix``."""
    before = dispatch.choices().get(
        ("hc_mix", "jnp", "stream_not_whole_lane_tiles"), 0)
    ex, x, _, (_, _, _, X, _, _) = loaded("hc_narrow")
    got, _ = ex.run("forward", feed_dict={x: X.reshape(B, S, -1)},
                    convert_to_numpy_ret_vals=True)
    assert np.isfinite(got).all()
    assert dispatch.choices()[
        ("hc_mix", "jnp", "stream_not_whole_lane_tiles")] > before
    assert HyperConnection(16, N, name="hc_narrow_path").path() == "xla"
    assert HyperConnection(128, N, name="hc_wide_path").path() == "pallas"
