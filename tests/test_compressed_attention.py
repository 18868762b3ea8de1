"""Compressed convolutional attention (``layers/compressed_attention.py``)
against a thirty-line ``jax.numpy`` copy of the equations, forward and
``jax.grad``; causality through both convolutions and the values' shift; the
heads' norms; with taps ``(0, 1)``, ``A_1 = I`` and the mean off it is plain
grouped-query attention on normed queries and keys; what it counts when it is
built."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.layers.compressed_attention import CompressedConvAttention

B, S, C, H, J, D, TURNED, THETA = 2, 24, 32, 4, 2, 8, 4, 5e6
G = H // J


def before(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], 1)


def rotate(x):
    """Half-split rotary on the first ``TURNED`` lanes of ``[B, S, n, d]``."""
    inv = THETA ** (-2.0 * jnp.arange(TURNED // 2) / TURNED)
    ang = jnp.arange(S)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    t = x[..., :TURNED]
    turned = jnp.concatenate([-t[..., TURNED // 2:], t[..., :TURNED // 2]], -1)
    return jnp.concatenate([t * jnp.cos(ang) + turned * jnp.sin(ang),
                            x[..., TURNED:]], -1)


def plain(u, w_qk, w_v, w_o, taps, tap_bias, mix, mix_bias, temp,
          qk_mean=True):
    """The equations on ``u [B, S, C]``: ``(y, q^, k^)``."""
    z = u @ w_qk
    z1 = (taps[0] * before(z) + taps[1] * z + tap_bias).reshape(
        B, S, H + J, D)
    z2 = (jnp.einsum("bsnd,nde->bsne", before(z1), mix[0])
          + jnp.einsum("bsnd,nde->bsne", z1, mix[1])
          + mix_bias.reshape(H + J, D))
    q, k = z2[:, :, :H], z2[:, :, H:]
    if qk_mean:
        mq = (z[..., :H * D].reshape(B, S, J, G, D)
              + z[..., H * D:].reshape(B, S, J, 1, D)) / 2
        q, k = q + mq.reshape(B, S, H, D), k + mq.mean(3)
    q = np.sqrt(D) * q / jnp.linalg.norm(q, axis=-1, keepdims=True)
    k = (np.sqrt(D) * k / jnp.linalg.norm(k, axis=-1, keepdims=True)
         * jnp.exp(temp)[:, None])
    half = J * D // 2
    v = jnp.concatenate([u @ w_v[:, :half], before(u) @ w_v[:, half:]],
                        -1).reshape(B, S, J, D)
    reads = jnp.arange(H) // G
    s = jnp.einsum("bqhd,bkhd->bhqk", rotate(q),
                   rotate(k)[:, :, reads]) / np.sqrt(D)
    s = jnp.where(jnp.arange(S)[:, None] >= jnp.arange(S)[None, :], s,
                  -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v[:, :, reads])
    return o.reshape(B, S, H * D) @ w_o, q, k


def draws(seed=0, identity=False):
    r = np.random.default_rng(seed)
    n, width = H + J, (H + J) * D
    w = dict(w_qk=r.normal(0, C ** -0.5, (C, width)),
             w_v=r.normal(0, C ** -0.5, (C, J * D)),
             w_o=r.normal(0, (H * D) ** -0.5, (H * D, C)),
             taps=r.normal(0, 1, (2, width)),
             tap_bias=r.normal(0, 0.5, width),
             mix=r.normal(0, D ** -0.5, (2, n, D, D)),
             mix_bias=r.normal(0, 0.5, width),
             temp=r.uniform(-0.5, 0.5, J))
    if identity:
        w["taps"] = np.stack([np.zeros(width), np.ones(width)])
        w["mix"] = np.stack([np.zeros((n, D, D)),
                             np.broadcast_to(np.eye(D), (n, D, D))])
        w["tap_bias"] = w["mix_bias"] = np.zeros(width)
    return {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}


def program(name, w, **kw):
    """The layer with the weights ``w``: its executor, the placeholder of ``u``
    and the variables in ``plain``'s order."""
    layer = CompressedConvAttention(C, H, J, D, conv_taps=(2, 2),
                                    rotary_dim=TURNED, rope_theta=THETA,
                                    sequence_length=S, name=name, **kw)
    u = ht.placeholder_op(f"{name}_u", (B, S, C))
    y = layer(u)
    loss = ht.reduce_sum_op(y * y, axes=None)
    variables = [layer.qk_proj.weight, layer.v_proj.weight,
                 layer.out_proj.weight, layer.taps, layer.tap_bias,
                 layer.mix, layer.mix_bias, layer.temp]
    ex = ht.Executor({"forward": [y, *layer.qk],
                      "grads": [loss] + ht.gradients(loss, variables)},
                     seed=0)
    for var, value in zip(variables, w.values()):
        assert tuple(var.shape) == value.shape, var.name
        ex.params[var.name] = value
    return ex, u, variables


U = np.random.default_rng(1).normal(0, 1, (B, S, C)).astype(np.float32)


def test_the_layer_is_the_equations_forward_and_backward():
    w = draws()
    ex, u, variables = program("cca_eq", w)
    y, q, k = ex.run("forward", feed_dict={u: U},
                     convert_to_numpy_ret_vals=True)
    with jax.default_matmul_precision("highest"):
        want, wq, wk = plain(jnp.asarray(U), *w.values())
        grads = jax.grad(lambda p: jnp.sum(
            plain(jnp.asarray(U), *p)[0] ** 2))(list(w.values()))
    assert np.abs(y - np.asarray(want)).max() < 2e-5
    assert np.abs(q - np.asarray(wq).reshape(B, S, -1)).max() < 2e-5
    assert np.abs(k - np.asarray(wk).reshape(B, S, -1)).max() < 2e-5
    mine = ex.run("grads", feed_dict={u: U}, convert_to_numpy_ret_vals=True)
    for var, g, wnt in zip(variables, mine[1:], grads):
        wnt = np.asarray(wnt)
        assert np.abs(wnt).max() > 0, var.name
        assert np.abs(g - wnt).max() < 5e-4 * np.abs(wnt).max(), var.name


def test_position_t_does_not_see_what_comes_after_it():
    """Through both convolutions, the mean and the values' shift: the outputs
    up to ``t`` stay to the bit when every input after ``t`` changes; the one
    at ``t + 1`` moves."""
    ex, u, _ = program("cca_causal", draws(2))
    t = 9
    other = U.copy()
    other[:, t + 1:] = np.random.default_rng(5).normal(
        0, 1, other[:, t + 1:].shape)
    a, qa, ka = ex.run("forward", feed_dict={u: U},
                       convert_to_numpy_ret_vals=True)
    b, qb, kb = ex.run("forward", feed_dict={u: other},
                       convert_to_numpy_ret_vals=True)
    for x, y in ((a, b), (qa, qb), (ka, kb)):
        np.testing.assert_array_equal(x[:, :t + 1], y[:, :t + 1])
        assert np.abs(x[:, t + 1] - y[:, t + 1]).max() > 1e-3


def test_position_t_sees_the_position_before_it_three_ways():
    """Changing the input at ``t - 1`` alone moves ``q^`` at ``t`` (a tap) and
    at ``t + 1`` (a tap of a tap) and no later ``q^``; the output at ``t``
    moves by the values' shift even where attention sees ``t`` alone."""
    ex, u, _ = program("cca_taps", draws(3))
    t = 6
    other = U.copy()
    other[:, t - 1] += 1.0
    _, qa, _ = ex.run("forward", feed_dict={u: U},
                      convert_to_numpy_ret_vals=True)
    _, qb, _ = ex.run("forward", feed_dict={u: other},
                      convert_to_numpy_ret_vals=True)
    moved = np.abs(qa - qb).max(axis=(0, 2)) > 1e-6
    assert moved[t - 1:t + 2].all() and not moved[t + 2:].any()
    assert not moved[:t - 1].any()
    first = U.copy()
    first[:, 0] += 1.0             # position 1's second value half is u_0's
    ya = ex.run("forward", feed_dict={u: U},
                convert_to_numpy_ret_vals=True)[0]
    yb = ex.run("forward", feed_dict={u: first},
                convert_to_numpy_ret_vals=True)[0]
    assert np.abs(ya[:, 1] - yb[:, 1]).max() > 1e-3


def test_every_head_has_the_norm_the_equations_give_it():
    w = draws(4)
    ex, u, _ = program("cca_norms", w)
    _, q, k = ex.run("forward", feed_dict={u: U},
                     convert_to_numpy_ret_vals=True)
    nq = np.linalg.norm(q.reshape(B, S, H, D), axis=-1)
    nk = np.linalg.norm(k.reshape(B, S, J, D), axis=-1)
    np.testing.assert_allclose(nq, np.sqrt(D), rtol=1e-5)
    np.testing.assert_allclose(nk, np.sqrt(D) * np.exp(
        np.asarray(w["temp"])) * np.ones((B, S, J)), rtol=1e-5)


def test_identity_taps_without_the_mean_are_plain_grouped_queries():
    """Taps ``(0, 1)``, ``A_0 = 0``, ``A_1 = I``, no biases, the mean off:
    grouped-query attention on L2-normed q and k (times the temperature) with
    the second value head a position behind."""
    w = draws(6, identity=True)
    ex, u, _ = program("cca_plain", w, _qk_mean=False)
    y, q, k = ex.run("forward", feed_dict={u: U},
                     convert_to_numpy_ret_vals=True)
    z = (jnp.asarray(U) @ w["w_qk"]).reshape(B, S, H + J, D)
    unit = np.sqrt(D) * z / jnp.linalg.norm(z, axis=-1, keepdims=True)
    np.testing.assert_allclose(q.reshape(B, S, H, D), unit[:, :, :H],
                               atol=2e-6)
    np.testing.assert_allclose(
        k.reshape(B, S, J, D),
        unit[:, :, H:] * jnp.exp(w["temp"])[:, None], atol=2e-6)
    with jax.default_matmul_precision("highest"):
        want = plain(jnp.asarray(U), *w.values(), qk_mean=False)[0]
    assert np.abs(y - np.asarray(want)).max() < 2e-5


def test_a_fresh_layers_mixing_is_the_identity_and_it_is_counted():
    telemetry.enable()
    try:
        def count(name, **labels):
            metric = telemetry.get_registry().snapshot().get(
                name, {"samples": []})
            return sum(s["value"] for s in metric["samples"]
                       if all(s["labels"].get(k) == v
                              for k, v in labels.items()))
        built = count("hetu_cca_entry_total", path="xla")
        flat = count("hetu_attn_layout_total", layout="bshd",
                     reason="head_dim_not_128_aligned")
        tiled = count("hetu_attn_layout_total", layout="bshd",
                      reason="in_place")
        layer = CompressedConvAttention(C, H, J, D, rotary_dim=TURNED,
                                        sequence_length=S, name="cca_fresh")
        u = ht.placeholder_op("cca_fresh_u", (B, S, C))
        y = layer(u)
        assert count("hetu_cca_entry_total", path="xla") == built + 1
        # heads of 8 lanes: one graph still, the kernels do not take it
        assert layer.layout() == ("bshd", "head_dim_not_128_aligned")
        assert count("hetu_attn_layout_total", layout="bshd",
                     reason="head_dim_not_128_aligned") == flat + 1
        CompressedConvAttention(C, H, J, 128, rotary_dim=64,
                                sequence_length=S, name="cca_tiled")(u)
        assert count("hetu_attn_layout_total", layout="bshd",
                     reason="in_place") == tiled + 1
    finally:
        telemetry.shutdown()
    assert layer.PATH == "xla" and "hetu_cca" in ht.scopes()
    assert {n.scope for n in layer.qk} == {"hetu_cca"}
    assert y.scope == "hetu_attn"
    ex = ht.Executor({"forward": [y, *layer.qk]}, seed=0)
    _, q, k = ex.run("forward", feed_dict={u: U},
                     convert_to_numpy_ret_vals=True)
    z = np.asarray(jnp.asarray(U) @ ex.params[layer.qk_proj.weight.name])
    zq = z[..., :H * D].reshape(B, S, J, G, D)
    zk = z[..., H * D:].reshape(B, S, J, 1, D)
    mq = (zq + zk) / 2
    want_q = (zq + mq).reshape(B, S, H, D)
    want_q = np.sqrt(D) * want_q / np.linalg.norm(want_q, axis=-1,
                                                  keepdims=True)
    np.testing.assert_allclose(q.reshape(B, S, H, D), want_q, atol=1e-5)


def test_an_odd_number_of_key_heads_is_refused():
    with pytest.raises(AssertionError, match="previous token's values"):
        CompressedConvAttention(C, 3, 3, D, name="cca_odd")
