"""The delta rule with a decay a key channel (``ops/kda.py``): the chunked
``jax.numpy`` form and the Pallas kernel pair (interpret mode) against the
recurrence, gates down to the bound, lengths no chunk divides, gradients of
the custom VJP against JAX's own, and the scalar-decay rule as its special
case.  The in-place entry, the scan node and the layer through the kernels
are ``tests/test_kda_in_place.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops import kda
from hetu_tpu.ops.gated_delta import recurrent_gated_delta_rule
from hetu_tpu.ops.pallas import kda as kernels

D = 128


def draw(seed, T, H=2, lo=-5.0, B=1, d=D, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, d)))
    v = jax.random.normal(ks[2], (B, T, H, d))
    g = lo * jax.random.uniform(ks[3], (B, T, H, d))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta)


def rel(a, b):
    a, b = (np.asarray(x, np.float64) for x in (a, b))
    return np.abs(a - b).max() / np.abs(b).max()


@pytest.mark.parametrize("T", [64, 150, 257])
@pytest.mark.parametrize("lo", [-5.0, -0.05])
def test_chunked_form_is_the_recurrence(T, lo):
    x = draw(T, T, lo=lo)
    o, s = kda.chunk_kda_jnp(*x)
    o_ref, s_ref = kda.recurrent_kda(*x)
    assert rel(o, o_ref) < 2e-5 and rel(s, s_ref) < 2e-5


def test_every_gate_at_the_bound_stays_finite():
    """``g = -5`` at every position and channel: 16 positions of it are
    ``exp(80)`` inside a sub-chunk, the most the chunked form ever raises."""
    q, k, v, g, beta = draw(3, 130)
    g = jnp.full_like(g, -5.0)
    for rule in (kda.chunk_kda_jnp, kernels.kda):
        o, s = rule(q, k, v, g, beta)
        o_ref, s_ref = kda.recurrent_kda(q, k, v, g, beta)
        assert np.isfinite(np.asarray(o)).all()
        assert rel(o, o_ref) < 2e-5 and rel(s, s_ref) < 2e-5


@pytest.mark.parametrize("T", [64, 150, 600])
def test_kernels_are_the_recurrence(T):
    x = draw(10 + T, T)
    o, s = kernels.kda(*x)
    o_ref, s_ref = kda.recurrent_kda(*x)
    assert rel(o, o_ref) < 2e-5 and rel(s, s_ref) < 2e-5


def test_kernels_take_bf16_operands_and_keep_an_f32_state():
    x = draw(5, 200, dtype=jnp.bfloat16)
    o, s = kernels.kda(*x)
    o_jnp, s_jnp = kda.chunk_kda_jnp(*x)
    assert o.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    assert rel(o.astype(jnp.float32), o_jnp.astype(jnp.float32)) < 2e-2
    assert rel(s, s_jnp) < 5e-3
    # a state carried in bf16 is another result
    _, s_low = kda.recurrent_kda(*x, state_dtype=jnp.bfloat16)
    _, s_ref = kda.recurrent_kda(*x)
    assert rel(s_low, s_ref) > 4 * rel(s, s_ref)


def weighted(rule, wo, ws):
    def loss(*x):
        o, s = rule(*x)
        return jnp.sum(o * wo) + jnp.sum(s * ws)
    return loss


@pytest.mark.parametrize("rule", ["jnp", "pallas"])
def test_gradients_against_the_recurrences(rule):
    """The custom VJP (a ``jax.vjp`` of the chunk inside the kernel) and
    JAX's own through the ``jax.numpy`` form, both against JAX's through the
    recurrence: q, k, v, g (a number a channel) and beta."""
    x = draw(7, 150)
    ks = jax.random.split(jax.random.PRNGKey(99), 2)
    wo = jax.random.normal(ks[0], x[2].shape)
    ws = jax.random.normal(ks[1], (1, 2, D, D))
    fn = kernels.kda if rule == "pallas" else kda.chunk_kda_jnp
    got = jax.grad(weighted(fn, wo, ws), argnums=(0, 1, 2, 3, 4))(*x)
    want = jax.grad(weighted(kda.recurrent_kda, wo, ws),
                    argnums=(0, 1, 2, 3, 4))(*x)
    for name, a, b in zip("qkvgb", got, want):
        assert a.shape == b.shape
        assert rel(a, b) < 5e-5, name


def test_a_constant_gate_across_channels_is_the_gated_delta_rule():
    q, k, v, g, beta = draw(11, 100)
    scalar = g[..., 0] * 0.02
    wide = jnp.broadcast_to(scalar[..., None], g.shape)
    o, s = kda.recurrent_kda(q, k, v, wide, beta)
    o_ref, s_ref = recurrent_gated_delta_rule(q, k, v, scalar, beta)
    np.testing.assert_array_equal(np.asarray(o), np.asarray(o_ref))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    o_c, s_c = kda.chunk_kda_jnp(q, k, v, wide, beta)
    assert rel(o_c, o_ref) < 2e-5 and rel(s_c, s_ref) < 2e-5


def test_a_scalar_decay_in_place_of_the_vector_is_another_result():
    x = draw(13, 128, lo=-1.0)
    q, k, v, g, beta = x
    mean = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    _, s = kda.recurrent_kda(*x)
    _, s_mean = kda.recurrent_kda(q, k, v, mean, beta)
    assert rel(s_mean, s) > 0.05


@pytest.mark.parametrize("case,reason", [
    ("ok", None), ("chunk", "chunk!=64"), ("dim", "head_dim_not_128_aligned"),
    ("mixed", "dtype:mixed"), ("f16", "dtype:float16"),
    ("gate", "gate_dtype:bfloat16")])
def test_unsupported_says_why(case, reason):
    q, k, v, g, beta = draw(1, 64)
    chunk = 64
    if case == "chunk":
        chunk = 32
    if case == "dim":
        q, k, g = q[..., :64], k[..., :64], g[..., :64]
    if case == "mixed":
        q = q.astype(jnp.bfloat16)
    if case == "f16":
        q, k, v = (t.astype(jnp.float16) for t in (q, k, v))
    if case == "gate":
        g = g.astype(jnp.bfloat16)
    assert kernels.unsupported(q, k, v, g, chunk) == reason


def test_chunk_kda_counts_its_choice_on_a_tpu_only(monkeypatch):
    from hetu_tpu import telemetry
    from hetu_tpu.ops.pallas import dispatch
    telemetry.enable()
    try:
        def kda_choices():
            return {k[1:]: n for k, n in dispatch.choices().items()
                    if k[0] == "kda"}
        before = kda_choices()
        x = draw(2, 64)
        kda.chunk_kda(*x)                    # the cpu: no choice to record
        assert kda_choices() == before
        monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
        monkeypatch.setattr(dispatch, "interpret", lambda: True)
        o, _ = kda.chunk_kda(*x)
        after = kda_choices()
        assert after.get(("pallas", ""), 0) == before.get(("pallas", ""),
                                                          0) + 1
        assert rel(o, kda.recurrent_kda(*x)[0]) < 2e-5
        kda.chunk_kda(*(t[..., :64] if t.ndim == 4 else t for t in x))
        assert kda_choices().get(("jnp", "head_dim_not_128_aligned")) == (
            before.get(("jnp", "head_dim_not_128_aligned"), 0) + 1)
    finally:
        telemetry.disable()


def test_the_layer_is_its_equations():
    """``layers/kda.py`` through the graph against the equations written
    out with the recurrence."""
    import hetu_tpu as ht
    from hetu_tpu.layers.kda import KimiDeltaAttention
    H, d, hid, S = 2, 32, 48, 40
    layer = KimiDeltaAttention(hid, H, d, name="kda_eq")
    x = ht.placeholder_op("kda_eq_x", (1, S, hid))
    ex = ht.Executor([layer(x)], seed=3)
    xv = np.random.default_rng(0).standard_normal((1, S, hid)).astype(
        np.float32)
    (got,) = ex.run(feed_dict={x: xv}, convert_to_numpy_ret_vals=True)
    p = {k: jnp.asarray(v) for k, v in ex.params.items()}
    w = lambda n: p[f"kda_eq_{n}"]
    hd = H * d
    proj = xv @ w("in_weight")
    conv = w("conv_weight")
    xp = jnp.pad(proj[..., :3 * hd], ((0, 0), (3, 0), (0, 0)))
    mixed = jax.nn.silu(sum(xp[:, j:j + S] * conv[j] for j in range(4)))
    heads = lambda t: t.reshape(1, S, H, d)
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True)
                                       + 1e-6)
    q, k, v = (heads(mixed[..., i * hd:(i + 1) * hd]) for i in range(3))
    g = -5.0 * jax.nn.sigmoid(jnp.exp(w("a_log"))[:, None] * (
        heads(proj[..., 3 * hd:4 * hd]) + w("dt_bias").reshape(H, d)))
    beta = jax.nn.sigmoid(xv @ w("beta_weight"))
    o, _ = kda.recurrent_kda(unit(q) * d ** -0.5, unit(k), v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6)
    o = o * w("norm_scale") * jax.nn.sigmoid(heads(proj[..., 4 * hd:]))
    want = o.reshape(1, S, hd) @ w("out_weight")
    assert rel(got, want) < 1e-4
    assert float(g.min()) >= -5.0 and float(g.max()) <= 0.0
