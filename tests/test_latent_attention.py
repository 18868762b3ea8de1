"""Latent attention's training half: the layer against a plain softmax with
two head sizes, the head-wise gate, rotary on the rope part alone; flash
attention with keys wider than values against ``jax.numpy``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.layers.latent_attention import LatentAttention
from hetu_tpu.ops.pallas.flash_attention import (entries, flash_attention,
                                                 unsupported)

H, DN, DR, DV, RANK, HID, S = 2, 32, 16, 24, 20, 40, 48


def plain(p, x, name, gated=True, normed=True, theta=1e4):
    """The layer's equations in plain ``jax.numpy`` (the docstring of
    ``layers/latent_attention.py``)."""
    w = lambda n: jnp.asarray(p[f"{name}_{n}"])
    rms = lambda t, s: t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                         + 1e-6) * s
    B = x.shape[0]
    q = (x @ w("q_weight")).reshape(B, S, H, DN + DR)
    kva = x @ w("kva_weight")
    c = rms(kva[..., :RANK], w("kv_norm_scale"))
    kvb = (c @ w("kvb_weight")).reshape(B, S, H, DN + DV)
    k = jnp.concatenate([kvb[..., :DN], jnp.broadcast_to(
        kva[..., None, RANK:], (B, S, H, DR))], -1)
    v = kvb[..., DN:]
    if normed:
        q, k = rms(q, w("q_norm_scale")), rms(k, w("k_norm_scale"))

    def rope(t):
        inv = 1.0 / theta ** (jnp.arange(0, DR, 2) / DR)
        ang = jnp.arange(S)[:, None] * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
        keep, r = t[..., :DN], t[..., DN:]
        turned = jnp.concatenate([-r[..., DR // 2:], r[..., :DR // 2]], -1)
        return jnp.concatenate([keep, r * cos + turned * sin], -1)
    q, k = rope(q), rope(k)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(DN + DR)
    i = jnp.arange(S)
    s = jnp.where(i[:, None] >= i[None, :], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    if gated:
        o = o * jax.nn.sigmoid(x @ w("gate_weight"))[..., None]
    return o.reshape(B, S, H * DV) @ w("out_weight")


@pytest.mark.parametrize("gated,normed", [(True, True), (False, True),
                                          (True, False)])
def test_layer_is_a_plain_softmax_with_two_head_sizes(gated, normed):
    name = f"mla_t_{int(gated)}{int(normed)}"
    layer = LatentAttention(HID, H, RANK, DN, DR, DV, rope_theta=1e4,
                            qk_norm=normed, head_gate=gated, name=name)
    x = ht.placeholder_op(f"{name}_x", (2, S, HID))
    ex = ht.Executor([layer(x)], seed=1)
    xv = np.random.default_rng(1).standard_normal((2, S, HID)).astype(
        np.float32)
    (got,) = ex.run(feed_dict={x: xv}, convert_to_numpy_ret_vals=True)
    assert got.shape == (2, S, HID)
    want = plain(ex.params, jnp.asarray(xv), name, gated, normed)
    assert np.abs(got - np.asarray(want)).max() < 2e-5 * np.abs(want).max()


def test_the_gate_is_one_number_a_head_and_rotary_spares_the_nope_part():
    name = "mla_parts"
    layer = LatentAttention(HID, H, RANK, DN, DR, DV, name=name)
    assert layer.gate_proj.shape == (HID, H)
    assert layer.q_norm.shape == (DN + DR,)
    assert layer.kvb_proj.shape == (RANK, H * (DN + DV))
    from hetu_tpu.layers.latent_attention import _rope_last
    x = jax.random.normal(jax.random.PRNGKey(0), (1, S, H, DN + DR))
    y = _rope_last(x, DR, 1e4)
    np.testing.assert_array_equal(np.asarray(y[..., :DN]),
                                  np.asarray(x[..., :DN]))
    assert np.abs(np.asarray(y[:, 1:, :, DN:] - x[:, 1:, :, DN:])).max() > 0.1
    np.testing.assert_allclose(np.asarray(y[:, 0]), np.asarray(x[:, 0]),
                               rtol=1e-6)


def reference_attention(q, k, v, causal=True):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        i = jnp.arange(q.shape[2])
        s = jnp.where(i[:, None] >= i[None, :], s, -1e9)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("dqk,dv,seq", [(192, 128, 384), (48, 32, 200),
                                        (64, 128, 256)])
def test_flash_takes_keys_and_values_of_two_widths(dqk, dv, seq):
    ks = jax.random.split(jax.random.PRNGKey(dqk), 4)
    q, k = (jax.random.normal(ks[i], (1, 2, seq, dqk)) for i in (0, 1))
    v = jax.random.normal(ks[2], (1, 2, seq, dv))
    w = jax.random.normal(ks[3], (1, 2, seq, dv))
    assert unsupported(q, k, v) is None
    out = flash_attention(q, k, v, causal=True)
    assert out.shape == v.shape
    want = reference_attention(q, k, v)
    assert np.abs(np.asarray(out - want)).max() < 2e-5
    got = jax.grad(lambda *a: jnp.sum(flash_attention(*a, causal=True) * w),
                   argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(reference_attention(*a) * w),
                   argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):
        assert a.shape == b.shape
        assert np.abs(np.asarray(a - b)).max() < 1e-4


def test_flash_still_refuses_what_is_no_self_attention():
    q = jnp.zeros((1, 2, 256, 64))
    assert unsupported(q, q[:, :, :128], q) == "not_self_attention_4d"
    assert unsupported(q, q, q[:, :1]) == "not_self_attention_4d"
    assert unsupported(q, q, jnp.zeros((1, 2, 256, 520))) == "head_dim>512"
    assert unsupported(q, q, jnp.zeros((1, 2, 256, 32))) is None


def test_the_entry_counter_names_the_two_widths():
    from hetu_tpu import telemetry
    telemetry.enable()
    try:
        before = entries().get(("bhsd_v128", 1), 0)
        q = jnp.zeros((1, 1, 128, 192))
        flash_attention(q, q, jnp.zeros((1, 1, 128, 128)), causal=True)
        assert entries().get(("bhsd_v128", 1), 0) == before + 1
    finally:
        telemetry.disable()
