"""Latent attention's training half: the layer against a plain softmax with
two head sizes, the head-wise gate, rotary on the rope part alone, the
low-rank query path, YaRN on the rope part and the scores' multiplier; the
Ling path's graph and loss as they were before those; flash attention with
keys wider than values against ``jax.numpy``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.layers.latent_attention import LatentAttention
from hetu_tpu.ops.pallas.flash_attention import (entries, flash_attention,
                                                 unsupported)

H, DN, DR, DV, RANK, HID, S = 2, 32, 16, 24, 20, 40, 48


QRANK = 12
YARN = dict(factor=64.0, original=16, beta_fast=32.0, beta_slow=1.0)


def yarn_inverse_frequencies(theta):
    """transformers' YaRN over the ``DR`` rotary dimensions, by hand."""
    inv = 1.0 / theta ** (np.arange(0, DR, 2) / DR)

    def dimension(turns):
        return (DR * np.log(YARN["original"] / (turns * 2 * np.pi))
                / (2 * np.log(theta)))
    low = max(np.floor(dimension(YARN["beta_fast"])), 0)
    high = min(np.ceil(dimension(YARN["beta_slow"])), DR - 1)
    ramp = np.clip((np.arange(DR // 2) - low) / max(high - low, 0.001), 0, 1)
    return inv * (1 - ramp) + inv / YARN["factor"] * ramp


def plain(p, x, name, gated=True, normed=True, theta=1e4, low_rank=False,
          yarn=False, scale_mult=1.0):
    """The layer's equations in plain ``jax.numpy`` (the docstring of
    ``layers/latent_attention.py``)."""
    w = lambda n: jnp.asarray(p[f"{name}_{n}"])
    rms = lambda t, s: t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                         + 1e-6) * s
    B = x.shape[0]
    xq = rms(x @ w("qa_weight"), w("qa_norm_scale")) if low_rank else x
    q = (xq @ w("q_weight")).reshape(B, S, H, DN + DR)
    kva = x @ w("kva_weight")
    c = rms(kva[..., :RANK], w("kv_norm_scale"))
    kvb = (c @ w("kvb_weight")).reshape(B, S, H, DN + DV)
    k = jnp.concatenate([kvb[..., :DN], jnp.broadcast_to(
        kva[..., None, RANK:], (B, S, H, DR))], -1)
    v = kvb[..., DN:]
    if normed:
        q, k = rms(q, w("q_norm_scale")), rms(k, w("k_norm_scale"))

    def rope(t):
        inv = (jnp.asarray(yarn_inverse_frequencies(theta)) if yarn
               else 1.0 / theta ** (jnp.arange(0, DR, 2) / DR))
        ang = jnp.arange(S)[:, None] * inv[None, :]
        cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
        sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
        keep, r = t[..., :DN], t[..., DN:]
        turned = jnp.concatenate([-r[..., DR // 2:], r[..., :DR // 2]], -1)
        return jnp.concatenate([keep, r * cos + turned * sin], -1)
    q, k = rope(q), rope(k)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(DN + DR) * scale_mult
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


@pytest.mark.parametrize("low_rank,yarn,mult", [
    (True, False, None), (False, True, None), (False, False, 2.0047),
    (True, True, 2.0047)])
def test_low_rank_queries_yarn_and_the_scores_multiplier(low_rank, yarn,
                                                         mult):
    """Xing4.0's mixer: ``c_q = N(x W_qa)`` in front of ``W_qb``, YaRN's
    frequencies on the rope part (tables times 1), the scores' scale times
    ``m^2``; no QK-norm, no gate."""
    from hetu_tpu.ops.rotary import yarn_scaling
    name = f"mla_x_{int(low_rank)}{int(yarn)}{int(bool(mult))}"
    layer = LatentAttention(
        HID, H, RANK, DN, DR, DV, rope_theta=1e4, qk_norm=False,
        head_gate=False, q_lora_rank=QRANK if low_rank else None,
        rope_scaling=yarn_scaling(YARN["factor"], YARN["original"],
                                  YARN["beta_fast"], YARN["beta_slow"], 1.0)
        if yarn else None, softmax_scale_mult=mult, name=name)
    assert layer.q_proj.shape == ((QRANK if low_rank else HID), H * (DN + DR))
    assert (layer.qa_proj is not None) == low_rank
    x = ht.placeholder_op(f"{name}_x", (2, S, HID))
    ex = ht.Executor([layer(x)], seed=2)
    if low_rank:
        ex.params[f"{name}_qa_norm_scale"] = ex.params[
            f"{name}_qa_norm_scale"] * 1.3
    xv = np.random.default_rng(2).standard_normal((2, S, HID)).astype(
        np.float32)
    (got,) = ex.run(feed_dict={x: xv}, convert_to_numpy_ret_vals=True)
    kw = dict(gated=False, normed=False, low_rank=low_rank, yarn=yarn,
              scale_mult=mult or 1.0)
    want = plain(ex.params, jnp.asarray(xv), name, **kw)
    assert np.abs(got - np.asarray(want)).max() < 2e-5 * np.abs(want).max()
    # each of the three is seen: the plain form without it is far off
    for off in ("low_rank", "yarn", "scale_mult"):
        if kw[off] in (False, 1.0) or off == "low_rank":
            continue
        other = plain(ex.params, jnp.asarray(xv), name,
                      **dict(kw, **{off: False if off == "yarn" else 1.0}))
        assert np.abs(got - np.asarray(other)).max() > 1e-3 * np.abs(
            want).max(), off


def test_the_ling_path_builds_the_graph_and_the_loss_it_had():
    """``q_lora_rank=None``, no scaling, no multiplier: the Ling toy program
    (``tests/test_ling3_reference.py build``) has the node count and, to the
    bit, the loss and the logits' sum it had at the parent of PR 56 (read
    there: 325 and 337 nodes, loss 0x1.6459d4p+2)."""
    import test_ling3_reference as ling
    model, ex, variables, feed = ling.build(name="lingpin")
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert len(ex.subexecutor["forward"].topo) == 325
    assert len(ex.subexecutor["grads"].topo) == 337
    assert float(out[1]).hex() == "0x1.6459d40000000p+2"
    assert float(np.float64(out[0]).sum()).hex() == "-0x1.243e79034bd00p+3"
    mla = model.model.layers[5].mixer
    assert mla.qa_proj is None and mla.rope_scaling is None
    assert mla.scale == (32 + 16) ** -0.5


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
