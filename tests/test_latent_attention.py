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
    there: 325 and 337 nodes, loss 0x1.6459d4p+2).  Since PR 59 a latent
    layer has one node more: the node that chooses the heads' layout and its
    three items where ``_queries``, ``_keys`` and ``_values`` stood."""
    import test_ling3_reference as ling
    model, ex, variables, feed = ling.build(name="lingpin")
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    for graph, nodes in (("forward", 325), ("grads", 337)):
        topo = ex.subexecutor[graph].topo
        latent = sum(type(node).__name__ == "_Heads" for node in topo)
        assert latent and len(topo) == nodes + latent
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


# -- the heads in place (PR 59): ``ops/pallas/mla_pack.py`` behind ``_Heads`` ----

#: lane-real widths (128 / 64 / 128: what the kernels take) at a small size
WH, WS, WHID, WRANK, WQRANK = 4, 256, 64, 32, 48


def forms_of(cell):
    """The layer's arguments in the two cells' forms: Ling-3.0's (a norm a
    head, a gate a head, a full-rank query) and Xing4.0's (a low-rank query,
    YaRN's tables, the scores' multiplier; no norm, no gate)."""
    from hetu_tpu.ops.rotary import yarn_scaling
    return {"ling": dict(qk_norm=True, head_gate=True),
            "xing4": dict(qk_norm=False, head_gate=False, q_lora_rank=WQRANK,
                          rope_scaling=yarn_scaling(64.0, 16, 32.0, 1.0, 1.0),
                          softmax_scale_mult=2.0047)}[cell]


@pytest.fixture
def asked(monkeypatch):
    """The layer asks for its kernels as it does on a TPU, and gets them in
    interpret mode (``dispatch.take(asked=True)``)."""
    import types
    from hetu_tpu.layers import latent_attention as forms
    from hetu_tpu.ops.pallas import dispatch
    monkeypatch.setattr(forms, "dispatch", types.SimpleNamespace(
        take=lambda kernel, mesh, why:
        dispatch.take(kernel, mesh, why, asked=True)))
    return lambda: monkeypatch.setattr(forms, "dispatch", dispatch)


def wide_program(name, cell, dims=(128, 64, 128)):
    """The layer, a loss over its output and every gradient, the weights
    drawn by their names' ends (two programs of two names hold the same)."""
    import zlib
    from hetu_tpu.graph.node import VariableOp, find_topo_sort
    layer = LatentAttention(WHID, WH, WRANK, *dims, rope_theta=1e4, name=name,
                            **forms_of(cell))
    x = ht.placeholder_op(f"{name}_x", (1, WS, WHID))
    y = layer(x)
    loss = ht.reduce_sum_op(y * y, axes=None)
    variables = [n for n in find_topo_sort([loss])
                 if isinstance(n, VariableOp)]
    ex = ht.Executor({"forward": [y],
                      "grads": [loss] + ht.gradients(loss, variables)},
                     seed=0)
    for key, value in list(ex.params.items()):
        r = np.random.default_rng(zlib.crc32(key[len(name):].encode()))
        scale = 0.2 if key.endswith("_scale") else value.shape[0] ** -0.5
        ex.params[key] = jnp.asarray(
            key.endswith("_scale") + scale * r.normal(size=value.shape),
            value.dtype)
    feed = {x: np.random.default_rng(3).normal(size=(1, WS, WHID)).astype(
        np.float32)}
    return ex, feed, [v.name[len(name):] for v in variables]


def pack_choices():
    from hetu_tpu.ops.pallas import dispatch
    return {k[1:]: n for k, n in dispatch.choices().items()
            if k[0] == "mla_pack"}


def layouts():
    from test_attention_layout import layouts_built
    return layouts_built()


@pytest.mark.parametrize("cell", ["ling", "xing4"])
def test_the_layer_in_place_is_the_layer_by_heads(asked, live_registry, cell):
    """The layer through the executor, its output and every weight's
    gradient, with the kernel pairs asked for (interpret mode: q and k at a
    stride of 256 lanes a head, the attention op on ``[B, S, H x 256]`` and
    ``[B, S, H x 128]``, the context flat into the gate and ``W_o``) and on
    the views by heads: the same numbers."""
    chosen, built = pack_choices(), layouts()
    ex, feed, names = wide_program(f"mla_in_{cell}", cell)
    got = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    got += ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    taken = pack_choices()
    assert set(taken) == {("pallas", "")} | set(chosen)
    assert taken[("pallas", "")] >= chosen.get(("pallas", ""), 0) + 2
    assert layouts().get(("bshd", "latent_in_place"), 0) >= built.get(
        ("bshd", "latent_in_place"), 0) + 2
    asked()                              # the real ``dispatch`` back
    ex, feed, _ = wide_program(f"mla_by_{cell}", cell)
    want = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    want += ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert pack_choices() == taken       # off a TPU, unasked: nothing recorded
    assert layouts()[("bhsd", "latent_no_mosaic")] >= 2
    assert len(got) == len(want) == 2 + len(names)
    for name, g, w in zip(["y", "loss"] + names, got, want):
        assert np.abs(w).max() > 0, name
        assert np.abs(g - w).max() < 2e-5 * max(1.0, np.abs(w).max()), name


@pytest.mark.parametrize("cell", ["ling", "xing4"])
def test_other_head_sizes_asked_for_say_why_and_run_by_heads(
        asked, live_registry, cell):
    """Where the path is not taken (a toy's heads of 32 + 16 over values of
    24) the layer is, to the bit, the layer it was: asked for the kernels it
    records the refusal and runs ``_queries``, ``_keys`` and ``_values``."""
    before = pack_choices().get(("jnp", "nope_dim_not_128"), 0)
    ex, feed, _ = wide_program(f"mla_toy_{cell}", cell, (DN, DR, DV))
    got = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert pack_choices()[("jnp", "nope_dim_not_128")] > before
    assert layouts()[("bhsd", "latent_nope_dim_not_128")] >= 1
    asked()
    ex, feed, _ = wide_program(f"mla_toy_un_{cell}", cell, (DN, DR, DV))
    want = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    assert float(got[0]).hex() == float(want[0]).hex()


@pytest.mark.parametrize("normed", [True, False])
def test_the_spare_lanes_are_exact_zeros_both_ways(normed):
    """``q^`` and ``k^`` hold a head's 192 and 64 exact zeros, the values are
    ``c W_kvb``'s lanes, and what flash hands back for ``q^`` and ``k^`` (read
    in place: ``bshd`` at 256 / 128) is zero on the same lanes; the context
    and the three gradients are those of the call by heads at 192 / 128."""
    from hetu_tpu import telemetry
    from hetu_tpu.ops.pallas import mla_pack
    from hetu_tpu.ops.rotary import _pair_tables
    r = np.random.default_rng(5)
    x = jnp.asarray(r.normal(size=(1, WS, WH * 192)), jnp.float32)
    kvb = jnp.asarray(r.normal(size=(1, WS, WH * 256)), jnp.float32)
    rope = jnp.asarray(r.normal(size=(1, WS, 64)), jnp.float32)
    w = jnp.asarray(1 + 0.2 * r.normal(size=(192,)), jnp.float32)
    tables = mla_pack.tables(_pair_tables(seq_len=WS, dim=128, theta=1e4,
                                          rotary_dim=64))
    q = mla_pack.queries(x, tables, w if normed else None, WH, 1e-6)
    k, v = mla_pack.keys_values(kvb, rope, tables, w if normed else None, WH,
                                1e-6)
    by_heads = lambda t: t.reshape(1, WS, WH, -1)
    for t in (q, k):
        assert t.shape == (1, WS, WH * 256)
        assert (np.asarray(by_heads(t))[..., 192:] == 0).all()
        assert np.abs(np.asarray(by_heads(t))[..., :192]).min() > 0
    np.testing.assert_array_equal(np.asarray(by_heads(v)),
                                  np.asarray(by_heads(kvb))[..., 128:])
    g = jnp.asarray(r.normal(size=v.shape), jnp.float32)
    flat = lambda *a: jnp.sum(flash_attention(
        *a, causal=True, scale=192 ** -0.5, num_heads=WH) * g)
    telemetry.enable()
    try:
        before = entries().get(("bshd_v128", 1), 0)
        out = flash_attention(q, k, v, causal=True, scale=192 ** -0.5,
                              num_heads=WH)
        assert entries().get(("bshd_v128", 1), 0) == before + 1
    finally:
        telemetry.disable()
    got = jax.grad(flat, argnums=(0, 1, 2))(q, k, v)
    for t in got[:2]:
        assert (np.asarray(by_heads(t))[..., 192:] == 0).all()
    split = lambda t, d: by_heads(t)[..., :d].transpose(0, 2, 1, 3)
    q4, k4, v4 = split(q, 192), split(k, 192), split(v, 128)
    g4 = split(g, 128)
    want_out = flash_attention(q4, k4, v4, causal=True, scale=192 ** -0.5)
    want = jax.grad(lambda *a: jnp.sum(flash_attention(
        *a, causal=True, scale=192 ** -0.5) * g4), argnums=(0, 1, 2))(
            q4, k4, v4)
    assert np.abs(np.asarray(split(out, 128) - want_out)).max() < 1e-6
    for a, b, d in zip(got, want, (192, 192, 128)):
        assert np.abs(np.asarray(split(a, d) - b)).max() < 1e-5


@pytest.mark.parametrize("cell,layers_", [
    ("ling-3.0-flash-vl.b1-s8192", 1), ("xing4.0-29b-a4b.b1-s4096", 3)])
def test_the_cells_toys_at_heads_of_192_over_128_go_in_place(
        asked, live_registry, cell, layers_):
    """The Ling-3.0 toy (one latent layer behind a KDA layer, whole layers
    recomputed) and the Xing4.0 toy (two layers and the MTP depth's, low-rank
    queries, YaRN, hyper-connected streams) at the cells' head sizes, 128 +
    64 over 128: every latent layer is traced in place through the kernel
    pairs, the program is as near its cell's plain reference as the toy's
    limits ask, a train step runs, and the harness's own reading of the
    kernels chosen finds no ``jax.numpy`` form the platform does not
    explain."""
    from test_attention_layout import toy
    built = layouts()
    program, mix = toy(cell, qk_nope_head_dim=128, qk_rope_head_dim=64,
                       v_head_dim=128)
    try:
        feed, = program.make_batches(59, 1)
        want = program.reference_loss(feed, int(mix["reference_chunk"]))
        got = program.eval_loss(feed)
        for term, limit in mix["reference_tolerance"].items():
            assert abs(got[term] - want[term]) < limit, (term, got, want)
        assert np.isfinite(program.step(feed))
        _, fallbacks = program.kernel_choices()
        assert fallbacks == []
        now = layouts()
        assert now.get(("bshd", "latent_in_place"), 0) >= built.get(
            ("bshd", "latent_in_place"), 0) + 2 * layers_
        assert {k: n for k, n in now.items() if k[0] == "bhsd"
                and k[1].startswith("latent")} == {
                    k: n for k, n in built.items() if k[0] == "bhsd"
                    and k[1].startswith("latent")}
    finally:
        program.close()
