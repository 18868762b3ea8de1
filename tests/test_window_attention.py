"""Attention over a window (``ops/attention.py WindowAttentionOp``, the flash
kernels under ``hetu_swa_fwd`` / ``hetu_swa_bwd``), YaRN's rotary tables
(``ops/rotary.py yarn_scaling``) and what they count: against an explicit
mask ``0 <= i - j < w`` and against the plain reference's table, on the CPU
(the kernels in interpret mode)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.ops import rotary
from hetu_tpu.ops.attention import (ScaledDotProductAttentionOp,
                                    WindowAttentionOp,
                                    scaled_dot_product_attention_op)
from hetu_tpu.ops.pallas import flash_attention as fa

from chipbench.reference import laguna as ref


def masked(q, k, v, window):
    """Attention under the explicit mask, ``[B, H, S, D]``, in f32."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    gap = jnp.arange(q.shape[2])[:, None] - jnp.arange(q.shape[2])[None, :]
    seen = (gap >= 0) & (gap < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


def operands(heads, kv, seq, dim=32, seed=0):
    """q at ``heads`` heads and k, v at ``kv`` key heads repeated for their
    query heads, as ``repeat_kv_op`` hands them over."""
    r = np.random.default_rng(seed)
    q = jnp.asarray(r.normal(0, 1, (1, heads, seq, dim)), jnp.float32)
    k, v = (jnp.repeat(jnp.asarray(r.normal(0, 1, (1, kv, seq, dim)),
                                   jnp.float32), heads // kv, axis=1)
            for _ in range(2))
    return q, k, v


def weigh(o):
    return (o * jnp.cos(jnp.arange(o.size, dtype=jnp.float32)
                        ).reshape(o.shape)).sum()


#: (sequence, window, (block_q, block_k)): the band's edge inside a block, on
#: a block's border, past the first row of blocks, a key block narrower than
#: the query block, and the published 512 at its planned blocks
PLANS = [(512, 100, (128, 128)), (512, 128, (128, 128)),
         (512, 129, (128, 128)), (512, 300, (128, 128)),
         (1024, 300, (256, 128)), (1024, 512, (512, 512)),
         (1024, 1, (256, 256))]


#: every plan at a group of 2; groups of 6 and of 8 at the first and the last
CASES = ([plan + (2, 1) for plan in PLANS]
         + [PLANS[0] + (6, 1), PLANS[0] + (8, 1), PLANS[5] + (6, 1),
            PLANS[5] + (16, 2)])


@pytest.mark.parametrize("seq,window,blocks,heads,kv", CASES)
def test_the_kernels_against_an_explicit_mask(monkeypatch, seq, window,
                                              blocks, heads, kv):
    """Forward and both gradients (q; k and v summed over a key head's
    group of 6 or of 8 by the repeat's transpose)."""
    monkeypatch.setattr(fa, "WINDOW_BLOCKS", (blocks,))
    r = np.random.default_rng(1)
    q = jnp.asarray(r.normal(0, 1, (1, heads, seq, 32)), jnp.float32)
    k0, v0 = (jnp.asarray(r.normal(0, 1, (1, kv, seq, 32)), jnp.float32)
              for _ in range(2))

    def through(attend):
        def f(q, k0, v0):
            k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (k0, v0))
            return attend(q, k, v)
        return f
    kernel = through(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, window=window))
    plain = through(lambda q, k, v: masked(q, k, v, window))
    assert np.abs(kernel(q, k0, v0) - plain(q, k0, v0)).max() < 2e-5
    got = jax.grad(lambda *a: weigh(kernel(*a)), (0, 1, 2))(q, k0, v0)
    want = jax.grad(lambda *a: weigh(plain(*a)), (0, 1, 2))(q, k0, v0)
    for g, w in zip(got, want):
        assert np.abs(g - w).max() < 5e-5 * max(1.0, np.abs(w).max())


def test_in_place_heads_take_a_window_too():
    """The projections' ``[B, S, H d]`` read in place, two heads of 64 a
    program."""
    q, k, v = operands(4, 4, 256, dim=64)
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(1, 256, -1)
    got = fa.flash_attention(flat(q), flat(k), flat(v), causal=True,
                             window=100, num_heads=4)
    assert np.abs(got - flat(masked(q, k, v, 100))).max() < 2e-5


@pytest.mark.parametrize("rep", [1, 6, 8])
@pytest.mark.parametrize("seq,window", [(512, None), (2048, 512)])
def test_key_heads_read_in_place_are_the_repeated_ones(live_registry, seq,
                                                       window, rep):
    """q ``[B, S, H d]`` on k, v ``[B, S, KV d]`` (two key heads of 128, each
    under ``rep`` query heads), causal and under the published window (its
    band cut out by element at the planned blocks), against the same kernels
    on ``[B, H, S, D]`` behind ``repeat_kv``: the context and dq, dk, dv, the
    last two summed over each group outside the backward kernel."""
    kv, d = 2, 128
    heads = kv * rep
    r = np.random.default_rng(rep)
    q = jnp.asarray(r.normal(0, 1, (1, heads, seq, d)), jnp.float32)
    k, v = (jnp.asarray(r.normal(0, 1, (1, kv, seq, d)), jnp.float32)
            for _ in range(2))
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(1, seq, -1)

    def repeated(q, k, v):
        return flat(fa.flash_attention(
            q, rotary._repeat_kv(k, n_rep=rep), rotary._repeat_kv(v, n_rep=rep),
            causal=True, window=window))

    def in_place(q, k, v):
        return fa.flash_attention(flat(q), flat(k), flat(v), causal=True,
                                  window=window, num_heads=heads)
    before = fa.entries()
    want, got = repeated(q, k, v), in_place(q, k, v)
    assert got.shape == (1, seq, heads * d)
    assert np.abs(got - want).max() < 1e-6
    layout = ("bshd" + ("" if window is None else f"_w{window}")
              + ("" if rep == 1 else f"_kv{kv}"), 1)
    assert fa.entries().get(layout, 0) == before.get(layout, 0) + 1
    gw = jax.grad(lambda *a: weigh(repeated(*a)), (0, 1, 2))(q, k, v)
    gg = jax.grad(lambda *a: weigh(in_place(*a)), (0, 1, 2))(q, k, v)
    for what, a, b in zip(("dq", "dk", "dv"), gg, gw):
        assert a.shape == b.shape, what
        assert np.abs(a - b).max() < 2e-5 * max(1.0, np.abs(b).max()), what


def test_grouped_keys_come_in_place_alone():
    """``[B, H, S, D]`` callers (the ring, Galvatron) own one head count; key
    heads narrower than whole lane tiles are not cut out in place."""
    q = jnp.zeros((1, 4, 256, 64))
    assert fa.flash_attention(q, q[:, :2], q[:, :2], causal=True) is None
    flat = jnp.zeros((1, 256, 4 * 64))
    assert fa.flash_attention(flat, flat[..., :128], flat[..., :128],
                              causal=True, num_heads=4) is None
    assert fa.flash_attention(flat, flat, flat, causal=True,
                              num_heads=4) is not None


def test_a_window_that_holds_every_key_is_no_window(live_registry):
    q, k, v = operands(2, 2, 256)
    before = fa.entries()
    out = fa.flash_attention(q, k, v, causal=True, window=256)
    assert np.abs(out - masked(q, k, v, 256)).max() < 2e-5
    new = {key for key, n in fa.entries().items() if n > before.get(key, 0)}
    assert new == {("bhsd", 1)}            # no ``_w256``: the causal kernels
    fa.flash_attention(q, k, v, causal=True, window=255)
    assert ("bhsd_w255", 1) in fa.entries()


def test_the_kernels_names_say_which_kind():
    q = jax.ShapeDtypeStruct((1, 2, 256, 32), jnp.float32)

    def text(window):
        return str(jax.make_jaxpr(jax.grad(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=window).sum(), (0, 1, 2)))(q, q, q))
    with_window, without = text(64), text(None)
    assert "hetu_swa_fwd" in with_window and "hetu_swa_bwd" in with_window
    assert "hetu_flash" not in with_window
    assert "hetu_flash_fwd" in without and "hetu_swa" not in without


def test_what_is_not_built_is_refused():
    q = jax.ShapeDtypeStruct((1, 2, 256, 32), jnp.float32)
    assert fa.unsupported(q, q, q, None, 0.9, 64) == "window_with_dropout"
    assert fa.unsupported(q, q, q, None, 1.0, 64) is None
    x = ht.placeholder_op("wa_refused", (1, 2, 256, 32))
    with pytest.raises(AssertionError, match="dropout"):
        scaled_dot_product_attention_op(x, x, x, causal=True, window=64,
                                        dropout_keep=0.9)
    with pytest.raises(AssertionError, match="window"):
        scaled_dot_product_attention_op(x, x, x, causal=False, window=64)
    node = scaled_dot_product_attention_op(x, x, x, causal=True, window=64)
    import types
    ctx = types.SimpleNamespace(
        mesh=types.SimpleNamespace(shape={"cp": 2}), training=False)
    arr = jnp.zeros((1, 2, 256, 32))
    with pytest.raises(NotImplementedError, match="context-parallel"):
        node._attend(arr, arr, arr, None, ctx, None)


@pytest.mark.parametrize("window", [1, 16, 40, 64, 100])
@pytest.mark.parametrize("layout", ["bhsd", "bshd"])
def test_the_node_against_an_explicit_mask(window, layout):
    """The ``jax.numpy`` form (what the CPU runs), forward and the gradients
    of q, k and v, in both layouts; a window past the sequence is causal."""
    heads, seq, dim = 3, 64, 16
    q, k, v = operands(heads, 3, seq, dim, seed=2)
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(1, seq, -1)
    if layout == "bshd":
        feed, shape, kw = [flat(x) for x in (q, k, v)], (1, seq, heads * dim), \
            {"num_heads": heads}
    else:
        feed, shape, kw = [q, k, v], (1, heads, seq, dim), {}
    nodes = [ht.placeholder_op(f"wa_{layout}{window}_{n}", shape)
             for n in "qkv"]
    out = scaled_dot_product_attention_op(*nodes, causal=True, window=window,
                                          **kw)
    assert type(out) is WindowAttentionOp and out.window == window
    assert isinstance(out, ScaledDotProductAttentionOp)
    weight = np.cos(np.arange(np.prod(shape), dtype=np.float32)).reshape(shape)
    loss = ht.reduce_sum_op(out * ht.Variable(
        f"wa_{layout}{window}_w", value=weight, trainable=False))
    ex = ht.Executor([out] + ht.gradients(loss, nodes), seed=0)
    got = ex.run(feed_dict=dict(zip(nodes, map(np.asarray, feed))),
                 convert_to_numpy_ret_vals=True)
    back = (lambda x: x) if layout == "bhsd" else flat
    want_out = back(masked(q, k, v, window))
    assert np.abs(got[0] - want_out).max() < 2e-5
    want = jax.grad(lambda *a: (back(masked(*a, window)) * weight).sum(),
                    (0, 1, 2))(q, k, v)
    for g, w in zip(got[1:], want):
        assert np.abs(g - back(w)).max() < 5e-5


# -- YaRN ----------------------------------------------------------------------

PUBLISHED = {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
             "original_max_position_embeddings": 4096, "beta_slow": 1,
             "beta_fast": 64, "attention_factor": 1.4158883083359672,
             "partial_rotary_factor": 0.5}


def test_yarns_table_is_the_references_at_the_published_parameters():
    """Two f32 computations of one frequency differ in the last bit, which
    8,192 radians make 1e-3 of a cosine: tight over the first positions,
    loose over all."""
    scaling = rotary.yarn_scaling(64, 4096, 64, 1, PUBLISHED[
        "attention_factor"])
    cos, sin = rotary._rope_tables(8192, 64, 500000.0, scaling=scaling)
    want_cos, want_sin = ref.rotary_tables(8192, 64, PUBLISHED)
    for got, want in ((cos, want_cos), (sin, want_sin)):
        np.testing.assert_allclose(got[:64], want[:64], atol=2e-5)
        np.testing.assert_allclose(got, want, atol=3e-3)
    # the blend: the fastest pairs turn as without scaling, the slowest 64
    # times slower, both 1.4159 long; the default factor is 0.1 ln 64 + 1
    plain = rotary._rope_tables(8192, 64, 500000.0)
    f = PUBLISHED["attention_factor"]
    np.testing.assert_allclose(cos[:, 0], plain[0][:, 0] * f, atol=2e-6)
    inv_last = 500000.0 ** (-62 / 64) / 64
    np.testing.assert_allclose(sin[:, 31], np.sin(np.arange(8192) * inv_last)
                               * f, atol=2e-5)
    assert rotary.yarn_scaling(64, 4096, 64, 1)[-1] == pytest.approx(f)
    assert np.abs(cos - plain[0] * f).max() > 0.5


def test_the_plain_table_is_what_it_was():
    cos, sin = rotary._rope_tables(128, 32, 10000.0)
    inv = 1.0 / 10000.0 ** (np.arange(0, 32, 2, dtype=np.float32) / 32)
    ang = np.outer(np.arange(128, dtype=np.float32), inv)
    np.testing.assert_array_equal(
        cos, jnp.cos(jnp.concatenate([ang, ang], -1)))
    np.testing.assert_array_equal(
        sin, jnp.sin(jnp.concatenate([ang, ang], -1)))


def test_a_models_tables_are_one_node_a_scaling():
    tables = rotary.RopeTables()
    scaling = rotary.yarn_scaling(64, 4096, 64, 1)
    plain, again = tables(64, 16, 1e4), tables(64, 16, 1e4)
    yarn, other = tables(64, 16, 1e4, scaling), tables(
        64, 16, 1e4, rotary.yarn_scaling(32, 4096, 64, 1))
    assert plain is again and yarn is not plain and other is not yarn
    assert "scaling" not in plain.attrs and yarn.attrs["scaling"] == scaling
    assert len(tables.nodes) == 3


@pytest.mark.parametrize("turned", [None, 8])
def test_rotary_with_yarn_on_a_part_of_a_head(turned):
    """``_rotary(scaling=, rotary_dim=)`` against the reference's ``rotate``
    with its own table."""
    p = dict(PUBLISHED, original_max_position_embeddings=32, beta_fast=8)
    scaling = rotary.yarn_scaling(64, 32, 8, 1, p["attention_factor"])
    x = jnp.asarray(np.random.default_rng(4).normal(0, 1, (1, 3, 40, 16)),
                    jnp.float32)
    got = rotary._rotary(x, theta=500000.0, rotary_dim=turned,
                         scaling=scaling)
    cos, sin = ref.rotary_tables(40, turned or 16, p)
    want = ref.rotate(x.transpose(0, 2, 1, 3), cos, sin).transpose(0, 2, 1, 3)
    assert np.abs(got - want).max() < 1e-5
    assert np.abs(got - rotary._rotary(
        x, theta=500000.0, rotary_dim=turned)).max() > 0.1


# -- what is counted -----------------------------------------------------------

def test_the_scope_the_counter_and_the_gauge(live_registry, monkeypatch):
    from hetu_tpu import telemetry
    from hetu_tpu.layers.attention import MultiHeadAttention

    def built():
        metric = telemetry.get_registry().snapshot().get(
            "hetu_attn_layers_total", {"samples": []})
        return {s["labels"]["kind"]: s["value"] for s in metric["samples"]}
    before = built()
    x = ht.placeholder_op("wa_scope_x", (1, 64, 32))
    kw = dict(sequence_length=64, causal_mask=True, num_kv_heads=1,
              head_dim=16, bias=False, output_gate="head", rope_theta=1e4)
    full = MultiHeadAttention(32, 2, name="wa_scope_full", **kw)(x, x, x)
    windowed = MultiHeadAttention(32, 4, window=16, name="wa_scope_win",
                                  **kw)(x, x, x)
    assert "hetu_window_attn" in ht.scopes() and "hetu_attn" in ht.scopes()
    assert full.scope == "hetu_attn" and windowed.scope == "hetu_window_attn"
    after = built()
    assert after["full"] - before.get("full", 0) == 1
    assert after["window"] - before.get("window", 0) == 1
    # the gauge: set where the kernel is planned
    share = fa._window_plan(8192, 512)[1]
    gauge = telemetry.get_registry().snapshot()[
        "hetu_attn_window_block_share"]["samples"][0]["value"]
    assert gauge == share
    bq, bk = fa.WINDOW_BLOCKS[0]
    visited = sum((lo + bq - 1) // bk + 1 - max(0, (lo - 511) // bk)
                  for lo in range(0, 8192, bq)) * bq * bk
    assert share == visited / (sum(range(1, 17)) * 512 * 512)
    assert 0.12 < share < 0.26
