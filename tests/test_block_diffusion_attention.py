"""Attention under the block-diffusion mask: ``ops/attention.py
block_diffusion_mask`` against the four rules written out, the flash kernels'
``bd`` path (``hetu_flash_fwd_bd`` / ``hetu_flash_bwd_bd``) in interpret mode
against the ``jax.numpy`` form, forward and the three gradients, at halves that
are not a multiple of the tile, blocks of 4 and 32, four-dimensional operands
and grouped queries 8:1 read in place; the tiles the kernels' loops walk
against the tiles that hold a visible pair (``hetu_flash_tiles``); what the
kernels refuse; the node and the layer (positions repeated); and the kernels
compiled by Mosaic at the SDAR cell's shape."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.layers.attention import MultiHeadAttention
from hetu_tpu.ops.attention import (ScaledDotProductAttentionOp,
                                    block_diffusion_mask,
                                    scaled_dot_product_attention_op)
from hetu_tpu.ops.pallas import flash_attention as fa
from hetu_tpu.ops.rotary import _rope_tables


# -- the mask -----------------------------------------------------------------

@pytest.mark.parametrize("tokens, block", [(8, 4), (24, 4), (64, 32), (6, 1)])
def test_the_mask_is_the_four_rules(tokens, block):
    got = np.asarray(block_diffusion_mask(2 * tokens, block))
    for p in range(2 * tokens):
        for r in range(2 * tokens):
            (qn, i), (kn, j) = divmod(p, tokens), divmod(r, tokens)
            bi, bj = i // block, j // block
            want = {(0, 0): bj <= bi, (1, 0): bj < bi, (1, 1): bj == bi,
                    (0, 1): False}[qn, kn]
            assert got[p, r] == want, (p, r)
    # L^2 + K L pairs of the 4 L^2, and every position sees something
    assert got.sum() == tokens * tokens + block * tokens
    assert got.any(1).all()


# -- the kernels against the jax.numpy form -----------------------------------

def plain(q, k, v, block, heads=None):
    """The ``jax.numpy`` form: dense scores under the mask, f32."""
    if heads is not None:
        B, S, W = q.shape
        d = W // heads
        rep = W // k.shape[-1]
        q = q.reshape(B, S, heads, d).transpose(0, 2, 1, 3)
        k, v = (jnp.repeat(t.reshape(B, S, heads // rep, d)
                           .transpose(0, 2, 1, 3), rep, 1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(block_diffusion_mask(s.shape[-1], block), s, -1e9)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    if heads is not None:
        o = o.transpose(0, 2, 1, 3).reshape(o.shape[0], o.shape[2], -1)
    return o


#: (q's shape, k's and v's shape, block, heads in place).  Halves of 192 and
#: 320 are not multiples of their tiles; 320 and 640 walk several tiles a half
CASES = {
    "bhsd_half192_k4": ((1, 2, 384, 32), (1, 2, 384, 32), 4, None),
    "bhsd_half320_k32": ((1, 2, 640, 32), (1, 2, 640, 32), 32, None),
    "bhsd_half512_k4": ((2, 1, 1024, 32), (2, 1, 1024, 32), 4, None),
    "gqa8_half320_k4": ((1, 640, 1024), (1, 640, 128), 4, 8),
    "gqa8_half384_k32": ((1, 768, 1024), (1, 768, 128), 32, 8),
}


@pytest.fixture(scope="module", params=CASES)
def case(request):
    q_shape, k_shape, block, heads = CASES[request.param]
    rng = np.random.default_rng(11)
    q, k, v, g = (jnp.asarray(rng.normal(size=s), jnp.float32)
                  for s in (q_shape, k_shape, k_shape, q_shape))
    with jax.default_matmul_precision("highest"):
        def kernel(q, k, v):
            out = fa.flash_attention(q, k, v, block_diffusion=block,
                                     num_heads=heads)
            assert out is not None
            return out
        got = kernel(q, k, v)
        want = plain(q, k, v, block, heads)
        grads = jax.grad(lambda *a: (kernel(*a) * g).sum(), (0, 1, 2))(
            q, k, v)
        wants = jax.grad(lambda *a: (plain(*a, block, heads) * g).sum(),
                         (0, 1, 2))(q, k, v)
    return dict(got=got, want=want, grads=grads, wants=wants)


def test_the_kernel_forward_is_the_jnp_form(case):
    assert case["got"].shape == case["want"].shape
    assert float(jnp.abs(case["want"]).max()) > 0.1
    assert float(jnp.abs(case["got"] - case["want"]).max()) < 2e-5


@pytest.mark.parametrize("which", range(3), ids=["dq", "dk", "dv"])
def test_the_kernel_gradients_are_the_jnp_forms(case, which):
    got, want = case["grads"][which], case["wants"][which]
    assert got.shape == want.shape and float(jnp.abs(want).max()) > 0.1
    assert float(jnp.abs(got - want).max()) < 5e-5 * max(
        1.0, float(jnp.abs(want).max()))


# -- the tiles walked are the tiles that hold a visible pair ------------------

@pytest.mark.parametrize("nh", [1, 2, 3, 16])
def test_no_tile_without_a_visible_pair_is_walked(nh):
    """``_bd_tiles`` counts the kernels' loops as they run (forward: the clean
    tiles before ``t``, the tile under the edge, a noised tile's own;
    backward the transpose) against the mask itself on whole tiles."""
    tile, block = 128, 4
    counts = fa._bd_tiles(nh)
    mask = np.asarray(block_diffusion_mask(2 * nh * tile, block))
    by_tile = mask.reshape(2 * nh, tile, 2 * nh, tile).any((1, 3))
    for which, (walked, visible) in counts.items():
        assert walked == visible == by_tile.sum(), which
    # and of the causal plan over the same 2 nh tiles, about half
    assert counts["forward"][0] == nh * (nh + 1) + nh
    assert counts["forward"][0] <= nh * (2 * nh + 1)


def test_the_gauge_says_walked_and_visible_at_the_last_plan():
    telemetry.enable()
    try:
        rows, tile, bd = fa._bd_plan(8192, 4)
        assert (rows, tile, bd) == (8192, 512, (4, 16))
        samples = telemetry.get_registry().snapshot()[
            "hetu_flash_tiles"]["samples"]
        got = {(s["labels"]["pass"], s["labels"]["tiles"]): s["value"]
               for s in samples if s["labels"]["mask"] == "block_diffusion"}
        assert got == {(w, t): 288.0 for w in ("forward", "backward")
                       for t in ("walked", "visible")}
    finally:
        telemetry.shutdown()


# -- what the kernels refuse --------------------------------------------------

def shapes(s=512, d=32):
    x = jax.ShapeDtypeStruct((1, 2, s, d), jnp.float32)
    return x, x, x


@pytest.mark.parametrize("kw, reason", [
    (dict(dropout_keep=0.9), "block_diffusion_with_dropout"),
    (dict(mask=jax.ShapeDtypeStruct((1, 1, 1, 512), jnp.float32)),
     "block_diffusion_with_mask"),
    (dict(block_diffusion=3), "block_not_a_power_of_two"),
    (dict(block_diffusion=128), "block>64"),
])
def test_unsupported_names_its_reason(kw, reason):
    kw = dict(dict(block_diffusion=4), **kw)
    assert fa.unsupported(*shapes(), **kw) == reason
    assert fa.unsupported(*shapes(), block_diffusion=4) is None
    assert fa.unsupported(*shapes(128), block_diffusion=4) == "half<128"


def test_the_node_refuses_a_second_mask_beside_the_block_mask():
    q = ht.placeholder_op("bdq", (1, 2, 256, 16))
    for kw in (dict(causal=True), dict(dropout_keep=0.9), dict(window=8,
                                                                causal=True)):
        with pytest.raises(AssertionError):
            scaled_dot_product_attention_op(q, q, q, block_diffusion=4, **kw)
    with pytest.raises(AssertionError):
        MultiHeadAttention(64, 4, block_diffusion=4, causal_mask=True)


# -- the node and the layer ---------------------------------------------------

def test_the_node_is_counted_by_its_kind_and_keeps_its_type():
    telemetry.enable()
    try:
        def built():
            metric = telemetry.get_registry().snapshot().get(
                "hetu_attn_layers_total", {"samples": []})
            return {s["labels"]["kind"]: s["value"]
                    for s in metric["samples"]}
        before = built().get("block_diffusion", 0)
        q = ht.placeholder_op("bdn", (1, 2, 16, 8))
        node = scaled_dot_product_attention_op(q, q, q, block_diffusion=4)
        # the flash passes' events are counted on the nodes of this type
        assert type(node) is ScaledDotProductAttentionOp
        assert (node.kind, node.block_diffusion, node.causal) == (
            "block_diffusion", 4, False)
        assert built()["block_diffusion"] == before + 1
        plain_node = scaled_dot_product_attention_op(q, q, q, causal=True)
        assert (plain_node.kind, plain_node.block_diffusion) == ("full", None)
    finally:
        telemetry.shutdown()


def test_the_node_on_the_cpu_is_the_mask_on_dense_scores():
    rng = np.random.default_rng(2)
    x = rng.normal(size=(1, 2, 32, 8)).astype(np.float32)
    q = ht.placeholder_op("bdc", x.shape)
    ex = ht.Executor([scaled_dot_product_attention_op(q, q, q,
                                                      block_diffusion=4)])
    got = ex.run(feed_dict={q: x}, convert_to_numpy_ret_vals=True)[0]
    want = plain(*(jnp.asarray(x),) * 3, 4)
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-6)


def test_both_copies_turn_at_the_same_positions():
    cos, sin = _rope_tables(16, 8, 1e4, copies=2)
    once = _rope_tables(8, 8, 1e4)
    for got, want in zip((cos, sin), once):
        np.testing.assert_array_equal(got[:8], want)
        np.testing.assert_array_equal(got[8:], want)
    with pytest.raises(AssertionError):
        _rope_tables(15, 8, 1e4, copies=2)


@pytest.mark.parametrize("head_dim, layout", [(16, "bhsd"), (128, "bshd")])
def test_the_layer_sees_a_copy_of_a_token_where_the_token_is(head_dim,
                                                             layout):
    """A layer under the block mask with rotary: feeding ``[x | x]`` (the
    noised copy equal to the clean one), the noised position ``i`` of block
    ``b`` attends clean blocks ``< b`` and the noised block ``b``, which here
    hold the same vectors at the same positions as the clean blocks ``<= b``
    that the clean position ``i`` attends: both copies' outputs are equal.
    With positions ``0 .. 2L - 1`` they would not be."""
    heads, kv, L = 4, 2, 16
    hidden = heads * head_dim
    layer = MultiHeadAttention(hidden, heads, num_kv_heads=kv,
                               head_dim=head_dim, rope_theta=1e4, bias=False,
                               block_diffusion=4, name=f"bdl{head_dim}")
    assert layer.layout()[0] == layout
    x = ht.placeholder_op(f"bdl{head_dim}_x", (1, 2 * L, hidden))
    ex = ht.Executor([layer(x, x, x, seq_len=2 * L)], seed=1)
    half = np.random.default_rng(4).normal(size=(1, L, hidden)).astype(
        np.float32)
    out = ex.run(feed_dict={x: np.concatenate([half, half], 1)},
                 convert_to_numpy_ret_vals=True)[0]
    assert np.abs(out).max() > 1e-3
    np.testing.assert_allclose(out[:, L:], out[:, :L], rtol=1e-4, atol=1e-6)


# -- Mosaic takes the kernels at the cell's shape ------------------------------

@pytest.mark.parametrize("shape, heads", [
    ((1, 32, 16384, 128), None),            # [B, H, 2L, d]: the cell to PR 62
    ((1, 2048, 1024), 8),                   # in place, grouped queries 8:1
    ((1, 16384, 4096), 32)])                # the SDAR cell, in place (PR 63)
def test_mosaic_compiles_both_kernels(one_chip, monkeypatch, shape, heads):
    from hetu_tpu.ops.pallas import dispatch
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    monkeypatch.setattr(fa, "interpret", lambda: False)
    jax.clear_caches()
    k_shape = shape if heads is None else shape[:2] + (shape[2] // 8,)
    q, k = (jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in (shape, k_shape))

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, block_diffusion=4,
                                  num_heads=heads).astype(jnp.float32).sum()
    try:
        with jax.default_device(None):
            text = jax.jit(jax.grad(loss, (0, 1, 2)),
                           in_shardings=(one_chip,) * 3).lower(
                q, k, k).compile().as_text()
    finally:
        jax.clear_caches()
    assert "hetu_flash_fwd_bd" in text and "hetu_flash_bwd_bd" in text
