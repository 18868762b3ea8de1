"""The causal convolution's Pallas kernel pair (``hetu_tpu/ops/pallas/
causal_conv.py``), in interpret mode on the CPU, against ``causal_conv_jnp``:
values and every gradient with and without a bias, in bf16 and f32, at four
taps, at two and at one; sequences of several tiles and chunks (the carry
across a tile's edge, zeros before position 0, the reversed walk's carry); a
window read in place out of a wider array; batch 2; the rule by which
``causal_conv`` takes the kernels, and the node that reads the mesh.  (The kernels compiled
for a described v5e at the cells' shapes: ``tests/test_flash_attention.py``,
where the other such compiles are.)"""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.ops import causal_conv as op
from hetu_tpu.ops.causal_conv import causal_conv, causal_conv_jnp
from hetu_tpu.ops.pallas import causal_conv as kernels, dispatch


def conv_inputs(B, S, C, K=4, bias=True, dtype=jnp.float32, wide=0, seed=0):
    """``x [B, S, wide + C + wide]`` standard normal, taps and bias uniform
    within ``1 / sqrt(K)`` as the layers draw them, a cotangent for ``y``,
    and the window (None where ``x`` is as wide as the taps)."""
    r = np.random.default_rng(seed)
    bound = K ** -0.5
    x = jnp.asarray(r.normal(size=(B, S, C + 2 * wide)), dtype)
    w = jnp.asarray(r.uniform(-bound, bound, size=(K, C)), dtype)
    b = jnp.asarray(r.uniform(-bound, bound, size=(C,)), dtype) if bias \
        else None
    dy = jnp.asarray(r.normal(size=(B, S, C)), dtype)
    return x, w, b, dy, ((wide, wide + C) if wide else None)


def grads(fn, x, w, b, dy, window):
    """``(dx, dw[, db])`` of ``sum(fn(x, w, b, window) * dy)``."""
    if b is None:
        return jax.vjp(lambda x, w: fn(x, w, None, window), x, w)[1](dy)
    return jax.vjp(lambda x, w, b: fn(x, w, b, window), x, w, b)[1](dy)


def rel(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


def l2_gap(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum())


def close(got, want, dtype, what):
    """f32: the same sums in the same order, to rounding.  bf16: both forms
    round an f32 result once, so they differ by a bf16 step in a few entries
    (``tests/test_ssd_kernel.py`` holds the scan's kernels to 8e-3 of the
    largest entry and 4e-3 in L2)."""
    assert got.shape == want.shape and got.dtype == want.dtype, what
    if dtype == jnp.float32:
        assert rel(got, want) < 2e-6, what
    else:
        assert rel(got, want) < 8e-3 and l2_gap(got, want) < 4e-3, what


@pytest.mark.parametrize("K", [4, 2, 1])
@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernels_are_the_jnp_form(dtype, bias, K):
    """Values and the gradient of every operand through the public entry,
    ``kernels.conv``, at its own tile sizes: 80 positions are five chunks of
    16 rows at 384 lanes."""
    x, w, b, dy, _ = conv_inputs(1, 80, 384, K, bias, dtype)
    close(kernels.conv(x, w, b), causal_conv_jnp(x, w, b), dtype, "y")
    got = grads(kernels.conv, x, w, b, dy, None)
    want = grads(causal_conv_jnp, x, w, b, dy, None)
    assert len(got) == (3 if bias else 2)
    for name, g, t in zip(("dx", "dw", "db"), got, want):
        close(g, t, dtype, name)


def through_small_tiles(x, w, b, dy, window, tile, chunk):
    """The two kernel entries at a tile of ``tile`` bytes and a chunk of
    ``chunk`` elements, 128 lanes wide, and what ``conv``'s backward rule
    makes of their sums."""
    lo, hi = window or (0, x.shape[-1])
    kw = dict(lo=lo, width=hi - lo, interpret=True, lanes=128, tile=tile,
              chunk=chunk)
    b2 = None if b is None else b.reshape(1, -1)
    y = kernels.hetu_conv_fwd(x, w, b2, **kw)
    dx, dw, db = kernels.hetu_conv_bwd(x, w, b2, dy, **kw)
    return y, dx, dw.sum(1).astype(w.dtype), (
        None if b is None else db.sum(0).astype(b.dtype))


@pytest.mark.parametrize("B,S,tile,chunk,dtype", [
    (1, 128, 32 * 512, 16 * 128, jnp.float32),    # 4 tiles of 2 chunks
    (1, 96, 16 * 512, 16 * 128, jnp.float32),     # 6 tiles of one chunk
    (2, 128, 64 * 256, 32 * 128, jnp.bfloat16),   # 2 sequences, 2 tiles each
    (2, 192, 64 * 256, 16 * 128, jnp.bfloat16),   # 3 tiles of 4 chunks
    (1, 64, 64 * 256, 64 * 128, jnp.bfloat16),    # one tile, one chunk
])
def test_the_carry_across_tiles_and_chunks(B, S, tile, chunk, dtype):
    """A sequence cut into several tiles and a tile into several chunks, two
    channel tiles: the ``K - 1`` rows before a chunk come from the chunk
    before it, across a tile's edge from the scratch the tile before left and
    as zeros before position 0; in the backward pass, walked from the last
    tile to the first, the rows of ``x`` before a tile come as its halo and
    the rows of ``dpre`` after a chunk from the chunk after it, zeros after
    the last position; ``dw`` and ``db`` are sums over every tile and both
    sequences of the batch."""
    x, w, b, dy, _ = conv_inputs(B, S, 256, 4, True, dtype, seed=1)
    y, dx, dw, db = through_small_tiles(x, w, b, dy, None, tile, chunk)
    close(y, causal_conv_jnp(x, w, b), dtype, "y")
    for name, g, t in zip(("dx", "dw", "db"), (dx, dw, db),
                          grads(causal_conv_jnp, x, w, b, dy, None)):
        close(g, t, dtype, name)


def test_a_position_reads_nothing_after_it_and_zeros_before_the_first():
    """Causality, and the left edge, read off the kernels alone: an impulse at
    position ``t`` of one sequence moves ``y`` at ``t .. t + K - 1`` of that
    sequence only, by the taps from the newest to the oldest."""
    K, S, t = 4, 64, 15                  # the impulse on a chunk's last row
    x = jnp.zeros((2, S, 128), jnp.float32).at[1, t].set(1.0)
    w = jnp.asarray(np.arange(1, K + 1)[:, None] * np.ones((1, 128)),
                    jnp.float32)
    kw = dict(lo=0, width=128, interpret=True, tile=16 * 512, chunk=16 * 128)
    y = np.asarray(kernels.hetu_conv_fwd(x, w, None, **kw))
    silu = lambda v: v / (1 + np.exp(-v))
    want = np.zeros((2, S))
    want[1, t:t + K] = silu(np.arange(K, 0, -1.0))
    np.testing.assert_allclose(y[..., 0], want, rtol=1e-6, atol=0)
    np.testing.assert_array_equal(y, y[..., :1] * np.ones(128))


@pytest.mark.parametrize("dtype,bias", [(jnp.float32, True),
                                        (jnp.bfloat16, True),
                                        (jnp.bfloat16, False)])
def test_a_window_is_read_in_place(dtype, bias):
    """The Mamba-2 layers' case: channels ``[lo, hi)`` of a wider array, ``lo``
    a multiple of 128 that the tile's lanes do not divide (128 of 384).  The
    result is the slice's, bit for bit, and the gradient of the wide array is
    the slice's gradient in its place with zeros around it."""
    x, w, b, dy, window = conv_inputs(2, 64, 384, 4, bias, dtype, wide=128,
                                      seed=2)
    lo, hi = window
    sliced = lambda x, w, b, window: kernels.conv(x[..., lo:hi], w, b)
    y = kernels.conv(x, w, b, window)
    np.testing.assert_array_equal(y, sliced(x, w, b, None))
    close(y, causal_conv_jnp(x, w, b, window), dtype, "y")
    got = grads(kernels.conv, x, w, b, dy, window)
    assert got[0].shape == x.shape
    for g, s in zip(got, grads(sliced, x, w, b, dy, None)):
        np.testing.assert_array_equal(g, s)
    for name, g, t in zip(("dx", "dw", "db"), got,
                          grads(causal_conv_jnp, x, w, b, dy, window)):
        close(g, t, dtype, name)
    assert not np.asarray(got[0][..., :lo]).any()
    assert not np.asarray(got[0][..., hi:]).any()


def test_sums_over_all_positions_are_f32_sums_cast_once():
    """``dw`` and ``db`` of bf16 operands: 2,048 positions of two sequences
    summed in f32 and rounded once are within a bf16 step of the f32 sums of
    the same products; a bf16 running sum would be percent off."""
    x, w, b, dy, _ = conv_inputs(2, 2048, 128, 4, True, jnp.bfloat16, seed=3)
    _, dw, db = grads(kernels.conv, x, w, b, dy, None)
    f32 = [t.astype(jnp.float32) for t in (x, w, b, dy)]
    _, dw32, db32 = grads(causal_conv_jnp, *f32, None)
    assert dw.dtype == db.dtype == jnp.bfloat16
    # (the f32 operands' dpre is not rounded where the kernels' is not either)
    assert rel(dw, dw32) < 8e-3 and rel(db, db32) < 8e-3


# -- the rule ------------------------------------------------------------------

def sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("why,x,taps,window", [
    (None, (1, 8192, 8192), 4, None),                # the Qwen3-Next cell
    (None, (1, 8192, 10304), 4, (4096, 10240)),      # the Nemotron-H cell
    (None, (1, 8192, 8512), 4, (4096, 8448)),        # the Granite cell
    (None, (3, 16, 128), 2, None),
    (None, (2, 48, 256), 8, (128, 256)),
    ("channels_not_128_aligned", (1, 64, 192), 4, None),
    ("channels_not_128_aligned", (1, 64, 384), 4, (64, 192)),   # lo
    ("channels_not_128_aligned", (1, 64, 384), 4, (128, 320)),  # width
    ("taps>8", (1, 64, 128), 9, None),
    (None, (1, 64, 128), 1, None),
    ("seq_not_16_aligned", (1, 200, 128), 4, None),
])
def test_unsupported_reads_its_operands(why, x, taps, window):
    lo, hi = window or (0, x[-1])
    assert kernels.unsupported(sds(x), sds((taps, hi - lo)), None,
                               window) == why


@pytest.mark.parametrize("dtype,why", [
    (jnp.bfloat16, None), (jnp.float32, None),
    (jnp.float16, "dtype:float16"), (jnp.float64, "dtype:float64"),
])
def test_unsupported_reads_the_type(dtype, why):
    assert kernels.unsupported(sds((1, 64, 128), dtype), sds((4, 128), dtype),
                               sds((128,), dtype)) == why


@pytest.fixture
def conv_choices(live_registry):
    """``{(impl, reason): count}`` of the rule's choices since the test
    began (the registry is the process's: ``conftest.live_registry``)."""
    before = dispatch.choices()

    def since():
        return {k[1:]: n - before.get(k, 0)
                for k, n in dispatch.choices().items()
                if k[0] == "causal_conv" and n > before.get(k, 0)}
    return since


def test_nothing_is_recorded_on_the_cpu(conv_choices, monkeypatch):
    """No Mosaic, no choice: the ``jax.numpy`` form runs, bit for bit, and the
    counter stays empty (the benchmark's rehearsal counts every ``jnp``
    sample it does not know as unexplained)."""
    monkeypatch.setattr(kernels, "conv", None)                # never reached
    x, w, b, _, window = conv_inputs(1, 64, 128, wide=128)
    np.testing.assert_array_equal(causal_conv(x, w, b, window),
                                  causal_conv_jnp(x, w, b, window))
    np.testing.assert_array_equal(causal_conv(x[..., 128:256], w),
                                  causal_conv_jnp(x, w, None, window))
    assert conv_choices() == {}


@pytest.mark.parametrize("why,x,window", [
    (None, (1, 8192, 8192), None),
    (None, (1, 8192, 10304), (4096, 10240)),
    ("channels_not_128_aligned", (2, 64, 160), None),
    ("seq_not_16_aligned", (1, 100, 256), (128, 256)),
])
def test_rule_reads_its_operands_as_on_tpu(conv_choices, monkeypatch, why, x,
                                           window):
    """With the platform patched to ``tpu``: the kernels where the rule takes
    the operands, else the ``jax.numpy`` form with its reason; one sample a
    call."""
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    taken = []
    monkeypatch.setattr(kernels, "conv", lambda *a: taken.append(a) or
                        causal_conv_jnp(*a))
    lo, hi = window or (0, x[-1])
    jax.eval_shape(lambda x, w: causal_conv(x, w, None, window), sds(x),
                   sds((4, hi - lo)))
    if why is None:
        assert len(taken) == 1 and conv_choices() == {("pallas", ""): 1}
    else:
        assert not taken and conv_choices() == {("jnp", why): 1}


# -- the layers through the kernels -----------------------------------------------

def layer_loss_and_grads(kind, through_kernels, monkeypatch):
    """Loss and every weight's gradient of one mixer whose convolution is 128
    lanes wide (and, for Mamba-2, a window at lane 128 of the projection's
    output), through the executor."""
    import hetu_tpu as ht
    from hetu_tpu.graph.node import graph_variables
    from hetu_tpu.layers.gated_delta_net import GatedDeltaNet
    from hetu_tpu.layers.mamba2 import Mamba2
    taken = []
    if through_kernels:
        monkeypatch.setattr(op, "causal_conv", lambda *a, **k: taken.append(k)
                            or kernels.conv(*a, **k))
    name = f"cck_{kind}_{int(through_kernels)}"
    if kind == "ssm":
        layer = Mamba2(32, 8, 16, 1, 64, name=name)    # xBC: 128 + 2 x 64
    else:
        layer = GatedDeltaNet(32, 2, 4, 16, 16, name=name)    # 2 x 32 + 64
    x = ht.placeholder_op(f"{name}_x", (2, 48, 32))
    loss = ht.reduce_sum_op(ht.sin_op(layer(x)), axes=[0, 1, 2])
    variables = graph_variables([loss], trainable_only=True)
    ex = ht.Executor({"grads": [loss] + ht.gradients(loss, variables)},
                     seed=3)
    r = np.random.default_rng(5)
    for var in variables:           # the same weights for both, off their
        value = ex.params[var.name]     # initial ones and zeros
        ex.params[var.name] = jnp.asarray(
            r.normal(0.2 if len(var.shape) == 1 else 0.0, 0.1, var.shape),
            value.dtype)
    feed = {x: r.normal(size=(2, 48, 32)).astype(np.float32)}
    out = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert taken == [{"window": (128, 384) if kind == "ssm" else None}
                     ] * through_kernels
    return out[0], out[1:]


@pytest.mark.parametrize("kind", ["ssm", "gdn"])
def test_layer_through_the_kernels_is_the_layer(kind, monkeypatch):
    """Loss and the gradient of every weight, f32, the layers' own nodes."""
    l1, g1 = layer_loss_and_grads(kind, False, monkeypatch)
    l2, g2 = layer_loss_and_grads(kind, True, monkeypatch)
    assert abs(float(l2 - l1)) < 1e-5 * abs(float(l1))
    assert len(g1) == len(g2) == (8 if kind == "ssm" else 7)
    for a, b in zip(g2, g1):
        assert a.shape == b.shape and np.abs(b).max() > 0
        assert np.abs(a - b).max() < 1e-4 * np.abs(b).max()


# -- no activation (compressed convolutional attention's depthwise taps) ------

@pytest.mark.parametrize("K", [2, 4])
@pytest.mark.parametrize("bias", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_without_an_activation_the_form_is_the_shifted_sum(dtype, bias, K):
    """``act=None``: ``y_t = sum_j w_j x_(t - K + 1 + j) + b`` in f32, one
    cast; its gradients are the shifted sum's."""
    x, w, b, dy, _ = conv_inputs(2, 48, 128, K=K, bias=bias, dtype=dtype)
    f32 = jnp.float32

    def plain(x, w, b):
        xp = jnp.pad(x.astype(f32), ((0, 0), (K - 1, 0), (0, 0)))
        y = sum(xp[:, j:j + 48] * w[j].astype(f32) for j in range(K))
        return (y if b is None else y + b.astype(f32)).astype(x.dtype)
    got = causal_conv_jnp(x, w, b, None, None)
    np.testing.assert_array_equal(got, plain(x, w, b))
    assert np.abs(np.asarray(got - causal_conv_jnp(x, w, b), f32)).max() > 0.1
    mine = grads(lambda x, w, b, window: causal_conv_jnp(x, w, b, window,
                                                         None), x, w, b, dy,
                 None)
    want = grads(lambda x, w, b, window: plain(x, w, b), x, w, b, dy, None)
    for g, wnt in zip(mine, want):
        close(g, wnt, dtype, "act=None")


def test_the_kernels_answer_act_none_and_the_form_runs_with_that_reason(
        conv_choices, monkeypatch):
    """The kernel pair applies SiLU: without an activation it answers
    ``act:none`` whatever the operands, and on a TPU ``causal_conv`` then runs
    the ``jax.numpy`` form with that reason recorded, one sample a call."""
    assert kernels.unsupported(sds((1, 8192, 1280)), sds((2, 1280)),
                               sds((1280,)), None, None) == "act:none"
    assert kernels.unsupported(sds((1, 8192, 1280)), sds((2, 1280)),
                               sds((1280,)), None, "silu") is None
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    monkeypatch.setattr(kernels, "conv", None)                # never reached
    x, w, b, _, _ = conv_inputs(1, 64, 128, K=2)
    np.testing.assert_array_equal(causal_conv(x, w, b, act=None),
                                  causal_conv_jnp(x, w, b, None, None))
    assert conv_choices() == {("jnp", "act:none"): 1}


def test_a_node_with_silu_is_the_node_it_was():
    """``ConvOp(scope, x, w, b, window=)`` builds the node it built before
    ``act`` existed (the four cells that call it: no new attribute), and
    computes what it computed, to the bit; ``act=None`` is one more
    attribute."""
    import hetu_tpu as ht
    x_v, w_v, b_v, _, window = conv_inputs(1, 32, 128, wide=128)
    x = ht.placeholder_op("conv_node_x", x_v.shape)
    w = ht.Variable("conv_node_w", shape=w_v.shape,
                    initializer=ht.init.zeros())
    b = ht.Variable("conv_node_b", shape=b_v.shape,
                    initializer=ht.init.zeros())
    silu = op.ConvOp("hetu_gdn_conv", x, w, b, window=window)
    bare = op.ConvOp("hetu_gdn_conv", x, w, b, window=window, act=None)
    assert silu.attrs == {"window": window}
    assert bare.attrs == {"window": window, "act": None}
    ex = ht.Executor({"forward": [silu, bare]}, seed=0)
    ex.params[w.name], ex.params[b.name] = w_v, b_v
    got = ex.run("forward", feed_dict={x: np.asarray(x_v)},
                 convert_to_numpy_ret_vals=True)
    np.testing.assert_allclose(got[0], causal_conv_jnp(x_v, w_v, b_v, window),
                               atol=1e-6)
    np.testing.assert_allclose(
        got[1], causal_conv_jnp(x_v, w_v, b_v, window, None), atol=1e-6)
