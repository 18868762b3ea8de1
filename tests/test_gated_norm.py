"""The recurrent mixers' gated RMS norm as a Pallas kernel pair
(``hetu_tpu/ops/pallas/gated_norm.py``), in interpret mode on the CPU, against
the layers' ``jax.numpy`` forms (``layers/gated_delta_net.py _out``: norm a
value head, then gate; ``layers/mamba2.py _out``: gate, then norm a group):
values and every gradient in bf16 and f32 at groups of 128 and 512 lanes and at
one group over all channels, batch 2; blocks of several chunks, sequences the
block's rows do not divide and several lane blocks (the scale's cotangent is
summed over all of them); ``z`` read in place out of a wider array with NaN in
every other lane; the rule by which the node takes the kernels, on and off a
mesh and off a TPU; each layer through the executor with and without them.
(The kernels compiled for a described v5e at the cells' shapes:
``tests/test_flash_attention.py``, where the other such compiles are.)"""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.layers import gated_delta_net as gdn, mamba2 as ssm
from hetu_tpu.ops import gated_norm as op
from hetu_tpu.ops.gated_norm import OutOp, Window
from hetu_tpu.ops.pallas import dispatch, gated_norm as kernels

#: name -> (entry, channels, group width, lanes of the wide array, window):
#: DeltaNet's ``z`` is the last 2 x 128 lanes of each key head's 768 (or, at a
#: key head of 64 lanes and one value head a key head, 128 lanes at 128 of
#: 256); Mamba-2's the first ``C`` lanes of a projection that is no multiple
#: of 128 wide
CASES = {
    "gdn_128": ("gdn", 512, 128, 2 * 768, Window(512, 256, 768)),
    "gdn_128_rep1": ("gdn", 384, 128, 3 * 256, Window(128, 128, 256)),
    "ssm_512": ("ssm", 1024, 512, 1024 + 328, Window(0, 1024, 1024)),
    "ssm_128": ("ssm", 256, 128, 256 + 200, Window(0, 256, 256)),
    "ssm_one_group": ("ssm", 512, 512, 512 + 72, Window(0, 512, 512)),
}


def how(name):
    entry, _, width, _, window = CASES[name]
    return dict(width=width, gate_first=entry == "ssm",
                eps=1e-5 if entry == "ssm" else 1e-6, window=window)


def operands(name, B, S, dtype, seed=0):
    """``o``, the wide array that holds ``z``, the scale (a head's for
    DeltaNet, every channel's for Mamba-2; about one) and a cotangent."""
    entry, C, width, wide, _ = CASES[name]
    r = np.random.default_rng(seed)
    return (jnp.asarray(r.normal(size=(B, S, C)), dtype),
            jnp.asarray(r.normal(size=(B, S, wide)), dtype),
            jnp.asarray(r.normal(1.0, 0.1, size=(width if entry == "gdn"
                                                 else C,)), dtype),
            jnp.asarray(r.normal(size=(B, S, C)), dtype))


def layer_form(name):
    """The layer's ``_out`` on the slice of ``z``, its product with the
    identity: ``(o, wide, scale) -> y [B, S, C]``."""
    entry, C, width, _, window = CASES[name]

    def form(o, wide, scale):
        z = kernels.take(wide, window, C)
        eye = jnp.eye(C, dtype=o.dtype)
        if entry == "gdn":
            return gdn._out(o, z, scale, eye, eps=1e-6)
        return ssm._out(o, z, scale, eye, groups=C // width, eps=1e-5)
    return form


def through_kernels(name):
    return lambda o, wide, scale: kernels.gated_norm(o, wide, scale,
                                                     **how(name))


def rel(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


def l2_gap(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum())


def close(got, want, dtype, what):
    """f32: the same mathematics in f32, to rounding (the sums along the
    lanes in another order).  bf16: an f32 result rounded once against the
    reference's (``tests/test_ssd_kernel.py`` holds the scan's kernels to 8e-3
    of the largest entry and 4e-3 in L2)."""
    assert got.shape == want.shape, what
    if dtype == jnp.float32:
        assert rel(got, want) < 5e-6, (what, rel(got, want))
    else:
        assert rel(got, want) < 8e-3 and l2_gap(got, want) < 4e-3, (
            what, rel(got, want), l2_gap(got, want))


def wanted_grads(name, o, wide, scale, dy):
    """The layer's form's gradients of the operands as f32: what a bf16
    kernel's f32 arithmetic rounds once (the form's own bf16 backward pass
    rounds at every step, and sums the scale's cotangent in bf16)."""
    f32 = [t.astype(jnp.float32) for t in (o, wide, scale, dy)]
    return jax.vjp(layer_form(name), *f32[:3])[1](f32[3])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", list(CASES))
def test_kernels_are_the_layers_form(name, dtype):
    """Values and the gradient of every operand through the public entry at
    its own block sizes, batch 2: the values round where the layer's form
    rounds (bit for bit in bf16), the gradients are f32 arithmetic rounded
    once, and the wide array's gradient is ``dz`` in its window."""
    o, wide, scale, dy = operands(name, 2, 48, dtype)
    y, vjp = jax.vjp(through_kernels(name), o, wide, scale)
    want = layer_form(name)(o, wide, scale)
    assert y.dtype == want.dtype == dtype
    close(y, want, dtype, "y")
    if dtype == jnp.bfloat16:
        assert l2_gap(y, want) < 1e-3          # a bf16 step in a few entries
    got = vjp(dy)
    for what, g, t, like in zip(("do", "dz", "dscale"), got,
                                wanted_grads(name, o, wide, scale, dy),
                                (o, wide, scale)):
        assert g.dtype == like.dtype and g.shape == like.shape, what
        close(g, t, dtype, what)


def through_small_blocks(name, o, wide, scale, dy, lanes, tile, chunk):
    """The two jitted entries at blocks of ``tile`` bytes and at most
    ``lanes`` lanes and chunks of ``chunk`` elements, and what the backward
    rule makes of their sums."""
    C = o.shape[2]
    w = jnp.tile(scale, C // scale.shape[0]).reshape(1, C)
    kw = dict(interpret=True, lanes=lanes, tile=tile, chunk=chunk, **how(name))
    y = kernels.hetu_gated_norm_fwd(o, wide, w, **kw)
    do, dz, dw = kernels.hetu_gated_norm_bwd(o, wide, w, dy, **kw)
    assert dw.shape == (8, C) and dw.dtype == jnp.float32
    return y, do, dz, dw.sum(0).reshape(-1, scale.shape[0]).sum(0)


@pytest.mark.parametrize("name,B,S,lanes,tile,chunk,dtype", [
    # 4 blocks of 2 chunks, 2 groups a lane block, 2 lane blocks
    ("gdn_128", 1, 128, 256, 32 * 256 * 4, 16 * 128, jnp.float32),
    # 80 rows: a block of 32 rows does not divide them, 5 blocks of 16
    ("gdn_128", 2, 80, 256, 32 * 256 * 2, 16 * 128, jnp.bfloat16),
    # one lane block a group, 3 blocks of one chunk, 2 sequences
    ("gdn_128_rep1", 2, 48, 128, 16 * 128 * 4, 16 * 128, jnp.float32),
    # a group of 512 lanes in blocks of 2 groups: 96 rows as 2 blocks of 48
    # (64 does not divide them) of 3 chunks
    ("ssm_512", 1, 96, 1024, 64 * 1024 * 2, 16 * 512, jnp.bfloat16),
    ("ssm_128", 2, 64, 128, 32 * 128 * 4, 32 * 128, jnp.float32),
    # one group over all channels, a block of one chunk of 16 rows
    ("ssm_one_group", 2, 112, 128, 16 * 512 * 2, 128, jnp.bfloat16),
])
def test_blocks_chunks_and_lane_blocks(name, B, S, lanes, tile, chunk, dtype):
    """A sequence cut into several blocks and a block into several chunks,
    the channels into several lane blocks: every row is normed once over its
    own group, and the scale's cotangent is the sum over every block, chunk,
    head and both sequences of the batch."""
    o, wide, scale, dy = operands(name, B, S, dtype, seed=1)
    y, do, dz, dscale = through_small_blocks(name, o, wide, scale, dy, lanes,
                                             tile, chunk)
    close(y, layer_form(name)(o, wide, scale), dtype, "y")
    _, C, _, _, window = CASES[name]
    t_do, t_dwide, t_dscale = wanted_grads(name, o, wide, scale, dy)
    close(do, t_do, dtype, "do")
    close(dz, kernels.take(t_dwide, window, C), dtype, "dz")
    close(dscale, t_dscale, dtype, "dscale")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("name", ["gdn_128", "gdn_128_rep1", "ssm_512",
                                  "ssm_one_group"])
def test_z_is_read_in_place(name, dtype):
    """NaN in every lane of the wide array that is not ``z``'s: the result is
    the slice's, bit for bit, and so is every gradient; the wide array's is
    ``dz`` in its window and zeros, not NaN, around it."""
    _, C, _, _, window = CASES[name]
    o, wide, scale, dy = operands(name, 2, 32, dtype, seed=2)
    assert kernels.in_place(C, how(name)["width"], window)
    mask = kernels.take(jnp.arange(wide.shape[2])[None, None], window, C)
    inside = np.zeros(wide.shape[2], bool)
    inside[np.asarray(mask).ravel()] = True
    assert inside.sum() == C
    wide = jnp.where(inside, wide, jnp.nan)
    sliced = lambda o, wide, scale: kernels.gated_norm(
        o, kernels.take(wide, window, C), scale, **dict(how(name),
                                                        window=None))
    y, vjp = jax.vjp(through_kernels(name), o, wide, scale)
    y_s, vjp_s = jax.vjp(sliced, o, wide, scale)
    np.testing.assert_array_equal(y, y_s)
    assert np.isfinite(np.asarray(y, np.float32)).all()
    for g, s in zip(vjp(dy), vjp_s(dy)):
        np.testing.assert_array_equal(g, s)
    dwide = np.asarray(vjp(dy)[1], np.float32)
    assert not dwide[..., ~inside].any() and dwide[..., inside].any()


def test_a_window_off_the_blocks_is_sliced_first():
    """Runs of 192 lanes: no block of whole 128-lane groups reaches them, so
    ``gated_norm`` slices ``z`` as the ``jax.numpy`` form does and the kernels
    read the copy."""
    window, C = Window(64, 128, 192), 256
    assert not kernels.in_place(C, 128, window)
    r = np.random.default_rng(3)
    o = jnp.asarray(r.normal(size=(1, 32, C)), jnp.float32)
    wide = jnp.asarray(r.normal(size=(1, 32, 2 * 192)), jnp.float32)
    scale = jnp.ones((128,), jnp.float32)
    y = kernels.gated_norm(o, wide, scale, width=128, gate_first=False,
                           eps=1e-6, window=window)
    z = jnp.concatenate([wide[..., 64:192], wide[..., 256:384]], -1)
    np.testing.assert_array_equal(kernels.take(wide, window, C), z)
    close(y, gdn._out(o, z, scale, jnp.eye(C), eps=1e-6), jnp.float32, "y")


def test_sums_over_all_rows_are_f32_sums_cast_once():
    """The scale's cotangent of bf16 operands: 4,096 rows of two sequences
    and four heads summed in f32 and rounded once are within a bf16 step of
    the f32 sums of the same products; a bf16 running sum would be percent
    off."""
    o, wide, scale, dy = operands("gdn_128", 2, 2048, jnp.bfloat16, seed=4)
    dscale = jax.vjp(through_kernels("gdn_128"), o, wide, scale)[1](dy)[2]
    want = wanted_grads("gdn_128", o, wide, scale, dy)[2]
    assert dscale.dtype == jnp.bfloat16 and rel(dscale, want) < 8e-3


# -- the rule ------------------------------------------------------------------

def sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("why,o,wide,scale,width", [
    (None, (1, 8192, 4096), (1, 8192, 12288), 128, 128),     # Qwen3-Next
    (None, (1, 8192, 4096), (1, 8192, 10304), 4096, 512),    # Nemotron-H
    (None, (1, 8192, 4096), (1, 8192, 8512), 4096, 4096),    # Granite
    (None, (3, 16, 128), (3, 16, 128), 128, 128),
    ("width_not_128_aligned", (1, 64, 128), (1, 64, 392), 128, 64),
    ("width_not_128_aligned", (1, 64, 384), (1, 64, 384), 384, 256),
    ("scale_not_a_group_or_all", (1, 64, 512), (1, 64, 512), 256, 128),
    ("seq_not_16_aligned", (1, 200, 128), (1, 200, 128), 128, 128),
    ("group_wider_than_a_block", (1, 64, 65536), (1, 64, 65536), 65536,
     65536),
])
def test_unsupported_reads_its_operands(why, o, wide, scale, width):
    assert kernels.unsupported(sds(o), sds(wide), sds((scale,)),
                               width=width) == why


@pytest.mark.parametrize("o,z,why", [
    (jnp.bfloat16, jnp.bfloat16, None), (jnp.float32, jnp.float32, None),
    (jnp.float16, jnp.float16, "dtype:float16"),
    (jnp.bfloat16, jnp.float32, "dtype:mixed"),
])
def test_unsupported_reads_the_types(o, z, why):
    assert kernels.unsupported(sds((1, 64, 128), o), sds((1, 64, 128), z),
                               sds((128,), o), width=128) == why


@pytest.mark.parametrize("channels,width,window,lanes", [
    (4096, 128, Window(512, 256, 768), 256),      # Qwen3-Next: a key head
    (4096, 512, Window(0, 4096, 4096), 4096),     # Nemotron-H: all eight
    (4096, 4096, Window(0, 4096, 4096), 4096),    # Granite: the one group
    (4096, 128, None, 4096),
    (256, 128, Window(64, 128, 192), 0),
])
def test_a_block_holds_whole_groups_and_reaches_the_window(channels, width,
                                                           window, lanes):
    assert kernels._lanes(channels, width, window, kernels.LANES) == lanes
    assert kernels.in_place(channels, width, window) == (lanes > 0)


@pytest.fixture
def norm_choices(live_registry):
    """``{(impl, reason): count}`` of the node's choices since the test
    began (the registry is the process's: ``conftest.live_registry``)."""
    before = dispatch.choices()

    def since():
        return {k[1:]: n - before.get(k, 0)
                for k, n in dispatch.choices().items()
                if k[0] == "gated_norm" and n > before.get(k, 0)}
    return since


def out_nodes(lanes):
    """The output node of each of the two mixers, its groups ``lanes``
    wide."""
    import hetu_tpu as ht
    x = ht.placeholder_op(f"gn_node_x{lanes}", (1, 64, 64))
    nodes = {
        "hetu_ssm_out": ssm.Mamba2(64, 8, lanes // 4, 2, 64,
                                   name=f"gn_node_ssm{lanes}")(x),
        "hetu_gdn_out": gdn.GatedDeltaNet(64, 2, 4, lanes, lanes,
                                          name=f"gn_node_gdn{lanes}")(x)}
    for scope, node in nodes.items():
        assert isinstance(node, OutOp) and node.scope == scope
    return nodes


@pytest.mark.parametrize("scope,args,window,first", [
    # y, [z | xBC | dt], the scale, the output weight
    ("hetu_ssm_out", [(1, 64, 256), (1, 64, 648), (256,), (256, 64)],
     Window(0, 256, 256), True),
    ("hetu_gdn_out", [(1, 64, 512), (1, 64, 1536), (128,), (512, 64)],
     Window(512, 256, 768), False),
])
def test_out_node_hands_the_kernels_where_z_lies(monkeypatch, scope, args,
                                                 window, first):
    """One node class for both mixers: what differs is handed in as data.
    (What the node takes on which platform and under a mesh:
    ``tests/test_kernel_dispatch.py``.)"""
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    called = []
    monkeypatch.setattr(kernels, "gated_norm", lambda o, *a, **k:
                        called.append(k) or o)
    node = out_nodes(128)[scope]
    out = jax.eval_shape(lambda *a: node._compute(
        list(a), types.SimpleNamespace(mesh=None)), *(sds(s) for s in args))
    assert out.shape == (1, 64, 64)
    assert called == [dict(window=window, width=128, gate_first=first,
                           eps=node.attrs["eps"])]


@pytest.mark.parametrize("scope,args,why", [
    ("hetu_ssm_out", [(1, 64, 128), (1, 64, 392), (128,), (128, 64)],
     "width_not_128_aligned"),          # two groups of 64 lanes
    ("hetu_gdn_out", [(1, 40, 256), (1, 40, 768), (64,), (256, 64)],
     "width_not_128_aligned"),          # heads of 64 lanes
])
def test_out_node_says_why_it_takes_the_jnp_form_on_a_tpu(
        norm_choices, monkeypatch, scope, args, why):
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    monkeypatch.setattr(kernels, "gated_norm", None)          # never reached
    node = out_nodes(64)[scope]
    out = jax.eval_shape(lambda *a: node._compute(
        list(a), types.SimpleNamespace(mesh=None)), *(sds(s) for s in args))
    assert out.shape == args[0][:2] + (64,)
    assert norm_choices() == {("jnp", why): 1}


# -- the layers through the kernels -----------------------------------------------

def layer_loss_and_grads(kind, through, monkeypatch):
    """Loss and every weight's gradient of one mixer whose norm runs over
    groups of 128 lanes, through the executor; ``through``: the output node
    takes the kernels (interpret mode) as it does on a TPU."""
    import hetu_tpu as ht
    from hetu_tpu.graph.node import graph_variables
    taken = []
    if through:
        monkeypatch.setattr(op, "dispatch", types.SimpleNamespace(
            take=lambda kernel, mesh, why:
            taken.append((kernel, why)) or why is None))
    name = f"gn_{kind}_{int(through)}"
    if kind == "ssm":           # d = 256 in two groups, z | xBC | dt = 648
        layer = ssm.Mamba2(32, 8, 32, 2, 32, chunk=16, name=name)
    else:                       # two key heads of [q | k | v v | z z] = 768
        layer = gdn.GatedDeltaNet(32, 2, 4, 128, 128, name=name)
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
    assert taken == [("gated_norm", None)] * through
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
