"""The delta rule's second entry (``ops/pallas/kda.py kda_in_place``): the
layer's arrays read where the convolution and the projection wrote them, the
norms and gates in the kernel (interpret mode), against the layer's
``jax.numpy`` form, the plain entry on the slices, the scan node's choice on
and off a mesh and the layer through the executor.  The helpers the kept
inverses' tests share (``tests/test_delta_inverse_kept.py``) live here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops import kda
from hetu_tpu.ops.pallas import common
from hetu_tpu.ops.pallas import kda as kernels
from test_kda import D, draw, rel

HEADS = 4


def layer_arrays(seed, B, T, dtype, at_bound=False, H=HEADS):
    """What the layer hands its scan node: ``mixed [B, T, 3 H d]`` after the
    convolution's SiLU, ``proj [B, T, 5 H d]``, ``beta_lin``, ``a_log``,
    ``dt_bias``, the norm's scale; ``at_bound``: ``f`` so large that ``g`` is
    the bound at every position and channel."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    hd = H * D
    mixed = jax.nn.silu(jax.random.normal(ks[0], (B, T, 3 * hd)))
    proj = jax.random.normal(ks[1], (B, T, 5 * hd))
    if at_bound:
        proj = proj.at[..., 3 * hd:4 * hd].set(40.0)
    beta_lin = jax.random.normal(ks[2], (B, T, H))
    a_log = jnp.log(jax.random.uniform(ks[3], (H,), minval=1.0, maxval=16.0))
    dt_bias = 0.5 * jax.random.normal(ks[4], (hd,))
    scale = 1.0 + 0.1 * jax.random.normal(ks[5], (D,))
    return (mixed.astype(dtype), proj.astype(dtype), beta_lin.astype(dtype),
            a_log, dt_bias, scale)


def mixer_jnp(mixed, proj, beta_lin, a_log, dt_bias, scale, H=HEADS,
              rule=kda.chunk_kda_jnp):
    """The layer's ``jax.numpy`` form between the convolution and the output
    product: ``_scan`` around ``rule`` (``chunk_kda_jnp``), then ``_out``'s
    norm and gate (its product taken with the identity)."""
    from hetu_tpu.layers.kda import _out, _scan
    o = _scan(proj, mixed, beta_lin, a_log, dt_bias, scale, heads=H, d=D,
              lower_bound=-5.0, eps=1e-6, rule=rule)
    assert o.ndim == 4
    return _out(o, proj, scale, jnp.eye(H * D, dtype=o.dtype), eps=1e-6)


def mixer_in_place(mixed, proj, beta_lin, a_log, dt_bias, scale):
    return kernels.kda_in_place(
        mixed, proj, jax.nn.sigmoid(beta_lin.astype(jnp.float32)),
        jnp.repeat(jnp.exp(a_log), D), dt_bias, scale, lower_bound=-5.0,
        eps=1e-6)


NAMES = ("mixed", "proj", "beta_lin", "a_log", "dt_bias", "norm_scale")


@pytest.mark.parametrize("dtype,T,B,at_bound", [
    ("float32", 150, 1, False),      # padding: 512 does not divide T
    ("float32", 512, 2, False),      # no padding, two batch rows
    ("float32", 130, 1, True),       # g = -5 everywhere
    ("bfloat16", 600, 1, False),     # two programs along the sequence
    ("bfloat16", 512, 2, False),
])
def test_in_place_entry_is_the_layers_jnp_form(dtype, T, B, at_bound):
    """Values and every gradient (``mixed``, the ``f`` and ``z`` windows of
    ``proj`` and nothing in its first three, ``beta_lin``, ``A_log``,
    ``dt_bias``, the norm's scale) of the kernels in interpret mode against
    ``_scan`` + ``_out``'s norm and gate in ``jax.numpy``."""
    dtype = jnp.dtype(dtype)
    x = layer_arrays(T + B, B, T, dtype, at_bound)
    wy = jax.random.normal(jax.random.PRNGKey(5), (B, T, HEADS * D))

    def both(fn):
        def loss(*a):
            y = fn(*a)
            return jnp.sum(y.astype(jnp.float32) * wy), y
        return jax.value_and_grad(loss, argnums=tuple(range(6)),
                                  has_aux=True)(*x)
    (_, y), got = both(mixer_in_place)
    (_, y_ref), want = both(mixer_jnp)
    tol = 2e-2 if dtype == jnp.bfloat16 else 5e-5
    # sums of bf16 terms over all positions (the small parameters'
    # gradients) are held to the f32 form of the same arrays instead: no
    # farther from it than twice the jax.numpy form's own bf16
    x = tuple(t.astype(jnp.float32) for t in x)
    exact = both(mixer_jnp)[1] if dtype == jnp.bfloat16 else want
    assert y.shape == (B, T, HEADS * D) and y.dtype == dtype
    assert rel(y.astype(jnp.float32), y_ref.astype(jnp.float32)) < tol
    if at_bound:
        g = -5.0 * jax.nn.sigmoid(jnp.exp(x[3])[:, None] * (
            40.0 + x[4].reshape(HEADS, D)))
        assert float(g.max()) == -5.0
    for name, a, b, c in zip(NAMES, got, want, exact):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = (np.asarray(t.astype(jnp.float32)) for t in (a, b))
        if b.any():
            assert rel(a, c) < max(tol, 2 * rel(b, c)), name
        else:                # a gate shut at its bound passes nothing back
            assert at_bound and not a.any(), name
    dproj = np.asarray(got[1].astype(jnp.float32))
    assert not dproj[..., :3 * HEADS * D].any()
    assert dproj[..., 3 * HEADS * D:4 * HEADS * D].any() != at_bound
    assert dproj[..., 4 * HEADS * D:].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_window_read_in_place_equals_the_slice_handed_in(dtype):
    """The kernels read ``f`` and ``z`` at their lanes of ``proj`` and ``q~,
    k~, v`` at theirs of ``mixed``: the in-place entry on the wide arrays is
    bit for bit the plain entry on the slices (norms and gate taken with the
    kernel's own functions, the gated norm undone), and nothing else of
    ``proj`` is read."""
    T, hd = 200, HEADS * D
    mixed, proj, beta_lin, a_log, dt_bias, scale = layer_arrays(
        9, 1, T, jnp.dtype(dtype))
    y = mixer_in_place(mixed, proj, beta_lin, a_log, dt_bias, scale)
    elsewhere = proj.at[..., :3 * hd].set(jnp.nan)
    np.testing.assert_array_equal(
        np.asarray(y, np.float32), np.asarray(mixer_in_place(
            mixed, elsewhere, beta_lin, a_log, dt_bias, scale), np.float32))
    # the same chunks from slices: the plain entry writes o in the compute
    # type, so compare at f32, where the one cast is the only difference
    if dtype == "float32":
        heads = lambda t: t.reshape(1, T, HEADS, D)
        unit = lambda t: heads(jnp.concatenate(
            [common.unit(t[0, :, h * D:(h + 1) * D])[0] for h in range(HEADS)],
            -1)[None])
        g = -5.0 * jax.nn.sigmoid(jnp.repeat(jnp.exp(a_log), D) * (
            proj[..., 3 * hd:4 * hd] + dt_bias))
        o, _ = kernels.kda(unit(mixed[..., :hd]) * D ** -0.5,
                           unit(mixed[..., hd:2 * hd]),
                           heads(mixed[..., 2 * hd:]), heads(g),
                           jax.nn.sigmoid(beta_lin))
        want = (o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6)
                * scale * jax.nn.sigmoid(heads(proj[..., 4 * hd:])))
        assert rel(y, want.reshape(1, T, hd)) < 1e-6


def scan_node():
    import hetu_tpu as ht
    from hetu_tpu.layers.kda import KimiDeltaAttention
    layer = KimiDeltaAttention(256, 2, D, name="kda_node")
    x = ht.placeholder_op("kda_node_x", (1, 64, 256))
    node = layer(x).inputs[0]
    assert node.scope == "hetu_kda_scan"
    return node


@pytest.mark.parametrize("platform,mesh,choice,entry,rank", [
    ("tpu", None, {("pallas", ""): 1}, {"in_place": 1}, 3),
    ("tpu", "a mesh", {("jnp", "mesh"): 1}, {}, 4),
    ("cpu", None, {}, {}, 4),
    ("cpu", "a mesh", {}, {}, 4),
])
def test_scan_node_takes_the_in_place_entry_off_a_mesh(
        monkeypatch, platform, mesh, choice, entry, rank):
    """``hetu_kda_scan`` hands ``hetu_kda_out`` the normalised, gated ``[B,
    S, H d]`` where the kernels run (counted ``pallas`` once and ``in_place``
    once) and the 4-D ``o`` of the ``jax.numpy`` form under a mesh (counted
    ``mesh``) and on a platform without Mosaic (nothing counted)."""
    import types
    from hetu_tpu import telemetry
    from hetu_tpu.ops.pallas import dispatch
    telemetry.enable()
    try:
        telemetry.get_registry().reset()
        monkeypatch.setattr(dispatch, "platform", lambda: platform)
        monkeypatch.setattr(dispatch, "interpret", lambda: True)
        node = scan_node()
        sds = jax.ShapeDtypeStruct
        bf16, hd = jnp.bfloat16, 2 * D
        out = jax.eval_shape(
            lambda *a: node._compute(list(a), types.SimpleNamespace(
                mesh=mesh)),
            sds((1, 64, 5 * hd), bf16), sds((1, 64, 3 * hd), bf16),
            sds((1, 64, 2), bf16), sds((2,), bf16), sds((hd,), bf16),
            sds((D,), bf16))
        assert out.shape == ((1, 64, hd) if rank == 3 else (1, 64, 2, D))
        assert out.dtype == bf16
        assert {k[1:]: n for k, n in dispatch.choices().items()
                if k[0] == "kda"} == choice
        assert kernels.entries() == entry
        if platform == "tpu" and mesh is None:
            q, k, v, g, beta = draw(2, 64)
            jax.eval_shape(kda.chunk_kda, q, k, v, g, beta)
            assert kernels.entries() == {"in_place": 1, "plain": 1}
            assert dispatch.choices()[("kda", "pallas", "")] == 2
    finally:
        telemetry.disable()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_layer_through_the_kernels_is_the_layer_without(monkeypatch,
                                                            dtype):
    """One ``KimiDeltaAttention`` at the published head size through the
    executor, loss and every weight's gradient: the in-place kernels
    (interpret mode, the platform read as ``tpu``) against the ``jax.numpy``
    nodes."""
    import hetu_tpu as ht
    from hetu_tpu.graph.node import graph_variables
    from hetu_tpu.layers.kda import KimiDeltaAttention
    from hetu_tpu.ops.pallas import dispatch

    def run(through_kernels, dtype=dtype):
        if through_kernels:
            monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
            monkeypatch.setattr(dispatch, "interpret", lambda: True)
            # the convolution's kernels have tests of their own
            from hetu_tpu.ops import causal_conv as cc
            monkeypatch.setattr(cc, "causal_conv", cc.causal_conv_jnp)
        name = f"kda_k{int(through_kernels)}_{dtype}"
        layer = KimiDeltaAttention(96, 2, D, name=name)
        x = ht.placeholder_op(f"{name}_x", (2, 100, 96))
        loss = ht.reduce_sum_op(ht.sin_op(layer(x)), axes=[0, 1, 2])
        params = graph_variables([loss], trainable_only=True)
        ex = ht.Executor({"grads": [loss] + ht.gradients(loss, params)},
                         seed=5, compute_dtype=jnp.dtype(dtype))
        r = np.random.default_rng(1)
        for var in params:          # the same weights for both, off their
            value = ex.params[var.name]     # initial ones
            ex.params[var.name] = jnp.asarray(
                r.normal(1.0 if var.shape == (D,) else 0.0, 0.1, var.shape),
                value.dtype)
        feed = {x: r.standard_normal((2, 100, 96)).astype(np.float32)}
        out = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
        return out[0], {"_".join(p.name.rsplit("_", 2)[1:]): g
                        for p, g in zip(params, out[1:])}
    want, grads_want = run(False)
    # bf16: both against the f32 layer, the kernels no farther from it than
    # twice the jax.numpy nodes' own bf16
    exact, grads_exact = (want, grads_want) if dtype == "float32" else run(
        False, "float32")
    got, grads_got = run(True)
    tol = 3e-2 if dtype == "bfloat16" else 1e-4
    assert abs(got - want) < tol * abs(want)
    assert set(grads_got) == set(grads_want) and len(grads_got) == 7
    for name, g in grads_got.items():
        assert g.dtype == grads_want[name].dtype, name
        assert rel(g, grads_exact[name]) < max(tol, 2 * rel(
            grads_want[name], grads_exact[name])), name
