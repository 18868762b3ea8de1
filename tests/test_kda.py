"""The delta rule with a decay a key channel (``ops/kda.py``): the chunked
``jax.numpy`` form and the Pallas kernel pair (interpret mode) against the
recurrence, gates down to the bound, lengths no chunk divides, gradients of
the custom VJP against JAX's own, and the scalar-decay rule as its special
case."""

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


# -- the in-place entry: the layer's arrays, norms and gates in the kernel ----

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
            [kernels._unit(t[0, :, h * D:(h + 1) * D]) for h in range(HEADS)],
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
