"""The chunked state-space scan (``hetu_tpu/ops/ssd.py chunk_ssd``) against
the recurrence one position at a time, the program's own
(``recurrent_ssd``) and the plain reference's
(``chipbench/reference/nemotron_h.py ssm_recurrence``): outputs, the last
state and the gradient of every input, over several chunk counts with a
ragged last chunk, in f32 and with bf16 inputs.  A state carried in bf16 must
fail the tolerance the chunked form passes.  And which function the
``Mamba2`` layer's scan node calls: on a TPU the kernels' in-place entry off a
mesh, the ``jax.numpy`` form under one.

Decays: ``dt`` about 0.7 and ``A`` of 0.003 to 1, so that the slowest head
forgets 0.2% a position and still holds the first position at the last."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.ops.ssd import chunk_ssd, recurrent_ssd, segsum

from chipbench.reference import nemotron_h as ref

B, H, P, G, N = 2, 8, 16, 2, 32
#: relative to the largest entry: f32 in another order of summation
TOL = 2e-5


def inputs(T, seed=0, dtype=jnp.float32):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(B, T, H, P)), dtype)
    dt = jnp.asarray(np.logaddexp(0, r.normal(size=(B, T, H))), jnp.float32)
    A = -jnp.asarray(np.geomspace(3e-3, 1.0, H), jnp.float32)
    Bm, Cm = (jnp.asarray(r.normal(size=(B, T, G, N)) * N ** -0.5, dtype)
              for _ in range(2))
    return x, dt, A, Bm, Cm


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / np.abs(want).max()


def reference(x, dt, A, Bm, Cm, **kw):
    with jax.default_matmul_precision("highest"):
        return ref.ssm_recurrence(*(t.astype(jnp.float32) for t in (
            x, dt, A, Bm, Cm)), **kw)


@pytest.mark.parametrize("T,chunk", [(16, 16), (70, 16), (96, 32), (40, 128)])
@pytest.mark.parametrize("other", [recurrent_ssd, reference],
                         ids=["recurrent_ssd", "reference"])
def test_chunked_is_the_recurrence(T, chunk, other):
    args = inputs(T)
    y, last = jax.jit(lambda *a: chunk_ssd(*a, chunk=chunk))(*args)
    y_want, last_want = jax.jit(other)(*args)
    assert y.shape == (B, T, H, P) and last.shape == (B, H, P, N)
    assert last.dtype == jnp.float32
    assert rel(y, y_want) < TOL and rel(last, last_want) < TOL


@pytest.mark.parametrize("T,chunk", [(48, 16), (70, 32)])
def test_gradient_of_every_input(T, chunk):
    args = inputs(T, seed=1)
    w_y, w_s = (jnp.asarray(np.random.default_rng(2).normal(size=s),
                            jnp.float32)
                for s in ((B, T, H, P), (B, H, P, N)))

    def loss(fn):
        def f(*a):
            y, last = fn(*a)
            return jnp.sum(y * w_y) + jnp.sum(last * w_s)
        return jax.jit(jax.grad(f, argnums=range(5)))
    got = loss(lambda *a: chunk_ssd(*a, chunk=chunk))(*args)
    want = loss(reference)(*args)
    for name, g, w in zip(("x", "dt", "A", "B", "C"), got, want):
        assert np.abs(np.asarray(w)).max() > 0, name
        assert rel(g, w) < 5 * TOL, name


def test_bf16_inputs_keep_an_f32_state():
    """x, B and C in bf16: the output is bf16, the state f32, and both are
    the f32 recurrence's on the same (rounded) inputs to what the bf16
    operands of the four products cost, a few parts in a thousand."""
    args = inputs(96, seed=3, dtype=jnp.bfloat16)
    y, last = jax.jit(lambda *a: chunk_ssd(*a, chunk=32))(*args)
    y_want, last_want = jax.jit(reference)(*args)
    assert y.dtype == jnp.bfloat16 and last.dtype == jnp.float32
    assert rel(y.astype(jnp.float32), y_want) < 1e-2
    assert rel(last, last_want) < 5e-3


@pytest.mark.parametrize("other", [
    lambda *a: recurrent_ssd(*a, state_dtype=jnp.bfloat16),
    lambda *a: reference(*a, state_dtype=jnp.bfloat16)],
    ids=["recurrent_ssd", "reference"])
def test_a_bf16_state_fails_the_tolerance(other):
    args = inputs(96, seed=4)
    _, last_want = jax.jit(reference)(*args)
    _, last = jax.jit(other)(*args)
    assert rel(last, last_want) > 100 * TOL


def test_segsum_is_a_sum_of_the_terms_between():
    a = -jnp.asarray(np.random.default_rng(5).uniform(0, 2, (3, 7)),
                     jnp.float32)
    seg = np.asarray(segsum(a))
    for t in range(7):
        for s in range(7):
            want = float(np.asarray(a)[1, s + 1:t + 1].sum()) if s <= t \
                else -np.inf
            assert seg[1, t, s] == pytest.approx(want, abs=1e-6)
    # no cancellation: after a running sum of -2,000 a small step is exact
    big = jnp.asarray([-2000.0, -1e-3, -1e-3], jnp.float32)
    assert float(segsum(big)[2, 0]) == pytest.approx(-2e-3, rel=1e-6)


# -- what the layer's scan node calls -------------------------------------------

@pytest.mark.parametrize("platform,mesh,form,choice", [
    ("tpu", None, {"in_place": 1}, {("pallas", ""): 1}),
    ("tpu", "a mesh", {}, {("jnp", "mesh"): 1}),
    ("cpu", None, {}, {}), ("cpu", "a mesh", {}, {})])
def test_scan_node_takes_the_in_place_entry_off_a_mesh(
        live_registry, monkeypatch, platform, mesh, form, choice):
    """The ``hetu_ssm_scan`` node of a ``Mamba2`` at the published head and
    state sizes (4 heads of 64, one group, state 128; 256 positions): on a
    TPU off a mesh ``ssd_in_place`` (``hetu_ssd_form_total{form="in_place"}``)
    counted ``pallas`` once; under a mesh the ``jax.numpy`` form around the
    slices and the skip, counted ``mesh``, and no entry of the kernels;
    without Mosaic that form and nothing counted."""
    import types
    import hetu_tpu as ht
    from hetu_tpu.layers.mamba2 import Mamba2
    from hetu_tpu.ops import ssd
    from hetu_tpu.ops.pallas import dispatch, ssd as kernels
    monkeypatch.setattr(dispatch, "platform", lambda: platform)
    jax.clear_caches()            # ``kernels._dot`` reads the mode when traced
    called = []
    real = ssd.chunk_ssd_jnp
    monkeypatch.setattr(ssd, "chunk_ssd_jnp",
                        lambda *a, **k: called.append("jnp") or real(*a, **k))
    x = ht.placeholder_op(f"ssn_{platform}_{mesh is None}_x", (1, 256, 256))
    node = Mamba2(256, 4, 64, 1, 128,
                  name=f"ssn_{platform}_{mesh is None}")(x).inputs[0]
    assert node.scope == "hetu_ssm_scan"
    forms, choices = kernels.forms(), dispatch.choices()
    sds = jax.ShapeDtypeStruct
    y = jax.eval_shape(
        lambda *a: node._compute(list(a), types.SimpleNamespace(mesh=mesh)),
        sds((1, 256, 512), jnp.bfloat16), sds((1, 256, 4), jnp.bfloat16),
        *(sds((4,), jnp.float32),) * 3)
    jax.clear_caches()
    assert y.shape == (1, 256, 256) and y.dtype == jnp.bfloat16
    assert called == ([] if form else ["jnp"])
    assert {k: n - forms.get(k, 0) for k, n in kernels.forms().items()
            if n > forms.get(k, 0)} == form
    assert {k[1:]: n - choices.get(k, 0)
            for k, n in dispatch.choices().items()
            if k[0] == "ssd" and n > choices.get(k, 0)} == choice
