"""A norm a head and the rotation in one pass over the projections' ``[B, S, H
d]`` (``hetu_qk_norm_rope_fwd`` / ``_bwd``, ``hetu_tpu/ops/pallas/rotary.py``;
the node ``ops/rotary.py NormRotaryPairOp``), in interpret mode on the CPU,
against ``ops/nn.py _rms_norm`` on the view by heads followed by ``ops/rotary.py
_rotary(seq_axis=1)``: the forward values bit for bit, the cotangents of q, k
and both scales against ``jax.grad`` of that form, at 32 : 4 and 4 : 4 heads of
128 under the block mask's tables (``copies=2``); the rule's refusals, each
once; the node's choice on and off a mesh and off a TPU; and Mosaic compiling
both kernels at the SDAR cell's shape.

Bit for bit, and what that can mean on a CPU.  The interpret-mode body is one
compiled function, and LLVM contracts its ``y cos + roll(y) sin`` into a fused
multiply-add where the ``jax.numpy`` form, run operation by operation, rounds
the product first (``tests/test_rotary_kernel.py close`` allows the plain pair
one place for the same reason).  So the roundings are held bit for bit where
the arithmetic leaves the compiler no choice: the norm alone under tables that
turn nothing (``cos = 1``, ``sin = 0``), and the whole pass under tables of
signed powers of two, where every product is exact and the sum rounds once
however it is fused.  At the real tables the pass is within one place."""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.ops import rotary as op
from hetu_tpu.ops.nn import _rms_norm
from hetu_tpu.ops.pallas import dispatch, rotary as kernels

from conftest import close, rotary_kernels_asked as asked


D, THETA, EPS = 128, 1e6, 1e-6
#: (query heads, key heads): the SDAR cell's grouping, and none
HEADS = [(32, 4), (4, 4)]
TYPES = [jnp.float32, jnp.bfloat16]


def operands(B, S, H, KV, dtype, seed=0):
    """``q, k`` (q at twice k's size, so their norms differ), both scales and
    a cotangent of each result."""
    r = np.random.default_rng(seed)
    q, k, gq, gk = (jnp.asarray(size * r.normal(size=(B, S, n * D)), dtype)
                    for n, size in ((H, 2.0), (KV, 1.0), (H, 1.0), (KV, 1.0)))
    wq, wk = (jnp.asarray(1 + 0.3 * r.normal(size=D), dtype) for _ in "qk")
    return q, k, wq, wk, gq, gk


def tables(S):
    return op._pair_tables(seq_len=S, dim=D, theta=THETA, copies=2)


def normed(x, w, **more):
    B, S, W = x.shape
    return _rms_norm(x.reshape(B, S, W // D, D), w, EPS, **more)


def plain(x, w, **turn):
    """The ``jax.numpy`` form: the norm on the view by heads, then the
    rotation there."""
    return op._rotary(normed(x, w), theta=THETA, seq_axis=1,
                      **(turn or {"copies": 2})).reshape(x.shape)


def forward(q, k, wq, wk, t, **more):
    return kernels.hetu_qk_norm_rope_fwd(
        q, k, kernels._scales(wq, wk), t, eps=EPS, interpret=True, **more)


@pytest.mark.parametrize("dtype", TYPES)
@pytest.mark.parametrize("H,KV", HEADS)
def test_the_norm_is_rms_norm_bit_for_bit(H, KV, dtype):
    """Under tables that turn nothing the pass is ``_rms_norm`` on the view:
    its rounding to the compute type before the scale and the one after it."""
    S = 64
    q, k, wq, wk, _, _ = operands(2, S, H, KV, dtype)
    still = jnp.stack([jnp.ones((S, D)), jnp.zeros((S, D))])
    for got, x, w in zip(forward(q, k, wq, wk, still), (q, k), (wq, wk)):
        assert got.dtype == dtype
        assert (got == normed(x, w).reshape(x.shape)).all()


@pytest.mark.parametrize("dtype", TYPES)
@pytest.mark.parametrize("H,KV,B,S,tile", [
    (32, 4, 2, 64, kernels.TILE),       # one block a sequence, 8 + 1 heads
    (4, 4, 2, 64, kernels.TILE),        # no third grid axis
    (32, 4, 1, 96, 2 ** 17),            # three blocks of 32 rows
    (8, 2, 2, 48, 2 ** 13),             # blocks of 16 rows, chunks of 16
])
def test_the_pass_is_the_jnp_form_bit_for_bit(monkeypatch, H, KV, B, S, tile,
                                              dtype):
    """Tables of signed powers of two (both forms read them from
    ``_rope_tables``): every product exact, one rounding of the sum, so which
    lane meets which, each sign and each rounding of the pass are ``_rms_norm``
    and ``_rotary``'s, bit for bit."""
    def signed_powers(seq_len, dim, *_, **__):
        r = np.random.default_rng(seq_len)
        half = lambda: np.tile(
            r.choice([-1.0, 1.0], (seq_len, dim // 2))
            * 2.0 ** -r.integers(0, 4, (seq_len, dim // 2)), 2)
        return (jnp.asarray(half(), jnp.float32),
                jnp.asarray(half(), jnp.float32))
    monkeypatch.setattr(op, "_rope_tables", signed_powers)
    q, k, wq, wk, _, _ = operands(B, S, H, KV, dtype, seed=S)
    got = forward(q, k, wq, wk, tables(S), tile=tile)
    for g, x, w in zip(got, (q, k), (wq, wk)):
        assert g.dtype == dtype and (g == plain(x, w)).all()
        assert (g != normed(x, w).reshape(x.shape)).any()


@pytest.mark.parametrize("dtype", TYPES)
@pytest.mark.parametrize("H,KV", HEADS)
def test_at_the_real_tables_the_pass_is_within_one_place(H, KV, dtype):
    q, k, wq, wk, _, _ = operands(2, 64, H, KV, dtype)
    got = kernels.norm_rope(q, k, wq, wk, tables(64), EPS)
    for what, g, x, w in zip("qk", got, (q, k), (wq, wk)):
        close(g, plain(x, w), dtype, what)


@pytest.mark.parametrize("dtype", TYPES)
@pytest.mark.parametrize("H,KV", HEADS)
def test_gradients_are_the_jnp_forms(H, KV, dtype):
    """``dq``, ``dk`` and both scales' cotangents against ``jax.grad`` of the
    ``jax.numpy`` form, from the same cotangents of the results.  f32: to
    rounding.  bf16: the kernel rounds the normed value's cotangent and its
    product with the scale as the form's own backward pass does, so ``dq`` and
    ``dk`` are a bf16 place of their largest apart; a scale's cotangent is an
    f32 sum here and a bf16 one there, so it is held to the form computed in
    f32 on the same bf16 values."""
    f32 = jnp.float32
    q, k, wq, wk, gq, gk = operands(2, 64, H, KV, dtype, seed=H)
    t = tables(64)

    def loss(fn, to=None):
        def f(q, k, wq, wk):
            if to is not None:
                q, k, wq, wk = (x.astype(to) for x in (q, k, wq, wk))
            a, b = fn(q, k, wq, wk)
            return (jnp.sum(a.astype(f32) * gq.astype(f32))
                    - jnp.sum(b.astype(f32) * gk.astype(f32)))
        return jax.grad(f, argnums=(0, 1, 2, 3))
    form = lambda q, k, wq, wk: (plain(q, wq), plain(k, wk))
    got = loss(lambda *a: kernels.norm_rope(*a, t, EPS))(q, k, wq, wk)
    want = loss(form)(q, k, wq, wk)
    exact = loss(form, to=f32)(q, k, wq, wk)
    for what, g, w, e in zip(("dq", "dk", "dwq", "dwk"), got, want, exact):
        assert g.shape == w.shape and g.dtype == w.dtype == dtype, what
        g, w, e = (np.asarray(x, np.float32) for x in (g, w, e))
        assert np.abs(w).max() > 0, what
        if dtype == f32:
            assert np.abs(g - w).max() < 4e-6 * np.abs(w).max(), what
        elif what in ("dq", "dk"):
            assert np.abs(g - w).max() < 2.0 ** -7 * np.abs(w).max(), what
        else:
            assert np.abs(g - e).max() < 1e-2 * np.abs(e).max(), what


def test_nothing_f32_of_the_operands_width_is_kept():
    """Kept for the backward pass: q, k, the scales and the tables."""
    q, k, wq, wk, _, _ = operands(1, 32, 4, 4, jnp.bfloat16)
    kept = jax.make_jaxpr(lambda *a: jax.vjp(
        lambda *a: kernels.norm_rope(*a, tables(32), EPS), *a)[1])(
            q, k, wq, wk)
    shapes = sorted((v.aval.shape, v.aval.dtype.name)
                    for v in kept.jaxpr.outvars)
    assert shapes == sorted([
        ((2, 32, D), "float32"), ((D,), "bfloat16"), ((D,), "bfloat16"),
        (q.shape, "bfloat16"), (k.shape, "bfloat16")])


# -- the rule -----------------------------------------------------------------

def sds(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


W16, W32 = sds(D), sds(D, dtype=jnp.float32)


@pytest.mark.parametrize("q,k,w,more,why", [
    (sds(1, 64, 4096), sds(1, 64, 512), W16, {}, None),     # the SDAR cell's
    (sds(1, 64, 512, dtype=jnp.float32), sds(1, 64, 512, dtype=jnp.float32),
     W32, {}, None),
    (sds(1, 64, 512), sds(1, 64, 512), W16, {"zero_centered": True},
     "zero_centered"),
    (sds(1, 64, 512), sds(1, 64, 512), W16, {"rotary_dim": 64},
     "partial_rotation"),
    (sds(1, 64, 512), sds(1, 64, 512), W16, {"rotary_dim": D}, None),
    (sds(1, 64, 256), sds(1, 64, 256), sds(64), {"head_dim": 64},
     "head_dim_not_128_aligned"),
    (sds(1, 64, 512, dtype=jnp.float16), sds(1, 64, 512, dtype=jnp.float16),
     sds(D, dtype=jnp.float16), {}, "dtype:float16"),
    (sds(1, 64, 512), sds(1, 64, 512, dtype=jnp.float32), W16, {},
     "dtype:mixed"),
    # f32 scales on bf16 operands: ``_rms_norm`` would hand back f32
    (sds(1, 64, 512), sds(1, 64, 512), W32, {}, "dtype:mixed"),
    (sds(1, 24, 512), sds(1, 24, 512), W16, {}, "seq_not_16_aligned"),
])
def test_unsupported_reads_its_operands(q, k, w, more, why):
    more = {"head_dim": D, **more}
    assert kernels.norm_unsupported(q, k, w, w, **more) == why


@pytest.fixture
def choices(live_registry):
    """``{(impl, reason): count}`` recorded under ``qk_norm_rope`` since the
    test began; nothing may be recorded under ``rotary``."""
    before = dispatch.choices()

    def since():
        new = {key: n - before.get(key, 0)
               for key, n in dispatch.choices().items()
               if n > before.get(key, 0)}
        assert not [key for key in new if key[0] == "rotary"], new
        return {key[1:]: n for key, n in new.items()
                if key[0] == "qk_norm_rope"}
    return since


def pair_node(q, k, name, d=D, **more):
    """The node over placeholders of ``q``'s and ``k``'s shapes."""
    nodes = [ht.placeholder_op(f"{name}_{n}", x.shape)
             for n, x in (("q", q), ("k", k))]
    scales = [ht.placeholder_op(f"{name}_w{n}", (d,)) for n in "qk"]
    turn = {key: more.pop(key) for key in ("rotary_dim",) if key in more}
    first, second = op.qk_norm_rotary_pair_op(
        *nodes, *scales, op.RopeTables()(q.shape[1], d, THETA, copies=2,
                                          **turn), eps=EPS, **more)
    pair, = first.inputs
    assert second.inputs == [pair] and isinstance(pair, op.NormRotaryPairOp)
    return pair


def compute(pair, q, k, wq, wk, mesh=None):
    t = pair.inputs[4]
    return pair._compute(
        [q, k, wq, wk, op._pair_tables(**t.attrs)],
        types.SimpleNamespace(mesh=mesh))


@pytest.mark.parametrize("more,why", [
    ({"zero_centered": True}, "zero_centered"),
    ({"rotary_dim": 64}, "partial_rotation"),
    ({"mixed": True}, "dtype:mixed"),
    ({}, None),
])
def test_a_refusal_takes_the_jnp_form_on_the_flat_path_and_counts_it(
        choices, monkeypatch, more, why):
    """What the kernels refuse is ``_rms_norm`` and ``_rotary`` on the view of
    the SAME flat operands, bit for bit, counted with its reason."""
    asked(monkeypatch)
    if why is not None:
        monkeypatch.setattr(kernels, "norm_rope", None)     # never reached
    more = dict(more)
    q, k, wq, wk, _, _ = operands(1, 32, 4, 2, jnp.bfloat16)
    if more.pop("mixed", False):
        wq, wk = (w.astype(jnp.float32) for w in (wq, wk))
    got = compute(pair_node(q, k, f"nr_{(why or 'taken')[:9]}", **more),
                  q, k, wq, wk)
    turn = {"copies": 2, **{key: more[key] for key in ("rotary_dim",)
                             if key in more}}
    for g, x, w in zip(got, (q, k), (wq, wk)):
        want = op._rotary(
            normed(x, w, zero_centered=more.get("zero_centered", False)),
            theta=THETA, seq_axis=1, **turn).reshape(x.shape)
        assert g.shape == want.shape and g.dtype == want.dtype
        if why is None:
            close(g, want, jnp.bfloat16, "taken")
        else:
            assert (g == want).all()
    assert choices() == ({("jnp", why): 1} if why else {("pallas", ""): 1})


def test_under_a_mesh_the_jnp_form_and_the_reason_mesh(choices, monkeypatch):
    asked(monkeypatch)
    monkeypatch.setattr(kernels, "norm_rope", None)
    q, k, wq, wk, _, _ = operands(1, 32, 4, 2, jnp.bfloat16)
    got = compute(pair_node(q, k, "nr_mesh"), q, k, wq, wk,
                  mesh=types.SimpleNamespace(shape={"dp": 2}))
    assert (got[0] == plain(q, wq)).all() and (got[1] == plain(k, wk)).all()
    assert choices() == {("jnp", "mesh"): 1}


def test_off_a_tpu_and_not_asked_nothing_is_recorded(choices, monkeypatch):
    monkeypatch.setattr(kernels, "norm_rope", None)
    q, k, wq, wk, _, _ = operands(1, 32, 4, 2, jnp.bfloat16)
    got = compute(pair_node(q, k, "nr_cpu"), q, k, wq, wk)
    assert (got[0] == plain(q, wq)).all() and (got[1] == plain(k, wk)).all()
    assert choices() == {}
    assert "qk_norm_rope" in dispatch.NO_CHOICE_OFF_TPU


# -- Mosaic takes the kernels at the cell's shape ------------------------------

@pytest.mark.parametrize("H,KV,dtype", [
    (32, 4, "bfloat16"),        # the SDAR cell: [1, 16384, 4096] on [.., 512]
    (4, 4, "bfloat16"),         # no third grid axis
    (16, 16, "float32"),        # the widest blocks: six of 1 MiB a program
])
def test_mosaic_compiles_both_kernels(one_chip, monkeypatch, H, KV, dtype):
    """Forward and backward once each under the default scoped VMEM, and
    around them no view by heads nor (bf16) an f32 array of q's shape."""
    import re
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    jax.clear_caches()
    S = 16384
    one = lambda s, dt=dtype: jax.ShapeDtypeStruct(s, jnp.dtype(dt),
                                                   sharding=one_chip)
    q, k, w = one((1, S, H * D)), one((1, S, KV * D)), one((D,))
    assert kernels.norm_unsupported(q, k, w, w, head_dim=D) is None

    def loss(q, k, wq, wk, t):
        a, b = kernels.norm_rope(q, k, wq, wk, t, EPS)
        assert a.shape == q.shape and b.shape == k.shape
        return (jnp.sum(a.astype(jnp.float32) ** 2)
                + jnp.sum(b.astype(jnp.float32) ** 2))
    try:
        with jax.default_device(None):
            hlo = jax.jit(jax.grad(loss, (0, 1, 2, 3))).lower(
                q, k, w, w, one((2, S, D), "float32")).compile().as_text()
    finally:
        jax.clear_caches()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2
    assert ("hetu_qk_norm_rope_fwd" in calls[0]
            and "hetu_qk_norm_rope_bwd" in calls[1])
    entry = hlo[hlo.index("\nENTRY "):]            # what reaches HBM
    assert not re.findall(rf" = \w+\[1,{S},\d+,{D}\]\S* ", entry)
    if dtype == "bfloat16":
        assert not re.findall(rf" = f32\[1,{S},{H * D}\]\S* ", entry)
