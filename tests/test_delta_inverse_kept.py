"""The delta rules' backward kernels read the chunk's inverse that their
forward kernel wrote (``ops/pallas/gated_delta.py``, ``ops/pallas/kda.py``;
interpret mode): for the scalar rule and both entries of KDA's pair, at 600
positions (two programs along the sequence, a ragged chunk and padding behind
it) with f32 and bf16 operands, what the forward kernel writes is
``common.unit_lower_inverse`` of the chunk's own ``L`` bit for bit; the
gradients are the ``jax.numpy`` form's within the limits the rules' own test
files hold and no farther from the recurrence than the parent's kernels, which
solved a third time; no stage of the substitution is left in a backward
kernel's trace; and a counter says which kernel solved and which read."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import telemetry
from hetu_tpu.ops import gated_delta, kda
from hetu_tpu.ops.pallas import common, dispatch
from hetu_tpu.ops.pallas import gated_delta as gdn_kernels
from hetu_tpu.ops.pallas import kda as kda_kernels
from hetu_tpu.ops.pallas.common import C
from test_gated_delta_kernel import delta_inputs, mixed_inputs
from test_kda import D, draw, rel
from test_kda_in_place import layer_arrays, mixer_in_place, mixer_jnp

T, H = 600, 2
ENTRIES = ("gdn", "plain", "in_place")
#: the scalar rule's entry that reads the convolution's output (PR 69): one
#: key head under two value heads
GDN_IN_PLACE = "gdn_in_place"
DTYPES = ("float32", "bfloat16")
LOWER = -5.0                         # the gate's bound, as ``mixer_in_place``'s


def case(entry, dtype):
    """``(operands, names, kernels, jax.numpy form, recurrence)`` of an
    entry: each form a function of the operands that returns a tuple of f32
    arrays (``o`` and the last state, or the mixer's ``y``)."""
    dtype = jnp.dtype(dtype)
    f32 = lambda rule: lambda *a: tuple(
        t.astype(jnp.float32) for t in rule(*a))
    if entry == "gdn":
        return (delta_inputs(T, 1, H, dtype, seed=1),
                ("o", "s", "dq", "dk", "dv", "dg", "dbeta"),
                f32(gdn_kernels.gated_delta_rule),
                f32(gated_delta.chunk_gated_delta_rule_jnp),
                f32(gated_delta.recurrent_gated_delta_rule))
    if entry == GDN_IN_PLACE:
        return (mixed_inputs(T, 1, H, dtype, seed=1), ("o",),
                lambda *a: (gdn_kernels.gated_delta_rule_in_place(
                    *a, dk=D, dv=D, rep=H).astype(jnp.float32),), None, None)
    if entry == "plain":
        return (draw(64, T, H=H, dtype=dtype),
                ("o", "s", "dq", "dk", "dv", "dg", "dbeta"),
                f32(kda_kernels.kda), f32(kda.chunk_kda_jnp),
                f32(kda.recurrent_kda))
    one = lambda rule: lambda *a: (rule(*a).astype(jnp.float32),)
    return (layer_arrays(T + 1, 1, T, dtype, H=H),
            ("y", "dmixed", "dproj", "dbeta_lin", "da_log", "ddt_bias",
             "dscale"),
            one(mixer_in_place), one(functools.partial(mixer_jnp, H=H)),
            one(functools.partial(mixer_jnp, H=H, rule=kda.recurrent_kda)))


def values_and_grads(fn, x):
    """``fn``'s outputs and the gradients of their weighted sum (weights
    drawn once, the same for every form) by every operand."""
    outs = jax.eval_shape(fn, *x)
    ks = jax.random.split(jax.random.PRNGKey(99), len(outs))
    ws = [jax.random.normal(k, o.shape) for k, o in zip(ks, outs)]

    def loss(*a):
        outs = fn(*a)
        return sum(jnp.sum(o * w) for o, w in zip(outs, ws)), outs
    (_, outs), grads = jax.value_and_grad(
        loss, argnums=tuple(range(len(x))), has_aux=True)(*x)
    return tuple(outs) + tuple(grads)


@functools.lru_cache(maxsize=None)
def readings(entry, dtype):
    """``{name: (kernels, jax.numpy form, recurrence on the f32
    operands)}``, f32 numpy arrays."""
    x, names, kernels, form, recurrence = case(entry, dtype)
    exact = tuple(t.astype(jnp.float32) for t in x)
    got, want, true = (values_and_grads(fn, ops) for fn, ops in (
        (kernels, x), (form, x), (recurrence, exact)))
    return {n: tuple(np.asarray(t.astype(jnp.float32)) for t in ts)
            for n, *ts in zip(names, got, want, true)}


# -- (a) what the forward kernel writes ---------------------------------------

def forward_call(module, monkeypatch, fn, x):
    """The operands ``module._fwd_call`` got from ``fn(*x)`` and what it
    returned."""
    seen, real = [], module._fwd_call

    def spy(*ops, **kw):
        seen.append((ops, real(*ops, **kw)))
        return seen[-1][1]
    monkeypatch.setattr(module, "_fwd_call", spy)
    fn(*x)
    (ops, outs), = seen
    return ops, outs


@jax.jit
def gdn_inverse(k, g_row, beta_row):
    c = gdn_kernels._chunk_open(k, g_row, beta_row, solve=True)
    (T_,) = common.together([common.unit_lower_inverse(c["L"], c["LT"])])
    return c["L"], T_


@functools.partial(jax.jit, static_argnames="gate")
def kda_inverse(q, k, g, beta_row, small, gate):
    (c,) = common.together([kda_kernels._open(
        q, k, g, beta_row, None if gate is None else small + (gate,))])
    eye = jnp.eye(C, dtype=jnp.bfloat16)
    (T_,) = common.together([common.unit_lower_inverse(
        c["L"], common.dot32(c["L"], eye, common.TN))])
    return c["L"], T_


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("entry", ENTRIES + (GDN_IN_PLACE,))
def test_the_forward_kernel_writes_the_inverse_it_solved_for(entry, dtype,
                                                            monkeypatch):
    """Every chunk and head of 600 positions (ten chunks, the last of 24
    positions, and six of padding, whose ``L`` is 0 and inverse the
    identity): the kept ``[B, H, T / (n C), n, C, C]`` f32 array holds
    ``unit_lower_inverse`` of that chunk's ``L``, formed from the kernel's
    own operands with the kernel's own stages, bit for bit."""
    x, _, kernels, _, _ = case(entry, dtype)
    module = gdn_kernels if "gdn" in entry else kda_kernels
    ops, outs = forward_call(module, monkeypatch, kernels, x)
    kept = np.asarray(outs[3])
    beta = ops[4 if len(ops) == 5 else 2]
    B, heads, groups, nc, _ = beta.shape
    assert kept.shape == (B, heads, groups, nc, C, C)
    assert kept.dtype == np.float32 and (groups, nc) == (2, 8)
    lanes = lambda t, h, window=0: t[0, :, (window * heads + h) * D:][:, :D]
    for h in range(heads):
        for n in range(groups * nc):
            rows = slice(n * C, (n + 1) * C)
            row = lambda t: t[0, h, n // nc, n % nc][None]
            if entry == "gdn":
                _, k, _, g, _ = ops
                L, want = gdn_inverse(lanes(k, h)[rows], row(g), row(beta))
            elif entry == GDN_IN_PLACE:
                # the one key head's k~ (the second window of ``mixed``)
                # through the kernel's own norm, rounded as the kernel does
                mixed, g, _ = ops
                k = common.unit(mixed[0, rows, D:2 * D])[0]
                L, want = gdn_inverse(k.astype(mixed.dtype), row(g),
                                      row(beta))
            elif entry == "plain":
                q, k, _, g, _ = ops
                L, want = kda_inverse(lanes(q, h)[rows], lanes(k, h)[rows],
                                      lanes(g, h)[rows], row(beta), None,
                                      gate=None)
            else:
                mixed, proj, _, rate, bias, _ = ops
                small = tuple(t[:, h * D:(h + 1) * D] for t in (rate, bias))
                L, want = kda_inverse(
                    lanes(mixed, h)[rows], lanes(mixed, h, 1)[rows],
                    lanes(proj, h, 3)[rows], row(beta), small, gate=LOWER)
            np.testing.assert_array_equal(kept[0, h, n // nc, n % nc],
                                          np.asarray(want), f"{h}, {n}")
            assert np.asarray(L).any() == (n * C < T)
            if n * C >= T:
                np.testing.assert_array_equal(np.asarray(want), np.eye(C))


# -- (b) the gradients are the parent's ---------------------------------------

#: the kernels' limits against the ``jax.numpy`` form on the same operands, as
#: ``tests/test_gated_delta_kernel.py`` (f32: 1e-5 of the largest; bf16: a step
#: or two of it for what is bf16, 1e-4 for what is f32) and ``tests/test_kda.py``
#: (5e-5; bf16 2e-2) hold them
LIMITS = {
    ("gdn", "float32"): dict.fromkeys(
        ("o", "s", "dq", "dk", "dv", "dg", "dbeta"), 1e-5),
    ("gdn", "bfloat16"): dict(o=2e-3, s=5e-6, dq=1e-2, dk=1e-2, dv=1e-2,
                              dg=1e-4, dbeta=1e-4),
    ("plain", "float32"): dict.fromkeys(
        ("o", "s", "dq", "dk", "dv", "dg", "dbeta"), 5e-5),
    ("plain", "bfloat16"): dict(o=2e-2, s=5e-3, dq=2e-2, dk=2e-2, dv=2e-2,
                                dg=2e-2, dbeta=2e-2),
}

#: the largest relative deviation from the recurrence (f32, the same operands)
#: of the kernels of commit 628f64a (PR 65, whose backward kernels solved for
#: the inverse again) on ``case(entry, dtype)``: recorded from that tree with
#: ``readings`` above.  This tree's kernels gave the same bits
PARENT = {
    ("gdn", "float32"): dict(
        o=1.686e-06, s=1.637e-07, dq=9.971e-07, dk=2.542e-07, dv=2.086e-07,
        dg=5.515e-07, dbeta=1.473e-07),
    ("gdn", "bfloat16"): dict(
        o=3.765e-03, s=9.673e-05, dq=4.011e-03, dk=2.745e-03, dv=2.750e-03,
        dg=2.474e-04, dbeta=1.031e-04),
    ("plain", "float32"): dict(
        o=1.635e-06, s=7.220e-07, dq=3.238e-06, dk=1.883e-06, dv=4.854e-07,
        dg=3.674e-06, dbeta=3.782e-07),
    ("plain", "bfloat16"): dict(
        o=5.478e-03, s=2.592e-05, dq=6.056e-03, dk=2.173e-03, dv=2.615e-03,
        dg=6.608e-04, dbeta=9.311e-05),
    ("in_place", "float32"): dict(
        y=5.131e-06, dmixed=6.081e-06, dproj=4.941e-06, dbeta_lin=6.200e-06,
        da_log=1.706e-06, ddt_bias=4.315e-06, dscale=1.138e-06),
    ("in_place", "bfloat16"): dict(
        y=6.208e-03, dmixed=1.114e-02, dproj=6.827e-03, dbeta_lin=5.710e-03,
        da_log=2.805e-03, ddt_bias=1.300e-02, dscale=2.956e-03),
}


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("entry", ENTRIES)
def test_gradients_are_the_forms_and_no_farther_from_the_recurrence(entry,
                                                                    dtype):
    """``dq, dk, dv, dg, dbeta`` (in place: the arrays' with ``df`` and
    ``dz`` in ``dproj``, and the small parameters' sums) through the kept
    inverse: the ``jax.numpy`` form's within today's limits (a sum of bf16
    terms over all positions is held to the f32 form instead, as
    ``tests/test_kda.py`` holds the in-place entry: the scalar rule's ``dg``
    over 600 positions is 1.7e-4 from the ``jax.numpy`` form and nearer the
    recurrence than that form is), and from the recurrence
    no farther than 1.5 times the parent's kernels were (expected equal: no
    value changes; the factor is ``tests/test_kda_passes.py``'s)."""
    got = readings(entry, dtype)
    tol = 2e-2 if dtype == "bfloat16" else 5e-5
    for name, (a, b, c) in got.items():
        assert a.shape == b.shape == c.shape, name
        if entry == "in_place":
            assert rel(a, c) < max(tol, 2 * rel(b, c)), name
        else:
            assert (rel(a, b) < LIMITS[entry, dtype][name]
                    or rel(a, c) < rel(b, c)), name
        assert rel(a, c) <= 1.5 * PARENT[entry, dtype][name], name
    if entry == "in_place":
        dproj = got["dproj"][0]
        assert not dproj[..., :3 * H * D].any()
        assert dproj[..., 3 * H * D:4 * H * D].any()
        assert dproj[..., 4 * H * D:].any()


# -- (c) no stage of the substitution in a backward kernel --------------------

@pytest.mark.parametrize("form", ["plain", "in_place"])
def test_kdas_backward_chunk_has_no_merge_and_no_transpose_product(form):
    """56 products and 107 passes a chunk and head (83 / 134 with the
    inverse's two merges, 24, and ``L^T``, 3); the six ``[C, C, C]`` left are
    the inverse's cotangent ``-T^T dT T^T`` (``tests/test_kda_passes.py`` has
    the whole table)."""
    products = kda_kernels.chunk_products(form)
    assert (sum(products["bwd"].values()),
            kda_kernels.passes(products["bwd"])) == (56, 107)
    assert products["bwd"][C, C, C] == 6
    assert products["fwd"][C, C, C] == 3 + 24
    assert (sum(products["fwd"].values()),
            kda_kernels.passes(products["fwd"])) == (48, 60)


def test_gdns_backward_kernel_has_no_merge():
    """The scalar rule's only products of 64 rows, 64 of contraction and 64
    columns are the inverse's merges (two merges of two f32 products of six
    passes: 24 a head): ``_fwd_call``'s jaxpr has them for each of a
    program's heads and ``_bwd_call``'s has none, and five of the forward
    body's six ``exp`` a head (the transposed decays went with ``L^T``)."""
    q, k, v, g, beta = delta_inputs(C, 1, H, jnp.bfloat16)
    rows = lambda t: t.reshape(1, C, H * D)
    gates = lambda t: jnp.moveaxis(t, 2, 1).reshape(1, H, 1, 1, C)
    ops = (rows(q), rows(k), rows(v), gates(g), gates(beta))
    fwd = jax.make_jaxpr(functools.partial(
        gdn_kernels._fwd_call, interpret=True))(*ops)
    o, last, states, inverses = jax.eval_shape(functools.partial(
        gdn_kernels._fwd_call, interpret=True), *ops)
    bwd = jax.make_jaxpr(functools.partial(
        gdn_kernels._bwd_call, interpret=True))(*ops, states, inverses, o,
                                                last)
    count = lambda closed: kda_kernels._products(closed.jaxpr,
                                                 collections.Counter())
    assert count(fwd)[C, C, C] == 24 * H
    assert count(bwd)[C, C, C] == 0 and sum(count(bwd).values()) > 0

    def exps(jaxpr):
        return sum((e.primitive.name == "exp") + sum(
            exps(sub) for sub in jax.core.jaxprs_in_params(e.params))
            for e in jaxpr.eqns)
    assert exps(fwd.jaxpr) == 6 * H and exps(bwd.jaxpr) == 5 * H


# -- (d) the counter ----------------------------------------------------------

@pytest.mark.parametrize("entry", ENTRIES + (GDN_IN_PLACE,))
def test_a_forward_kernel_counts_solved_and_a_backward_kernel_kept(entry):
    """One traced ``jax.grad`` of a rule: its forward kernel ``solved`` once,
    its backward kernel ``kept`` once, and nothing else (a backward kernel
    that solved would be a second ``solved``)."""
    x, _, kernels, _, _ = case(entry, "bfloat16")
    rule = "gdn" if "gdn" in entry else "kda"
    telemetry.enable()
    try:
        telemetry.get_registry().reset()
        jax.eval_shape(jax.grad(lambda *a: sum(
            jnp.sum(o) for o in kernels(*a))), *x)
        assert sorted((lab["rule"], lab["source"], n) for lab, n in
                      dispatch.counted("hetu_delta_inverse_total")) == [
                          (rule, "kept", 1), (rule, "solved", 1)]
        jax.eval_shape(kernels, *x)          # a forward pass alone solves
        assert dict((lab["source"], n) for lab, n in dispatch.counted(
            "hetu_delta_inverse_total")) == {"kept": 1, "solved": 2}
    finally:
        telemetry.get_registry().reset()
        telemetry.disable()


@pytest.mark.parametrize("remat", [False, True])
def test_a_delta_net_layer_solves_once_recomputed_or_not(remat, monkeypatch):
    """A ``GatedDeltaNet``'s loss and gradients traced and lowered as on a
    TPU: ``hetu_gdn_fwd`` and ``hetu_gdn_bwd`` once each, ``solved`` once and
    ``kept`` once, whether the layer stands in an ``ht.remat()`` group or not
    (the parent's group ran, and counted, a second forward kernel: since PR
    69 the group keeps what the first wrote, ``dispatch.KEPT``)."""
    from conftest import kernel_calls
    from test_remat_kept import delta_net_layer, traced
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    jax.clear_caches()
    telemetry.enable()
    try:
        telemetry.get_registry().reset()
        ex, _ = delta_net_layer(f"dik_layer{int(remat)}", remat)
        text = traced(ex).lower(lowering_platforms=("tpu",)).as_text()
        ex.close()
        assert sorted((lab["rule"], lab["source"], n) for lab, n in
                      dispatch.counted("hetu_delta_inverse_total")) == [
                          ("gdn", "kept", 1), ("gdn", "solved", 1)]
    finally:
        telemetry.get_registry().reset()
        telemetry.disable()
        jax.clear_caches()
    assert kernel_calls(text, "hetu_gdn_fwd") == 1
    assert kernel_calls(text, "hetu_gdn_bwd") == 1
