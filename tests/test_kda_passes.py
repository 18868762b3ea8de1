"""What a chunk of the KDA rule's kernels asks of the matrix unit, and what
that costs in digits (``ops/pallas/kda.py``): the ``dot_general``s of one
chunk of one head, traced (no kernel runs), held to the table in that file's
docstring; the same count as a gauge; and the kernels (interpret mode)
against the recurrence, no farther from it than 1.5 times what the kernels
of the parent commit were on the same operands."""

import jax
import jax.numpy as jnp
import pytest

from hetu_tpu import telemetry
from hetu_tpu.ops import kda
from hetu_tpu.ops.pallas import dispatch
from hetu_tpu.ops.pallas import kda as kernels
from hetu_tpu.ops.pallas.common import C
from test_kda import D, draw, rel, weighted

#: a chunk's products by (rows, contraction, columns) and what they are; the
#: docstring of ``ops/pallas/kda.py`` has the same table by stage.  A product
#: at f32 precision is three ``dot_general``s where one operand is bf16 and
#: six where both are f32 (``dot32``), or ONE over the parts stacked along
#: the contraction (``dot32_stacked``: 3 or 6 times as deep)
PRODUCTS = {
    "fwd": {
        (C, C, D): 3 + 6 + 1,      # G = ones x g; u = T beta X; P u
        (C, D, C): 4 + 4,          # the pair matrices of k and of q
        (C, C, C): 3 + 24,         # L^T; the inverse's two merges
        (C, 6 * D, D): 1 + 1,      # (k e^G) S and (q e^G) S, stacked
        (D, 6 * C, D): 1,          # the next state, stacked down the rows
    },
    "bwd": {                       # the forward again, then its transposition,
        (C, C, D): 10 + 3 + 6 + 6 + 2,      # the inverse kept: no L^T (3), no
        (C, D, C): 8 + 1,                   # merges (24) among [C, C, C]
        (C, C, C): 6,
        (C, 6 * D, D): 2 + 4,
        (D, 6 * C, D): 1 + 2,
        (C, 6 * D, C): 2,
        (C, 6 * C, D): 1,
        (C, 6 * C, C): 1,
        (C, 3 * C, D): 1,          # dg = ones^T dG
    },
}
#: ``dot_general``s and passes of the matrix unit (a product's contraction
#: in slices of 128): 66 and 66 forward, 162 and 162 in the backward kernel
#: at PR 63; 83 and 134 in the backward kernel at PR 64, which solved for the
#: inverse again
TOTAL = {"fwd": (48, 60), "bwd": (56, 107)}


@pytest.mark.parametrize("form", ["plain", "in_place"])
@pytest.mark.parametrize("kernel,issue", [("fwd", 63), ("bwd", 150)])
def test_a_chunk_asks_for_the_products_of_the_table(form, kernel, issue):
    """``jax.make_jaxpr`` of ``_chunks`` for one head (``fwd``) and of
    ``jax.vjp`` of it as ``_bwd_kernel`` takes it, the inverse kept
    (``bwd``), for both
    entries: the products the docstring's table ends on, by shape, and never
    above ISSUE 64's 63 / 150, as ``dot_general``s or as passes.  A PR that
    adds a product takes the new count here and in the table."""
    products = kernels.chunk_products(form)[kernel]
    assert dict(products) == PRODUCTS[kernel]
    count = (sum(products.values()), kernels.passes(products))
    assert count == TOTAL[kernel] and max(count) <= issue


def test_the_count_is_a_gauge_beside_the_entry_counter():
    telemetry.enable()
    try:
        telemetry.get_registry().reset()
        assert kernels.entries() == {}
        x = draw(1, C, H=1, dtype=jnp.bfloat16)
        jax.eval_shape(kernels.kda, *x)
        assert kernels.entries() == {"plain": 1}
        assert {lab["kernel"]: n for lab, n in dispatch.counted(
            "hetu_kda_chunk_passes")} == {k: n for k, (_, n) in TOTAL.items()}
    finally:
        telemetry.get_registry().reset()
        telemetry.disable()


#: the largest relative deviation from ``recurrent_kda`` (f32, the same
#: operands) of the kernels of commit b2f8092 (PR 63: 66 / 162 products a
#: chunk) in interpret mode on ``draw(64, 600, lo=lo, dtype=dtype)`` with the
#: weights below: ``o``, the last state, and ``dq, dk, dv, dg, dbeta`` through
#: ``jax.grad``
PARENT = {
    ("float32", -5.0): dict(
        o=1.635e-06, s=7.410e-07, dq=3.238e-06, dk=1.883e-06, dv=4.896e-07,
        dg=3.674e-06, dbeta=3.660e-07),
    ("float32", -0.05): dict(
        o=5.096e-07, s=2.293e-07, dq=3.656e-07, dk=2.821e-07, dv=1.801e-07,
        dg=3.352e-07, dbeta=1.731e-07),
    ("bfloat16", -5.0): dict(
        o=5.478e-03, s=2.592e-05, dq=6.056e-03, dk=2.173e-03, dv=2.615e-03,
        dg=6.608e-04, dbeta=9.311e-05),
    ("bfloat16", -0.05): dict(
        o=3.935e-03, s=4.256e-04, dq=5.294e-03, dk=2.568e-03, dv=2.615e-03,
        dg=7.385e-04, dbeta=7.155e-04),
}


@pytest.mark.parametrize("dtype,lo", sorted(PARENT))
def test_the_kernels_are_no_farther_from_the_recurrence_than_the_parents(
        dtype, lo):
    """600 positions (two programs along the sequence, padding behind the
    last), gates down to the bound ``-5`` and near ``-0.05``, f32 and bf16
    operands: every deviation under 1.5 times the parent's."""
    x = draw(64, 600, lo=lo, dtype=jnp.dtype(dtype))
    exact = tuple(t.astype(jnp.float32) for t in x)
    ks = jax.random.split(jax.random.PRNGKey(99), 2)
    loss = lambda rule: weighted(
        lambda *a: tuple(t.astype(jnp.float32) for t in rule(*a)),
        jax.random.normal(ks[0], x[2].shape),
        jax.random.normal(ks[1], (1, 2, D, D)))
    got = dict(zip(("o", "s"), kernels.kda(*x)))
    want = dict(zip(("o", "s"), kda.recurrent_kda(*exact)))
    names = ("dq", "dk", "dv", "dg", "dbeta")
    got.update(zip(names, jax.grad(loss(kernels.kda), range(5))(*x)))
    want.update(zip(names, jax.grad(loss(kda.recurrent_kda), range(5))(*exact)))
    for name, limit in PARENT[dtype, lo].items():
        assert rel(got[name].astype(jnp.float32), want[name]) < 1.5 * limit, (
            name)
