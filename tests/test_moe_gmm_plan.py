"""The grouped products' blocks come from the product's own widths (PR 73).

``ops/pallas/moe_gmm.py gmm_plan`` / ``tgmm_plan`` are functions of ``(tm,
k, n, dtype)`` and ``VMEM_LIMIT`` alone.  Held here:

1. the three kernels in interpret mode against ``jax.lax.ragged_dot`` and
   its gradients at the benchmark's nine ``(hidden, expert width)`` pairs,
   on tiles of 128 and of 8 rows, with tiles past ``n_used`` and an expert
   that has no pair;
2. what the plan says at each of the nine: one contraction block for all six
   products of an expert layer, one pass over the rows for both weight
   gradients, the blocks' sum under the budget; and a made-up width that no
   budget takes whole, which gets a split into multiples of 128 and the same
   result;
3. the trace-time counter ``hetu_moe_gmm_plan_total``: once a traced kernel,
   nothing on the ``ragged`` path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops import moe as moe_ops
from hetu_tpu.ops.pallas import dispatch, moe_gmm

#: (hidden, expert width, rows of a tile in the cell) of the nine expert cells
WIDTHS = {
    "olmoe-1b-7b": (2048, 1024, 256),
    "qwen3-next-80b-a3b": (2048, 512, 128),
    "nemotron-3-nano-30b-a3b": (2688, 1856, 128),
    "ling-3.0-flash-vl": (2560, 768, 128),
    "laguna-xs.2": (2048, 512, 128),
    "xing4.0-29b-a4b": (3584, 1024, 128),
    "zaya1-8b": (2048, 2048, 128),
    "sdar-30b-a3b": (2048, 768, 128),
    "mellum2-12b-a2.5b": (2304, 896, 128),
}
BF16 = jnp.bfloat16


def laid_out(tm, k, n, seed=0):
    """Three experts on four row tiles: expert 0 fills a tile, expert 1 has
    no pair and owns a tile of zeros, expert 2 has half a tile, and the
    fourth tile is past ``n_used`` (it holds numbers that must not count)."""
    r = np.random.default_rng(seed)
    x = r.normal(size=(4 * tm, k)).astype(np.float32)
    x[tm:2 * tm] = 0
    x[2 * tm + tm // 2:3 * tm] = 0
    w = r.normal(size=(3, k, n)).astype(np.float32) / np.sqrt(k)
    dy = r.normal(size=(4 * tm, n)).astype(np.float32)
    return (jnp.asarray(x, BF16), jnp.asarray(w, BF16), jnp.asarray(dy, BF16),
            jnp.asarray([0, 1, 2, 2], jnp.int32), jnp.asarray([3], jnp.int32),
            jnp.asarray([tm, tm, tm], jnp.int32))


def ragged(x, w, dy, sizes):
    """``(y, dx, dw)`` of ``jax.lax.ragged_dot`` over the live rows in f32
    (the kernels round theirs to bf16 once)."""
    live = int(sizes.sum())
    y, pull = jax.vjp(lambda a, b: jax.lax.ragged_dot(
        a, b, sizes, preferred_element_type=jnp.float32),
        x[:live].astype(jnp.float32), w.astype(jnp.float32))
    dx, dw = pull(dy[:live].astype(jnp.float32))
    pad = lambda a: jnp.pad(a, ((0, x.shape[0] - live), (0, 0)))
    return pad(y), pad(dx), dw


def close(got, want):
    """Within one rounding to bf16 of the f32 result."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=2 ** -7,
                               atol=1e-3 * np.abs(want).max())


def products(x, w, dy, te, nu, tm):
    y = moe_gmm.gmm(x, w, te, nu, tm=tm)
    dx = moe_gmm.gmm(dy, w, te, nu, tm=tm, transpose_rhs=True,
                     name="hetu_moe_gmm_dx")
    dw = moe_gmm.tgmm(x, dy, te, nu, w.shape[0], tm=tm)
    return y, dx, dw


@pytest.mark.parametrize("tm", [128, 8])
@pytest.mark.parametrize("cell", sorted(WIDTHS))
def test_the_kernels_are_ragged_dot_at_the_cells_widths(cell, tm):
    """Both products of an expert (``hidden -> width`` and back): ``gmm``,
    ``gmm`` with ``transpose_rhs`` and ``tgmm``, each on whole blocks."""
    hidden, width, _ = WIDTHS[cell]
    for k, n in ((hidden, width), (width, hidden)):
        x, w, dy, te, nu, sizes = laid_out(tm, k, n)
        got = products(x, w, dy, te, nu, tm)
        for g, want in zip(got, ragged(x, w, dy, sizes)):
            close(g, want)
        assert not np.asarray(got[0][3 * tm:], np.float32).any()
        assert not np.asarray(got[1][3 * tm:], np.float32).any()
        assert not np.asarray(got[2][1], np.float32).any()


@pytest.mark.parametrize("cell", sorted(WIDTHS))
def test_every_cells_products_fetch_weights_once_and_walk_the_rows_once(cell):
    hidden, width, tm = WIDTHS[cell]
    for k, n in ((hidden, width), (width, hidden)):
        fwd = moe_gmm.gmm_plan(tm, k, n, BF16)      # fwd, and the other's dx
        dw = moe_gmm.tgmm_plan(tm, k, n, BF16)
        assert (fwd.tk, fwd.tn, fwd.k_blocks, fwd.n_blocks) == (k, n, 1, 1)
        assert (dw.tk, dw.tn, dw.k_blocks * dw.n_blocks) == (k, n, 1)
        size = 2
        assert fwd.vmem == 2 * size * (tm * k + k * n + tm * n) + 4 * tm * n
        assert dw.vmem == 2 * size * (tm * k + tm * n + k * n) + 4 * k * n
        assert max(fwd.vmem, dw.vmem) <= moe_gmm.BLOCK_BUDGET \
            < moe_gmm.VMEM_LIMIT


def test_the_plan_reads_the_widths_the_tile_and_the_type_alone():
    """Wider than the budget: ``tgmm`` cuts where the passes are fewest and
    the operands read again are least; f32 operands weigh twice; a width no
    multiple of 128 divides is one block (``WHOLE_WIDTH``)."""
    assert moe_gmm.blocks(896) == [896, 128]
    assert moe_gmm.blocks(1856) == [1856]
    assert moe_gmm.blocks(2304) == [2304, 1152, 768, 384, 256, 128]
    p = moe_gmm.tgmm_plan(128, 4096, 2048, BF16)     # 67 MB whole
    assert (p.tk, p.tn, p.k_blocks, p.n_blocks) == (2048, 2048, 2, 1)
    assert p.vmem <= moe_gmm.BLOCK_BUDGET
    assert moe_gmm.tgmm_plan(128, 2048, 4096, BF16)[:2] == (2048, 2048)
    q = moe_gmm.tgmm_plan(128, 2688, 1856, jnp.float32)  # 62 MB whole in f32
    assert (q.tk, q.tn) == (896, 1856) and q.vmem <= moe_gmm.BLOCK_BUDGET
    g = moe_gmm.gmm_plan(256, 8192, 4096, BF16)      # whole k, n in quarters
    assert (g.tk, g.tn, g.k_blocks, g.n_blocks) == (8192, 1024, 1, 4)
    assert g.vmem <= moe_gmm.BLOCK_BUDGET


def test_a_contraction_no_budget_takes_whole_is_split_by_128s():
    """98,304 wide: a row tile and a 128-wide weight block of it are past
    the budget, so ``gmm`` splits the contraction as before PR 73 (48 blocks
    of 2,048) and the result is ``ragged_dot``'s all the same."""
    tm, k, n = 8, 98304, 128
    plan = moe_gmm.gmm_plan(tm, k, n, BF16)
    assert (plan.tk, plan.tn, plan.k_blocks) == (2048, 128, 48)
    assert plan.tk % 128 == 0 and plan.vmem <= moe_gmm.BLOCK_BUDGET
    whole = 2 * 2 * (tm * k + k * 128 + tm * 128) + 4 * tm * 128
    assert whole > moe_gmm.BLOCK_BUDGET
    x, w, dy, te, nu, sizes = laid_out(tm, k, n, seed=1)
    want, _, _ = ragged(x, w, dy, sizes)
    close(moe_gmm.gmm(x, w, te, nu, tm=tm), want)
    close(moe_gmm.gmm(x, jnp.swapaxes(w, 1, 2), te, nu, tm=tm,
                      transpose_rhs=True, name="hetu_moe_gmm_dx"), want)


# -- the counter --------------------------------------------------------------

def plans():
    return sorted((lab["kernel"], lab["k_blocks"], lab["row_passes"], n)
                  for lab, n in dispatch.counted("hetu_moe_gmm_plan_total"))


@pytest.fixture
def counting(live_registry):
    """The registry on and empty of plans, jax's caches empty: a kernel is
    traced, and counted, once a program."""
    live_registry.reset()
    jax.clear_caches()
    yield
    live_registry.reset()


def test_a_traced_kernel_counts_its_plan_once(counting):
    """An expert's product forward and backward: three kernels, three
    counts, however often the traced functions are called; a second shape
    is a second trace."""
    tm, k, n = 8, 256, 128
    x, w, dy, te, nu, _ = laid_out(tm, k, n)

    def loss(x, w):
        return jnp.sum(moe_gmm.grouped_matmul(x, w, te, nu, tm, 3)
                       .astype(jnp.float32) ** 2)
    for _ in range(2):
        jax.eval_shape(jax.grad(loss, argnums=(0, 1)), x, w)
    assert plans() == [("hetu_moe_gmm_dw", "1", "1", 1),
                       ("hetu_moe_gmm_dx", "1", "1", 1),
                       ("hetu_moe_gmm_fwd", "1", "1", 1)]
    big = laid_out(tm, 98304, n)
    jax.eval_shape(lambda x, w: moe_gmm.gmm(x, w, te, nu, tm=tm), *big[:2])
    assert ("hetu_moe_gmm_fwd", "48", "1", 1) in plans()


@pytest.mark.parametrize("impl,want", [("pallas", 6), ("ragged", 0)])
def test_the_ragged_path_counts_no_plan(counting, impl, want):
    """``dropless_moe`` with gated experts under ``jax.grad``: the Pallas
    form traces ``fwd`` twice (``hidden -> width`` for gate and up is one
    trace, ``width -> hidden`` another), ``dx`` and ``dw`` twice each; the
    ``ragged`` form none."""
    T, H, F, E, k = 64, 128, 256, 4, 2
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(T, H)), jnp.float32)
    idx = jnp.asarray(r.integers(0, E, size=(T, k)), jnp.int32)
    gate = jnp.full((T, k), 0.5, jnp.float32)
    w = [jnp.asarray(r.normal(size=s), jnp.float32) / 16
         for s in ((E, H, F), (E, H, F), (E, F, H))]

    def loss(x, *w):
        return jnp.sum(moe_ops.dropless_moe(x, idx, gate, *w,
                                            impl=impl)[0] ** 2)
    jax.eval_shape(jax.grad(loss, argnums=(0, 1, 2, 3)), x, *w)
    assert sum(p[-1] for p in plans()) == want
    assert all(p[1:3] == ("1", "1") for p in plans())
