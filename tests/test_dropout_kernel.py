"""Where ``DropoutOp`` draws its keep mask (ops/nn.py, ops/pallas/dropout.py).

The kernel draws from the chip's own generator, which neither the cpu nor
Pallas's interpret modes have, so what runs here is the decision, the jnp
form it leaves in place, and the traced (not executed) kernel path.  The
kernel is compiled for a described v5e in tests/test_flash_attention.py,
the one file that may load libtpu; its statistics are in PERF.md.
"""

import types

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import hetu_tpu as ht
from conftest import jaxpr_primitives
from hetu_tpu.ops import nn
from hetu_tpu.ops.pallas import dispatch


def _mesh(axes):
    from hetu_tpu.parallel import make_mesh
    return make_mesh(axes) if axes else None


def _ctx(training=True, mesh=None, seed=0):
    return types.SimpleNamespace(
        training=training, mesh=mesh,
        rng_for=lambda op: jax.random.fold_in(jax.random.key(seed), op.id))


def _dropout(shape, keep=0.9):
    return ht.dropout_op(ht.placeholder_op(
        f"dk_{'_'.join(map(str, shape))}_{keep}", shape), keep_prob=keep)


@pytest.mark.parametrize("shape,platform,axes,reason,batch_axes", [
    ((64, 512, 768), "tpu", None, None, ()),              # a BERT shard
    ((256, 512, 768), "tpu", {"dp": 4}, None, ("dp",)),   # DataParallel(4)
    ((256, 512, 768), "tpu", {"dp": 4, "tp": 1}, None, ("dp",)),
    ((64, 768), "tpu", None, None, ()),                   # whole int8 tiles
    ((8, 768), "tpu", None, "rows_not_32_aligned", ()),   # a pooled vector
    ((64, 768), "tpu", {"dp": 4}, "rows_not_32_aligned", ()),  # 16 a shard
    ((8, 100, 768), "tpu", None, "rows_not_32_aligned", ()),
    ((8, 3, 32, 32), "tpu", None, "last_dim_not_128_aligned", ()),  # CNN
    ((64, 512, 1000), "tpu", None, "last_dim_not_128_aligned", ()),
    ((768,), "tpu", None, "last_dim_not_128_aligned", ()),
    ((), "tpu", None, "last_dim_not_128_aligned", ()),
    ((64, 512, 768), "tpu", {"dp": 2, "tp": 2}, "mesh_axis:tp=2", ()),
    ((64, 512, 768), "tpu", {"dp": 2, "cp": 2}, "mesh_axis:cp=2", ()),
    ((64, 512, 768), "tpu", {"dp": 1, "pp": 2}, "mesh_axis:pp=2", ()),
    ((6, 512, 768), "tpu", {"dp": 4}, "mesh_axis:dp=4", ()),
    ((64, 512, 768), "cpu", None, "platform:cpu", ()),
    ((256, 512, 768), "cpu", {"dp": 4}, "platform:cpu", ()),
])
def test_mask_plan(monkeypatch, shape, platform, axes, reason, batch_axes):
    monkeypatch.setattr(dispatch, "platform", lambda: platform)
    assert nn._dropout_mask_plan(shape, _mesh(axes)) == (reason, batch_axes)


@pytest.mark.parametrize("shape,keep", [
    ((4, 32, 128), 0.9),       # what the kernel would take on a tpu
    ((8, 768), 0.9),
    ((2, 3, 8, 8), 0.5),
    ((16,), 0.25),
])
def test_cpu_output_is_bitwise_the_bernoulli_form(rng, shape, keep):
    op = _dropout(shape, keep)
    ctx = _ctx(seed=3)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    mask = jax.random.bernoulli(ctx.rng_for(op), keep, shape)
    want = jnp.where(mask, x / keep, 0.0).astype(x.dtype)
    got = op._compute([x], ctx)
    assert got.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _dropout_choices():
    return {k: n for k, n in dispatch.choices().items() if k[0] == "dropout"}


@pytest.mark.parametrize("training,keep,counted", [
    (True, 0.9, 1),        # a mask is drawn: one choice
    (False, 0.9, 0),       # a validate subgraph draws none
    (True, 1.0, 0),
    (False, 1.0, 0),
])
def test_choice_is_recorded_only_when_a_mask_is_drawn(live_registry,
                                                      training, keep,
                                                      counted):
    op = _dropout((4, 32, 128), keep)
    x = jnp.ones((4, 32, 128), jnp.float32)
    before = _dropout_choices()
    out = op._compute([x], _ctx(training=training))
    after = _dropout_choices()
    key = ("dropout", "jnp", "platform:cpu")
    assert after.get(key, 0) - before.get(key, 0) == counted
    assert {k for k in after if k != key} == {k for k in before if k != key}
    if not counted:
        assert out is x


def test_dropout2d_keeps_its_own_mask_and_records_nothing(live_registry):
    op = ht.dropout2d_op(ht.placeholder_op("dk_2d", (4, 128, 8, 8)), 0.5)
    ctx = _ctx(seed=5)
    x = jnp.ones((4, 128, 8, 8), jnp.float32)
    before = _dropout_choices()
    got = op._compute([x], ctx)
    assert _dropout_choices() == before
    mask = jax.random.bernoulli(ctx.rng_for(op), 0.5, (4, 128))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.where(mask, 2.0, 0.0)
                                    )[:, :, None, None] * np.ones((8, 8)))


@pytest.mark.parametrize("shape,keep", [((4, 32, 128), 0.9),
                                        ((8, 48), 0.5)])
def test_gradient_is_the_cotangent_times_mask_over_keep(rng, shape, keep):
    op = _dropout(shape, keep)
    # the key is this test's own, not folded with the op's id (which counts
    # what the process built before): the same mask in every run
    ctx = _ctx()
    ctx.rng_for = lambda op: jax.random.key(7)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    out, vjp = jax.vjp(lambda x: op._compute([x], ctx), x)
    (dx,) = vjp(g)
    mask = np.asarray(out) != 0
    assert abs(mask.mean() - keep) < 0.05
    np.testing.assert_allclose(np.asarray(dx),
                               np.where(mask, np.asarray(g) / keep, 0.0),
                               rtol=1e-6)


@pytest.mark.parametrize("axes,local", [
    (None, (64, 512, 768)),
    ({"dp": 4}, (16, 512, 768)),       # each device: its own rows
])
def test_traced_as_on_tpu_the_op_holds_one_kernel_and_no_wide_draw(
        monkeypatch, live_registry, axes, local):
    """What a TPU step would hold, forward and backward: one
    ``hetu_dropout_mask`` call of the local shard's shape, no random words
    of the activation's size, and the choice counted as ``pallas``."""
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    shape = (64, 512, 768)
    op = _dropout(shape)
    ctx = _ctx(mesh=_mesh(axes))
    took = ("dropout", "pallas", "")
    before = dispatch.choices().get(took, 0)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x: jnp.sum(op._compute([x], ctx).astype(jnp.float32) ** 2)))(
            jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    assert dispatch.choices()[took] == before + 1
    eqns = list(jaxpr_primitives(jaxpr.jaxpr))
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in kernels] == ["hetu_dropout_mask"]
    (mask,) = kernels[0].outvars
    assert mask.aval.dtype == jnp.int8
    assert mask.aval.shape == (local[0] * local[1], local[2])
    assert sum(e.primitive.name == "shard_map" for e in eqns) == bool(axes)
    drawn = [e.outvars[0].aval.shape for e in eqns
             if e.primitive.name == "random_bits"]
    assert drawn == [(1,)]            # the seed, and nothing else


def test_sharded_mask_is_laid_out_over_dp(monkeypatch):
    """The shard_map plumbing, run: the TPU interpret mode has no generator
    (its random words are zeros, so every element is kept), but it runs the
    kernel per shard and assembles the global mask over ``dp``."""
    from jax.experimental.pallas import tpu as pltpu
    from jax.sharding import NamedSharding, PartitionSpec as P
    from hetu_tpu.ops.pallas import dropout as D
    monkeypatch.setattr(D, "interpret", lambda: pltpu.InterpretParams())
    D._mask.clear_cache()
    mesh = _mesh({"dp": 4})
    seed = jnp.asarray([11], jnp.int32)
    try:
        mask = jax.jit(lambda s: D.sharded_dropout_mask(
            mesh, s, (8, 32, 128), 0.9, batch_axes=("dp",)))(seed)
    finally:
        D._mask.clear_cache()
    assert mask.shape == (8, 32, 128) and mask.dtype == jnp.int8
    assert mask.sharding.is_equivalent_to(
        NamedSharding(mesh, P("dp", None, None)), 3)
    assert bool((mask == 1).all())


def test_keep_threshold_has_one_definition():
    """Attention's in-kernel dropout and the mask kernel compare the same
    words with the same threshold, through one helper."""
    from hetu_tpu.ops.pallas import dropout as D, flash_attention as F
    assert D.tile_keep is F.tile_keep
    assert not hasattr(D, "_keep_threshold")
    assert int(F._keep_threshold(0.9)) == int(0.9 * 2 ** 32)
    assert int(F._keep_threshold(1.0)) == 2 ** 32 - 1
