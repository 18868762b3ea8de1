"""The router's choice by selection (``ops/moe.py select_k``,
``ops/pallas/moe_select.py hetu_moe_select``): ``jax.lax.top_k``'s indices,
bit for bit.

1. ``select_k`` with the kernel asked for (interpret mode) against
   ``jax.lax.top_k(x, k)[1]`` over the seven cells' ``(E, k)`` and over rows
   with exact ties, rows of ``-inf``, token counts that are no whole tile and
   expert counts that are no whole lane tile.
2. ``top_k_route`` through the kernel against the parent's form (three
   ``top_k`` calls, written out below): ``idx``, ``gate``, ``probs`` and the
   gradient of a sum of the gates, with and without groups, softmax and
   sigmoid with a bias.
3. Which form a call takes, and what it records.
"""

import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.ops import moe
from hetu_tpu.ops.pallas import dispatch, moe_select

#: the seven expert cells' routers: OLMoE, Qwen3-Next, Nemotron-H, Ling-3.0,
#: Laguna, Xing4.0, ZAYA1 (16 experts and a skip choice)
CELLS = [(64, 8), (512, 10), (128, 6), (512, 8), (256, 8), (64, 4), (17, 1)]


def top_k(x, k):
    return np.asarray(jax.lax.top_k(jnp.asarray(x), k)[1], np.int32)


def selected(x, k):
    got = moe.select_k(jnp.asarray(x), k, asked=True)
    assert got.dtype == jnp.int32
    return np.asarray(got)


def scores(seed, tokens, experts):
    return np.random.default_rng(seed).standard_normal(
        (tokens, experts)).astype(np.float32)


def coarse(x, steps=1):
    """``x`` rounded to ``1 / steps``: many exact ties.  No ``-0.0``: the
    kernel compares as floats do, ``top_k`` orders it below ``0.0``, and no
    sigmoid or softmax makes one."""
    return np.round(x * steps) / steps + 0.0


# -- 1. the indices ----------------------------------------------------------------

@pytest.mark.parametrize("experts,k", CELLS)
def test_the_cells_routers(experts, k):
    x = scores(experts + k, 256, experts)
    np.testing.assert_array_equal(selected(x, k), top_k(x, k))


@pytest.mark.parametrize("experts,k", CELLS)
def test_ties_go_to_the_lower_index(experts, k):
    """Rows of few distinct values, a constant row, a row that rises and one
    that falls in steps of two equal entries."""
    x = coarse(scores(3, 128, experts))
    x[0] = 0.5
    x[1] = np.arange(experts) // 2
    x[2] = -(np.arange(experts) // 2)
    np.testing.assert_array_equal(selected(x, k), top_k(x, k))


@pytest.mark.parametrize("experts,k", CELLS)
def test_rows_of_minus_infinity(experts, k):
    """``-inf`` in all but ``k`` entries (a group-limited row), in all but
    one, in every entry, and a finite row between them: the ``-inf`` entries
    come last, by index among themselves."""
    x = scores(5, 128, experts)
    keep = np.random.default_rng(6).permuted(
        np.tile(np.arange(experts) < k, (32, 1)), axis=1)
    x[:32] = np.where(keep, x[:32], -np.inf)
    x[32, 1:] = -np.inf
    x[33, :-1] = -np.inf
    x[34] = -np.inf
    x[36, ::2] = -np.inf
    np.testing.assert_array_equal(selected(x, k), top_k(x, k))


@pytest.mark.parametrize("tokens,experts,k", [
    (1, 64, 8), (77, 128, 6), (257, 256, 8), (300, 200, 7), (40, 24, 5),
    (130, 130, 9), (9, 512, 512)])
def test_no_whole_tiles(tokens, experts, k):
    """``T`` no multiple of the token tile, ``E`` no multiple of 128: padded
    inside, and a padded entry is never chosen (``k = E`` takes them all)."""
    x = coarse(scores(tokens, tokens, experts), 4)
    np.testing.assert_array_equal(selected(x, k), top_k(x, k))


@pytest.mark.parametrize("tt", [128, 512])
def test_the_token_tile_is_free(tt):
    x = scores(11, 640, 256)
    np.testing.assert_array_equal(
        np.asarray(moe_select.select(jnp.asarray(x), 8, tt=tt)), top_k(x, 8))


# -- 2. the router -----------------------------------------------------------------

def parent_route(logits, k, renorm=False, score="softmax", bias=None,
                 scale=None, groups=None):
    """``top_k_route`` as it was before it selected: three ``top_k`` calls."""
    if score == "softmax":
        scores = probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    else:
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    chosen_by = scores if bias is None else scores + bias.astype(jnp.float32)
    if groups is not None and groups[0] > 1:
        n_group, topk_group = groups
        T, E = chosen_by.shape
        by_group = chosen_by.reshape(T, n_group, E // n_group)
        group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        _, best = jax.lax.top_k(group_score, topk_group)
        kept = jnp.sum(jax.nn.one_hot(best, n_group, dtype=jnp.int32),
                       axis=1) > 0
        chosen_by = jnp.where(kept[:, :, None], by_group,
                              -jnp.inf).reshape(T, E)
    _, idx = jax.lax.top_k(chosen_by, k)
    gate = jnp.sum(jax.nn.one_hot(idx, scores.shape[-1], dtype=scores.dtype)
                   * scores[:, None, :], axis=-1)
    if renorm:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    if scale is not None:
        gate = gate * scale
    return idx.astype(jnp.int32), gate, probs


@pytest.fixture
def asked(monkeypatch):
    """``top_k_route`` asks for its kernel as it does on a TPU, and gets it
    in interpret mode (``dispatch.take(asked=True)``)."""
    real = moe.select_k
    monkeypatch.setattr(moe, "select_k", lambda x, k, mesh=None:
                        real(x, k, mesh, asked=True))


ROUTERS = {
    "olmoe": (64, 8, dict()),
    "mixtral": (64, 2, dict(renorm=True)),
    "qwen3_next": (512, 10, dict(renorm=True)),
    "nemotron_h": (128, 6, dict(renorm=True, score="sigmoid", bias=True,
                                scale=2.5)),
    "ling3": (512, 8, dict(renorm=True, score="sigmoid", bias=True,
                           scale=2.5, groups=(8, 4))),
    "ling3_ties": (512, 8, dict(score="sigmoid", bias=True, groups=(8, 4),
                                coarse=True)),
    "two_groups_of_32": (64, 4, dict(score="sigmoid", groups=(2, 1))),
    "one_group": (64, 4, dict(score="sigmoid", groups=(1, 1))),
    "softmax_groups": (128, 6, dict(groups=(4, 2), bias=True)),
    "zaya1": (17, 1, dict(bias=True)),
}


@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_the_router_is_the_parents_bit_for_bit(asked, live_registry, name):
    experts, k, kw = ROUTERS[name]
    kw = dict(kw)
    logits = scores(len(name), 192, experts)
    if kw.pop("coarse", False):         # equal scores, equal group scores
        logits = coarse(logits)
    logits = jnp.asarray(logits)
    if kw.pop("bias", False):
        kw["bias"] = jnp.asarray(scores(1, 1, experts)[0] * 0.05)
    before = dispatch.choices().get(("moe_select", "pallas", ""), 0)
    got = moe.top_k_route(logits, k, **kw)
    calls = dispatch.choices().get(("moe_select", "pallas", ""), 0) - before
    grouped = kw.get("groups", (1, 1))[0] > 1
    assert calls == (0 if k == 1 else 1) + (grouped and kw["groups"][1] > 1)
    want = parent_route(logits, k, **kw)
    for a, b in zip(got, want, strict=True):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def gates(route):
        return jax.grad(lambda x: jnp.sum(
            route(x, k, **kw)[1] * jnp.arange(1.0, k + 1)))(logits)
    np.testing.assert_array_equal(np.asarray(gates(moe.top_k_route)),
                                  np.asarray(gates(parent_route)))


def test_the_two_largest_of_a_group():
    """``m1 + m2`` with ``m2`` the maximum once the FIRST position of ``m1``
    is taken out: ``top_k(.., 2)[0].sum(-1)``, the same two f32 numbers."""
    x = scores(2, 64, 512).reshape(64, 8, 64)
    x[0] = coarse(x[0])                  # the maximum stands twice
    x[1, 0] = 1.25                       # every entry of a group equal
    x[2, 1, 1:] = -np.inf                # one finite entry
    x[3, 2] = -np.inf                    # none
    np.testing.assert_array_equal(
        np.asarray(moe._two_largest_sum(jnp.asarray(x))),
        np.asarray(jnp.sum(jax.lax.top_k(jnp.asarray(x), 2)[0], axis=-1)))


# -- 3. which form, and its record -------------------------------------------------

@pytest.fixture
def choices(live_registry):
    before = dispatch.choices()
    return lambda: {k[1:]: n - before.get(k, 0)
                    for k, n in dispatch.choices().items()
                    if k[0] == "moe_select" and n > before.get(k, 0)}


def test_off_a_tpu_top_k_runs_and_nothing_is_recorded(choices, monkeypatch):
    monkeypatch.setattr(moe_select, "select", None)     # never reached
    x = scores(0, 64, 64)
    np.testing.assert_array_equal(
        np.asarray(moe.select_k(jnp.asarray(x), 8)), top_k(x, 8))
    assert choices() == {}


def test_one_choice_is_the_first_maximum_and_no_kernel(choices, monkeypatch):
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    monkeypatch.setattr(moe_select, "select", None)
    x = coarse(scores(0, 64, 17))
    np.testing.assert_array_equal(
        np.asarray(moe.select_k(jnp.asarray(x), 1)), top_k(x, 1))
    assert choices() == {}


def test_under_a_mesh_top_k_runs_and_says_so(choices, monkeypatch):
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    monkeypatch.setattr(moe_select, "select", None)
    x = scores(0, 64, 64)
    mesh = types.SimpleNamespace(shape={"dp": 2})
    np.testing.assert_array_equal(
        np.asarray(moe.select_k(jnp.asarray(x), 8, mesh)), top_k(x, 8))
    assert choices() == {("jnp", "mesh"): 1}


@pytest.mark.parametrize("experts,k,dtype,why", [
    (64, 8, jnp.float32, None), (512, 10, jnp.float32, None),
    (24, 24, jnp.float32, None),
    (64, 8, jnp.bfloat16, "dtype:bfloat16"),
    (8, 9, jnp.float32, "k_not_in_1..8:9")])
def test_what_the_kernel_refuses(experts, k, dtype, why):
    assert moe_select.unsupported(experts, k, dtype) == why


def test_a_refusal_is_recorded_and_top_k_runs(choices):
    x = jnp.asarray(scores(0, 64, 64)).astype(jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(moe.select_k(x, 8, asked=True)),
        np.asarray(jax.lax.top_k(x, 8)[1]))
    assert choices() == {("jnp", "dtype:bfloat16"): 1}


def test_the_layers_node_hands_its_mesh(choices, monkeypatch):
    """``_DroplessOp.routing`` hands ``ctx.mesh`` to the gate's ``route``:
    under a mesh on a TPU the choice is ``top_k``'s and counted ``mesh``."""
    import hetu_tpu as ht
    from hetu_tpu.layers.moe import MoELayer
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    monkeypatch.setattr(moe_select, "select", None)
    x = ht.placeholder_op("sel_x", (1, 16, 32))
    layer = MoELayer(32, 64, 8, k=2, capacity_factor=None,
                     expert_act="swiglu", name="sel_moe")
    layer(x)
    op, seen = layer.last_op, []
    real = op.gate.route
    monkeypatch.setattr(op.gate, "route", lambda *a, **kw: (
        seen.append(kw["mesh"]), real(*a, **kw))[1])
    mesh = types.SimpleNamespace(shape={"dp": 2})
    ctx = types.SimpleNamespace(mesh=mesh, master_params=None)
    vals = [jax.ShapeDtypeStruct(n.shape, jnp.float32) for n in op.inputs]
    jax.eval_shape(lambda *a: op.routing(list(a), ctx)[1], *vals)
    assert seen == [mesh] and choices() == {("jnp", "mesh"): 1}
