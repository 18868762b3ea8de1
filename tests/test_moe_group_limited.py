"""Group-limited selection (``ops/moe.py top_k_route(groups=)``): only
experts of the best groups are chosen, one group is the ungrouped router bit
for bit, and it composes with the sigmoid score, the selection bias and a
held share."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops.moe import top_k_route

T, E, K = 96, 64, 6


def logits(seed):
    return jax.random.normal(jax.random.PRNGKey(seed), (T, E)) * 2.0


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
@pytest.mark.parametrize("n_group,topk_group", [(8, 4), (4, 1), (8, 2)])
def test_only_experts_of_the_best_groups_are_chosen(score, n_group,
                                                    topk_group):
    lg = logits(n_group)
    bias = (0.3 * jax.random.normal(jax.random.PRNGKey(5), (E,))
            if score == "sigmoid" else None)
    k = min(K, topk_group * (E // n_group))
    idx, gate, _ = top_k_route(lg, k, renorm=True, score=score, bias=bias,
                               groups=(n_group, topk_group))
    s = (jax.nn.sigmoid(lg) if score == "sigmoid"
         else jax.nn.softmax(lg, -1))
    by = np.asarray(s + (0 if bias is None else bias))
    size = E // n_group
    grouped = by.reshape(T, n_group, size)
    group_score = np.sort(grouped, -1)[..., -2:].sum(-1)
    best = np.argsort(-group_score, -1, kind="stable")[:, :topk_group]
    idx = np.asarray(idx)
    for t in range(T):
        assert set(idx[t] // size) <= set(best[t]), t
        allowed = np.isin(np.arange(E) // size, best[t])
        want = np.argsort(-np.where(allowed, by[t], -np.inf),
                          kind="stable")[:k]
        assert list(idx[t]) == list(want)
    np.testing.assert_allclose(np.asarray(gate).sum(-1), 1.0, rtol=1e-5)


@pytest.mark.parametrize("groups", [None, (1, 1)])
def test_one_group_is_the_ungrouped_router_bit_for_bit(groups):
    lg = logits(3)
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(6), (E,))
    kw = dict(renorm=True, score="sigmoid", bias=bias, scale=2.5)
    plain = top_k_route(lg, K, **kw)
    grouped = top_k_route(lg, K, groups=groups, **kw)
    for a, b in zip(plain, grouped):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_all_groups_kept_is_the_ungrouped_choice():
    lg = logits(4)
    a = top_k_route(lg, K, score="sigmoid", groups=(8, 8))
    b = top_k_route(lg, K, score="sigmoid")
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))


def test_grouping_changes_what_is_chosen():
    lg = logits(9)
    a = np.asarray(top_k_route(lg, K, score="sigmoid", groups=(8, 2))[0])
    b = np.asarray(top_k_route(lg, K, score="sigmoid")[0])
    assert (np.sort(a, -1) != np.sort(b, -1)).any()


def test_the_gates_have_a_gradient_through_the_grouped_choice():
    lg = logits(2)
    g = jax.grad(lambda x: jnp.sum(top_k_route(
        x, K, renorm=False, score="sigmoid", groups=(8, 4))[1] ** 2))(lg)
    assert np.isfinite(np.asarray(g)).all() and np.abs(np.asarray(g)).max() > 0


def test_layer_with_groups_a_bias_and_a_held_share_trains():
    """``MoELayer(router_groups=, router_score="sigmoid", router_bias_rate=,
    held=)``: the bias moves, the load counts pairs here and elsewhere, and
    every chosen expert lies in a kept group."""
    import hetu_tpu as ht
    from hetu_tpu.layers.moe import MoELayer
    layer = MoELayer(32, 16, num_experts=16, k=2, capacity_factor=None,
                     expert_act="swiglu", track_load=True, held=(4, 8),
                     shared_width=16, shared_gate=False,
                     router_score="sigmoid", router_scale=2.5,
                     router_bias_rate=1e-3, router_groups=(4, 2),
                     name="glr")
    x = ht.placeholder_op("glr_x", (2, 24, 32))
    y = layer(x)
    loss = ht.reduce_sum_op(y * y, axes=[0, 1, 2])
    ex = ht.Executor({"train": [loss, ht.SGDOptimizer(1e-3).minimize(loss),
                                layer.load(), layer.router_bias(),
                                layer.chosen()]}, seed=0)
    xv = np.random.default_rng(0).standard_normal((2, 24, 32)).astype(
        np.float32)
    out = ex.run("train", feed_dict={x: xv}, convert_to_numpy_ret_vals=True)
    load, bias, chosen = out[2], out[3], out[4]
    assert load.shape == (4, 8) and load[0].sum() + load[2, 0] == 2 * 24 * 2
    assert np.abs(bias).max() == pytest.approx(1e-3)
    assert chosen.shape == (48, 2)
