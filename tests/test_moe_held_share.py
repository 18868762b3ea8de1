"""One chip's share of an expert-parallel layer (``MoELayer(held=(first,
count))``) tied to the whole layer: at a small size the routed parts that the
16 shares compute, added up, plus the shared expert once, are the uncut
layer's output and the plain reference's; the shares' pair counters add up
to ``T k``; a batch that overflows the static row bound is counted as dropped
and not silently lost; both forms of the grouped products lay out held
experts alike."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.layers.moe import MoELayer, record_moe_load
from hetu_tpu.ops import moe as moe_ops

from chipbench.reference import qwen3_next as ref

T, H, F, E, K = 48, 32, 16, 32, 4
SHARES = 16
PER = E // SHARES


def layer(name, **kw):
    return MoELayer(H, F, E, k=K, capacity_factor=None, expert_act="swiglu",
                    renorm_topk=True, track_load=True, name=name, **kw)


@pytest.fixture(scope="module")
def shares():
    """The uncut layer and its 16 shares in one program, the shares' weights
    cut out of the uncut layer's."""
    x = ht.placeholder_op("x", (T, H))
    whole = layer("whole", shared_width=F)
    parts = [layer(f"share{j}", held=(PER * j, PER)) for j in range(SHARES)]
    fetch = [whole(x), whole.load()]
    for p in parts:
        fetch += [p(x), p.load()]
    ex = ht.Executor({"f": fetch}, seed=11)
    for j, p in enumerate(parts):
        ex.params[p.gate.wg.name] = ex.params[whole.gate.wg.name]
        for mine, theirs in ((p.w1, whole.w1), (p.w2, whole.w2),
                             (p.w3, whole.w3)):
            ex.params[mine.name] = ex.params[theirs.name][PER * j:
                                                          PER * (j + 1)]
    weights = {n: np.asarray(ex.params[v.name]) for n, v in (
        ("router", whole.gate.wg), ("w_gate", whole.w1), ("w_up", whole.w3),
        ("w_down", whole.w2), *zip(("shared_gate", "shared_up", "shared_down",
                                    "shared_sigmoid"), whole.shared))}
    xv = np.random.default_rng(2).normal(size=(T, H)).astype(np.float32)
    out = ex.run("f", feed_dict={x: xv}, convert_to_numpy_ret_vals=True)
    return dict(x=xv, weights=weights, whole=out[:2],
                parts=list(zip(out[2::2], out[3::2])))


def shared_expert(x, w):
    y = (jax.nn.silu(x @ w["shared_gate"]) * (x @ w["shared_up"])
         ) @ w["shared_down"]
    return np.asarray(y * jax.nn.sigmoid(x @ w["shared_sigmoid"]))


def test_shares_add_up_to_the_uncut_layer(shares):
    y_whole, _ = shares["whole"]
    total = sum(y for y, _ in shares["parts"]) + shared_expert(
        shares["x"], shares["weights"])
    np.testing.assert_allclose(total, y_whole, atol=2e-6)
    # no share is the whole: the sum needs them all
    assert np.abs(shares["parts"][0][0] - y_whole).max() > 1e-2


def test_uncut_layer_is_the_references(shares):
    c = {"num_experts_per_tok": K}
    y, _ = ref.moe(jnp.asarray(shares["x"]), shares["weights"], c,
                   lambda a, b: a @ b)
    np.testing.assert_allclose(shares["whole"][0], y, atol=2e-6)


@pytest.mark.parametrize("j", [0, 5, SHARES - 1])
def test_a_share_is_the_references_share(shares, j):
    w = dict(shares["weights"])
    for n in ("w_gate", "w_up", "w_down"):
        w[n] = w[n][PER * j:PER * (j + 1)]
    y, _ = ref.moe(jnp.asarray(shares["x"]), w, {"num_experts_per_tok": K},
                   lambda a, b: a @ b, held=(PER * j, PER))
    routed_only = np.asarray(y) - shared_expert(shares["x"], w)
    np.testing.assert_allclose(shares["parts"][j][0], routed_only, atol=2e-6)


def test_pair_counters_add_up(shares):
    """Every share sees all ``T k`` pairs, here or elsewhere; the pairs the
    shares hold are the uncut layer's load, expert by expert."""
    _, whole_load = shares["whole"]
    assert whole_load[0].sum() == T * K
    here = [load for _, load in shares["parts"]]
    for load in here:
        assert load.shape == (3, PER)
        assert load[0].sum() + load[2, 0] == T * K
        np.testing.assert_array_equal(load[1], load[0])
    np.testing.assert_array_equal(np.concatenate([l[0] for l in here]),
                                  whole_load[0])
    assert sum(l[0].sum() for l in here) == T * K


def test_overflow_of_the_row_bound_is_counted():
    """A router that sends every token to experts 0..3, all held here: 192
    pairs against a bound of 2 x 192 x 4 / 32 = 48 rows.  The first 48 rows
    in expert order are expert 0's, so the layer computes expert 0's part,
    says it kept 48 of 192, and the counters call the rest dropped."""
    x = ht.placeholder_op("x", (T, H))
    part = layer("over", held=(0, 4))
    ex = ht.Executor({"f": [part(x), part.load()]}, seed=5)
    router = np.zeros((H, E), np.float32)
    router[0, :K] = 20.0 - np.arange(K)          # x[:, 0] = 1 carries it
    ex.params[part.gate.wg.name] = jnp.asarray(router)
    xv = np.random.default_rng(3).normal(size=(T, H)).astype(np.float32)
    xv[:, 0] = 1.0
    y, load = ex.run("f", feed_dict={x: xv}, convert_to_numpy_ret_vals=True)
    np.testing.assert_array_equal(load[0], [T] * 4)
    np.testing.assert_array_equal(load[1], [T, 0, 0, 0])
    assert load[2, 0] == 0 and np.isfinite(y).all()
    w1, w3, w2 = (np.asarray(ex.params[v.name][0])
                  for v in (part.w1, part.w3, part.w2))
    p = jax.nn.softmax(jnp.asarray(xv @ router), -1)[:, :K]
    p0 = np.asarray(p[:, 0] / p.sum(-1))
    want = p0[:, None] * np.asarray(
        (jax.nn.silu(xv @ w1) * (xv @ w3)) @ w2)
    np.testing.assert_allclose(y, want, atol=2e-6)
    telemetry.enable()
    try:
        telemetry.get_registry().reset()
        record_moe_load("layer0", load)
        record_moe_load("layer0", [[3, 1], [3, 1], [20, 0]])
        snap = telemetry.get_registry().snapshot()
        value = {n: snap[n]["samples"][0]["value"] for n in snap
                 if n.startswith("hetu_moe_")}
        assert value["hetu_moe_pairs_routed_total"] == 4 * T + 4
        assert value["hetu_moe_pairs_dropped_total"] == 3 * T
        assert value["hetu_moe_pairs_elsewhere_total"] == 20
        assert value["hetu_moe_expert_load_max_over_mean"] == 1.5
    finally:
        telemetry.shutdown()


# -- the op: both forms of the grouped products over held experts ------------

def held_inputs(seed=0, T=64, H=32, F=48, E=16):
    r = np.random.default_rng(seed)
    return [jnp.asarray(a, jnp.float32) for a in (
        r.normal(size=(T, H)), r.normal(size=(H, E)),
        r.normal(size=(E, H, F)) * 0.1, r.normal(size=(E, H, F)) * 0.1,
        r.normal(size=(E, F, H)) * 0.1)]


def dense_share(x, wg, w1, w3, w2, k, held):
    first, count = held
    probs = jax.nn.softmax(x @ wg, -1)
    chosen = jnp.argsort(-probs, -1, stable=True)[:, :k]
    gate = jnp.take_along_axis(probs, chosen, -1)
    weight = jnp.sum(jax.nn.one_hot(chosen, wg.shape[1]) * gate[..., None], 1)
    return sum(weight[:, e:e + 1]
               * ((jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e])
               for e in range(first, first + count))


def held_op(k, held, impl, rows=None):
    first, count = held

    def f(x, wg, w1, w3, w2):
        idx, gate, _ = moe_ops.top_k_route(x @ wg, k)
        sl = slice(first, first + count)
        return moe_ops.dropless_moe(x, idx, gate, w1[sl], w3[sl], w2[sl],
                                    impl=impl, held=held, rows=rows)
    return f


@pytest.mark.parametrize("impl,held", [
    ("ragged", (0, 4)), ("ragged", (6, 6)),
    ("pallas", (0, 4)), ("pallas", (12, 4)), ("pallas", (6, 6))])
def test_held_grouped_products_forward_and_backward(impl, held):
    """Values and the gradient of every operand against the dense
    computation over the held experts, with rows for every pair and with the
    bound of twice the mean share (no overflow at this routing)."""
    args = held_inputs()
    k = 3
    want_y = dense_share(*args, k, held)
    want = jax.grad(lambda *a: jnp.sum(dense_share(*a, k, held) ** 2),
                    argnums=range(5))(*args)
    for rows in (None, moe_ops.held_rows(64 * k, 16, held[1])):
        y, lay = jax.jit(held_op(k, held, impl, rows))(*args)
        np.testing.assert_allclose(y, want_y, atol=2e-6)
        np.testing.assert_array_equal(lay["kept"], lay["load"])
        assert int(lay["load"].sum() + lay["elsewhere"]) == 64 * k
        got = jax.jit(jax.grad(
            lambda *a: jnp.sum(held_op(k, held, impl, rows)(*a)[0] ** 2),
            argnums=range(5)))(*args)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=1e-5)


@pytest.mark.parametrize("impl", ["ragged", "pallas"])
def test_held_layout_over_the_bound(impl):
    """Fewer rows than pairs land here: the pairs kept are the first in
    expert order, the others read zeros forward and backward, and ``kept``
    says how many each expert computed."""
    args = held_inputs(seed=4)
    held, k, rows = (2, 5), 4, 40
    y, lay = jax.jit(held_op(k, held, impl, rows))(*args)
    load, kept = np.asarray(lay["load"]), np.asarray(lay["kept"])
    assert load.sum() > rows and (kept <= load).all()
    assert np.isfinite(np.asarray(y)).all()
    if impl == "ragged":
        assert kept.sum() == rows
    # the experts wholly kept give their dense part; zeroing the others'
    # weights in the dense computation leaves exactly that
    whole = kept == load
    assert whole.any() and not whole.all()
    part = np.zeros(16, bool)
    part[held[0]:held[0] + held[1]] = ~whole
    if (kept[~whole] == 0).all():
        x, wg, w1, w3, w2 = args
        w2 = w2 * jnp.asarray(~part, jnp.float32)[:, None, None]
        np.testing.assert_allclose(y, dense_share(x, wg, w1, w3, w2, k, held),
                                   atol=2e-6)
    g = jax.jit(jax.grad(lambda *a: jnp.sum(
        held_op(k, held, impl, rows)(*a)[0] ** 2), argnums=(0, 2)))(
            *args)
    assert all(np.isfinite(np.asarray(t)).all() for t in g)


def test_held_none_is_todays_layer():
    """``MoELayer(held=None)`` builds the layer as before: ``[2, E]`` load,
    weights for every expert, the op without a share."""
    m = layer("plain")
    assert m.w1.shape == (E, H, F) and m.load_var.shape == (2, E)
    assert m.shared is None and m.held is None
    op = m(ht.placeholder_op("x", (T, H)))
    assert op.held is None and op is m.last_op
