"""One chip's share of an expert-parallel layer (``MoELayer(held=(first,
count))``) tied to the whole layer: at a small size the routed parts that the
16 shares compute, added up, plus the shared expert once, are the uncut
layer's output and the plain reference's; the shares' pair counters add up
to ``T k``; a batch that overflows the static row bound is counted as dropped
and not silently lost; both forms of the grouped products lay out held
experts alike.  The same for Nemotron-H's expert layer (sigmoid scores with a
selection bias, experts that are not gated, a shared expert without a
sigmoid gate), and the rule that moves the bias."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.layers.moe import MoELayer, record_moe_load
from hetu_tpu.ops import moe as moe_ops

from chipbench.reference import nemotron_h as ref_nemotron
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
        assert load.shape == (4, PER) and not load[3].any()
        assert load[0].sum() + load[2, 0] == T * K
        np.testing.assert_array_equal(load[1], load[0])
    np.testing.assert_array_equal(np.concatenate([l[0] for l in here]),
                                  whole_load[0])
    assert sum(l[0].sum() for l in here) == T * K


def test_overflow_of_the_row_bound_is_computed_and_counted():
    """A router that sends every token to experts 0..3, all held here: 192
    pairs against a bound of 2 x 192 x 4 / 32 = 48 rows a pass.  The first
    48 rows in expert order are expert 0's; three further passes compute
    experts 1 to 3, so the layer gives all four experts' part, says it
    computed every pair, and counts 144 of them over the bound."""
    x = ht.placeholder_op("x", (T, H))
    part = layer("over", held=(0, 4))
    ex = ht.Executor({"f": [part(x), part.load()]}, seed=5)
    router = np.zeros((H, E), np.float32)
    router[0, :K] = 20.0 - np.arange(K)          # x[:, 0] = 1 carries it
    ex.params[part.gate.wg.name] = jnp.asarray(router)
    xv = np.random.default_rng(3).normal(size=(T, H)).astype(np.float32)
    xv[:, 0] = 1.0
    y, load = ex.run("f", feed_dict={x: xv}, convert_to_numpy_ret_vals=True)
    assert load.shape == (4, 4)
    np.testing.assert_array_equal(load[0], [T] * 4)
    np.testing.assert_array_equal(load[1], load[0])
    np.testing.assert_array_equal(load[3], [0, T, T, T])
    assert load[2, 0] == 0
    w1, w3, w2 = (np.asarray(ex.params[v.name]) for v in (part.w1, part.w3,
                                                          part.w2))
    p = jax.nn.softmax(jnp.asarray(xv @ router), -1)[:, :K]
    p = np.asarray(p / p.sum(-1, keepdims=True))
    want = sum(p[:, e:e + 1] * np.asarray(
        (jax.nn.silu(xv @ w1[e]) * (xv @ w3[e])) @ w2[e]) for e in range(4))
    np.testing.assert_allclose(y, want, atol=4e-6)
    telemetry.enable()
    try:
        telemetry.get_registry().reset()
        record_moe_load("layer0", load)
        record_moe_load("layer0", [[3, 1], [2, 1], [20, 0]])
        snap = telemetry.get_registry().snapshot()
        value = {n: snap[n]["samples"][0]["value"] for n in snap
                 if n.startswith("hetu_moe_")}
        assert value["hetu_moe_pairs_routed_total"] == 4 * T + 4
        assert value["hetu_moe_pairs_dropped_total"] == 1
        assert value["hetu_moe_pairs_over_bound_total"] == 3 * T
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


@pytest.mark.parametrize("impl,held,k,rows", [
    ("ragged", (2, 5), 4, 40), ("pallas", (2, 5), 4, 40),
    ("ragged", (0, 4), 3, 8), ("pallas", (6, 6), 3, 16),
    ("ragged", (0, 16), 4, 24), ("pallas", (0, 16), 4, 24)])
def test_held_layout_over_the_bound(impl, held, k, rows):
    """Fewer rows a pass than pairs land here: ``kept`` says what the first
    pass held, the first pairs in expert order, and further passes over the
    same rows (up to eleven here) compute the others, so values and the
    gradient of every operand are the dense computation's."""
    args = held_inputs(seed=4)
    y, lay = jax.jit(held_op(k, held, impl, rows))(*args)
    load, kept = np.asarray(lay["load"]), np.asarray(lay["kept"])
    assert load.sum() > rows and (kept <= load).all()
    assert 0 < kept.sum() < load.sum()
    np.testing.assert_array_equal(lay["computed"], load)
    if impl == "ragged":
        assert kept.sum() == rows and int(lay["total"]) == load.sum()
    assert int(lay["total"]) > 2 * rows
    np.testing.assert_allclose(y, dense_share(*args, k, held), atol=2e-6)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(
        held_op(k, held, impl, rows)(*a)[0] ** 2), argnums=range(5)))(*args)
    want = jax.grad(lambda *a: jnp.sum(dense_share(*a, k, held) ** 2),
                    argnums=range(5))(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5)


@pytest.mark.parametrize("tile", [None, 8])
def test_windows_of_the_held_layout_hold_every_pair_once(tile):
    """The windows at 0, M, 2 M, .. below ``total``: every pair routed to a
    held expert has a row in exactly one of them, the row holds that pair,
    and a row tile's rows are one expert's."""
    idx = jnp.asarray(np.random.default_rng(2).integers(0, 16, (64, 4)),
                      jnp.int32)
    held, rows = (3, 6), 16
    flat = np.asarray(idx).reshape(-1)
    mine = (flat >= 3) & (flat < 9)
    seen = np.zeros(flat.size, int)
    first = moe_ops.grouped_layout(idx, None, tile, held=held, rows=rows)
    M, total = first["rows"], int(first["total"])
    assert total > M
    kept = 0
    for offset in range(0, total, M):
        lay = jax.jit(lambda o: moe_ops.grouped_layout(
            idx, None, tile, held=held, rows=rows, offset=o))(offset)
        slot, pair = (np.asarray(lay[n]) for n in ("slot_of_pair",
                                                   "pair_of_slot"))
        here = slot < M
        seen += here
        np.testing.assert_array_equal(pair[slot[here]], np.flatnonzero(here))
        assert (pair >= 0).sum() == here.sum() == np.asarray(lay["kept"]).sum()
        kept += here.sum()
        if tile:
            experts = np.repeat(np.asarray(lay["tile_expert"]), tile)
            used = pair >= 0
            np.testing.assert_array_equal(flat[pair[used]] - 3, experts[used])
            assert used[int(lay["n_used"][0]) * tile:].sum() == 0
    np.testing.assert_array_equal(seen, mine.astype(int))
    assert kept == mine.sum() == np.asarray(first["load"]).sum()


def test_held_none_is_todays_layer():
    """``MoELayer(held=None)`` builds the layer as before: ``[2, E]`` load,
    weights for every expert, the op without a share."""
    m = layer("plain")
    assert m.w1.shape == (E, H, F) and m.load_var.shape == (2, E)
    assert m.shared is None and m.held is None
    op = m(ht.placeholder_op("x", (T, H)))
    assert op.held is None and op is m.last_op


# -- Nemotron-H's expert layer: sigmoid scores with a bias, relu2 experts -----

NEMOTRON = {"num_experts_per_tok": K, "norm_topk_prob": True,
            "routed_scaling_factor": 2.5}


def relu2_layer(name, **kw):
    return MoELayer(H, F, E, k=K, capacity_factor=None, expert_act="relu2",
                    renorm_topk=True, track_load=True, router_score="sigmoid",
                    router_scale=2.5, shared_gate=False, name=name, **kw)


@pytest.fixture(scope="module")
def relu2_shares():
    """The uncut layer and its 16 shares in one program, the shares' weights
    and the router's bias cut out of the uncut layer's; the bias is off zero
    so that it decides some choices."""
    x = ht.placeholder_op("x", (T, H))
    whole = relu2_layer("rwhole", shared_width=F)
    parts = [relu2_layer(f"rshare{j}", held=(PER * j, PER))
             for j in range(SHARES)]
    assert whole.w3 is None and len(whole.shared) == 2
    fetch = [whole(x), whole.load(), whole.chosen()]
    for p in parts:
        fetch += [p(x), p.load()]
    ex = ht.Executor({"f": fetch}, seed=13)
    # small enough that no share's pairs pass its row bound (twice the mean)
    bias = jnp.asarray(np.random.default_rng(4).normal(0, 0.03, E),
                       jnp.float32)
    ex.params[whole.gate.bias.name] = bias
    for j, p in enumerate(parts):
        ex.params[p.gate.wg.name] = ex.params[whole.gate.wg.name]
        ex.params[p.gate.bias.name] = bias
        for mine, theirs in ((p.w1, whole.w1), (p.w2, whole.w2)):
            ex.params[mine.name] = ex.params[theirs.name][PER * j:
                                                          PER * (j + 1)]
    weights = {n: np.asarray(ex.params[v.name]) for n, v in (
        ("router", whole.gate.wg), ("router_bias", whole.gate.bias),
        ("w_up", whole.w1), ("w_down", whole.w2),
        *zip(("shared_up", "shared_down"), whole.shared))}
    xv = np.random.default_rng(2).normal(size=(T, H)).astype(np.float32)
    out = ex.run("f", feed_dict={x: xv}, convert_to_numpy_ret_vals=True)
    return dict(x=xv, weights=weights, whole=out[:3],
                parts=list(zip(out[3::2], out[4::2])))


def relu2_shared(x, w):
    return np.asarray(jnp.square(jax.nn.relu(x @ w["shared_up"]))
                      @ w["shared_down"])


def test_relu2_shares_add_up_to_the_uncut_layer_and_the_references(
        relu2_shares):
    """The 16 shares' routed parts plus the shared expert ONCE are the uncut
    layer, which is the reference's; the reference's choices are the
    layer's, and the bias decided some of them."""
    s = relu2_shares
    y_whole, load, chosen = s["whole"]
    total = sum(y for y, _ in s["parts"]) + relu2_shared(s["x"],
                                                         s["weights"])
    np.testing.assert_allclose(total, y_whole, atol=2e-6)
    assert np.abs(s["parts"][0][0] - y_whole).max() > 1e-2
    y_ref, (_, chosen_ref) = ref_nemotron.moe(
        jnp.asarray(s["x"]), s["weights"], NEMOTRON, lambda a, b: a @ b)
    np.testing.assert_allclose(y_whole, y_ref, atol=2e-6)
    np.testing.assert_array_equal(np.sort(chosen, -1),
                                  np.sort(chosen_ref, -1))
    unbiased = dict(s["weights"], router_bias=np.zeros(E, np.float32))
    _, (_, plain) = ref_nemotron.moe(jnp.asarray(s["x"]), unbiased, NEMOTRON,
                                     lambda a, b: a @ b)
    assert (np.sort(plain, -1) != np.sort(chosen_ref, -1)).any()
    assert load[0].sum() == T * K
    here = [l for _, l in s["parts"]]
    np.testing.assert_array_equal(np.concatenate([l[0] for l in here]),
                                  load[0])
    for l in here:
        np.testing.assert_array_equal(l[1], l[0])      # nothing dropped


@pytest.mark.parametrize("j", [0, 5, SHARES - 1])
def test_a_relu2_share_is_the_references_share(relu2_shares, j):
    s = relu2_shares
    w = dict(s["weights"])
    for n in ("w_up", "w_down"):
        w[n] = w[n][PER * j:PER * (j + 1)]
    y, _ = ref_nemotron.moe(jnp.asarray(s["x"]), w, NEMOTRON,
                            lambda a, b: a @ b, held=(PER * j, PER))
    routed_only = np.asarray(y) - relu2_shared(s["x"], w)
    np.testing.assert_allclose(s["parts"][j][0], routed_only, atol=2e-6)


def test_the_router_bias_moves_by_its_rule_and_is_no_weight():
    """One training step: ``bias += u sign(mean(load) - load)`` from the
    step's pair counts over ALL experts (this device holds 8 of 32), read
    from the f32 master under bf16 compute; the bias has no gradient and no
    AdamW state, and an evaluation leaves it where it is."""
    from hetu_tpu.graph.node import graph_variables
    u = 0.01
    x = ht.placeholder_op("x", (T, H))
    part = relu2_layer("rule", held=(8, 8), shared_width=F,
                       router_bias_rate=u)
    loss = ht.reduce_mean_op(part(x) * part(x), axes=[0, 1])
    opt = ht.AdamWOptimizer(learning_rate=1e-3, weight_decay=0.1)
    ex = ht.Executor(
        {"train": [loss, opt.minimize(loss), part.router_bias(),
                   part.chosen()],
         "eval": [loss, part.router_bias()]},
        seed=17, compute_dtype=jnp.bfloat16)
    name = part.gate.bias.name
    trainable = {v.name for v in graph_variables([loss],
                                                 trainable_only=True)}
    assert name not in trainable and part.gate.wg.name in trainable
    # a host copy: the training step donates the state's buffers
    start = np.random.default_rng(6).normal(0, 0.3, E).astype(np.float32)
    ex.params[name] = jnp.asarray(start)
    xv = np.random.default_rng(3).normal(size=(T, H)).astype(np.float32)
    _, fetched = ex.run("eval", feed_dict={x: xv},
                        convert_to_numpy_ret_vals=True)
    np.testing.assert_array_equal(fetched, start)
    np.testing.assert_array_equal(ex.params[name], start)
    _, _, moved, chosen = ex.run("train", feed_dict={x: xv},
                                 convert_to_numpy_ret_vals=True)
    load = np.bincount(chosen.reshape(-1), minlength=E)
    want = start + u * np.sign(load.mean() - load)
    np.testing.assert_allclose(moved, want, atol=1e-7)
    np.testing.assert_allclose(ex.params[name], want, atol=1e-7)
    assert ex.params[name].dtype == jnp.float32
    assert (load > load.mean()).any() and (load < load.mean()).any()
    states = [k for state in ex.opt_state.values()
              for k in jax.tree_util.tree_leaves_with_path(state)]
    assert states and not any(name in jax.tree_util.keystr(path)
                              for path, _ in states)


def caller_built(kind):
    from hetu_tpu.layers.moe import KTop1Gate, StateRouter, TopKGate
    return {"sigmoid_gate": lambda: TopKGate(H, E, score="sigmoid"),
            "ktop1_gate": lambda: KTop1Gate(H, E),
            "router": lambda: StateRouter(H, E, 8)}[kind]()


@pytest.mark.parametrize("keyword,value,regime", [
    ("held", (0, 4), "dropless"),
    ("router", "router", "dropless"),
    ("router_groups", (2, 1), "dropless"),
    ("router_score", "sigmoid", "dropless"),
    ("expert_act", "relu2", "dropless"),
    ("gate", "sigmoid_gate", "dropless"),
    ("expert_act", "gelu", "capacity"),
    ("gate", "hash", "capacity"),
    ("gate", "ktop1", "capacity"),
    ("gate", "sam", "capacity"),
    ("gate", "balance", "capacity"),
    ("gate", "ktop1_gate", "capacity"),
])
def test_a_keyword_of_the_other_regime_is_refused_by_name(keyword, value,
                                                          regime):
    """``layers/moe.py _ONE_REGIME_ALONE``: a layer of one regime
    (``capacity_factor is None`` or not) refuses what belongs to the other
    alone, and says which keyword and which regime; under its own regime the
    same keyword builds."""
    if value in ("router", "sigmoid_gate", "ktop1_gate"):
        value = caller_built(value)
    kw = {"expert_act": "swiglu", keyword: value}
    if value == "sam":
        kw["num_groups"] = 2
    own, other = (None, 1.25) if regime == "dropless" else (1.25, None)
    with pytest.raises(ValueError, match=rf"{keyword}=.* belongs to the "
                                         rf"{regime} regime"):
        MoELayer(H, F, E, k=K, capacity_factor=other, **kw)
    MoELayer(H, F, E, k=K, capacity_factor=own, **kw)


@pytest.mark.parametrize("capacity_factor", [None, 1.25])
def test_the_layers_own_keywords_build_under_either_regime(capacity_factor):
    layer = MoELayer(H, F, E, k=K, capacity_factor=capacity_factor,
                     expert_act="swiglu", shared_width=8, shared_gate=False,
                     ep_axis="ep", track_load=True, renorm_topk=False)
    # dropless experts spread over an axis count the host's pairs in the
    # four rows of a held layer (PR 72: ``elsewhere`` 0, the further passes')
    rows = 4 if capacity_factor is None else 2
    assert len(layer.shared) == 3 and layer.load_var.shape == (rows, E)


# -- rows to tokens: the sum over token tiles against the one-hot product -----

def window_of_rows(case, T=64, M=96, k=6):
    """``tok [M]`` of a held window's rows (``T``: a row of padding)."""
    r = np.random.default_rng(7)
    if case == "padding":              # what a layout gives: most rows none
        tok = np.where(r.random(M) < 0.4, r.integers(0, T, M), T)
    elif case == "empty_tile":         # the tokens 16..31 own no row
        tok = r.integers(0, T - 16, M)
        tok = np.where(tok >= 16, tok + 16, tok)
    elif case == "k_rows":             # token 5 owns k rows, others one
        tok = np.full(M, T)
        at = r.permutation(M)
        tok[at[:k]] = 5
        tok[at[k:k + 40]] = r.permutation(np.delete(np.arange(T), 5))[:40]
    elif case == "one_tile":           # every row in the tile 8..15
        tok = r.integers(8, 16, M)
    return jnp.asarray(tok, jnp.int32)


def assert_rounded_alike(got, want, dtype):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=2e-6)
    else:       # one unit in the last place of bf16: 8 bits of significand
        assert (np.abs(got - want)
                <= 2.0 ** -7 * np.maximum(np.abs(want), 2.0 ** -126)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("H", [256, 384])
@pytest.mark.parametrize("case", ["padding", "empty_tile", "k_rows",
                                  "one_tile"])
def test_segmented_sum_is_the_one_hot_products(case, H, dtype):
    """``_sum_rows`` over token tiles (``token_tiles``, ``hetu_moe_rows_sum``
    in interpret mode) against the ``[T, M]`` product: the values, the sum as
    the backward pass of ``_tokens_to_rows``, and ``_rows_to_tokens``'s own
    backward pass, which is the gather it was."""
    T, tt, tile = 64, 8, 8
    tok = window_of_rows(case, T)
    r = np.random.default_rng(3)
    v = jnp.asarray(r.normal(size=(tok.shape[0], H)), dtype)
    seg = jax.jit(lambda t: moe_ops.token_tiles(t, T, tt, tile))(tok)
    assert seg.src.shape[0] == tok.shape[0] + T // tt * tile
    assert int((seg.loc >= 0).sum()) == int((tok < T).sum())
    want = moe_ops._sum_rows(v, tok, T)
    assert_rounded_alike(moe_ops._sum_rows(v, tok, T, seg), want, dtype)
    # H in blocks of 128: two and three programs a row tile
    from hetu_tpu.ops.pallas.moe_rows import rows_sum
    blocks = rows_sum(jnp.take(v, seg.src, axis=0), seg.loc, seg.tile_group,
                      seg.n_used, tokens=T, tt=tt, th=128)
    assert_rounded_alike(blocks, want, dtype)
    tokens = jnp.asarray(r.normal(size=(T, H)), dtype)
    xs, pull = jax.vjp(lambda x: moe_ops._tokens_to_rows(x, tok, seg), tokens)
    np.testing.assert_array_equal(xs, moe_ops._take_rows(tokens, tok))
    assert_rounded_alike(pull(v)[0], want, dtype)
    dy = jnp.asarray(r.normal(size=(T, H)), dtype)
    y, back = jax.vjp(lambda a: moe_ops._rows_to_tokens(a, tok, T, seg), v)
    assert_rounded_alike(y, want, dtype)
    np.testing.assert_array_equal(back(dy)[0], moe_ops._take_rows(dy, tok))


def test_segmented_sum_of_a_window_past_the_first():
    """The rows of the layout's second window, its ``offset`` traced: the
    token tiles are laid out from that window's ``tok`` and the sum is the
    product's."""
    T, k, tile, held, rows = 64, 4, 8, (3, 6), 16
    idx = jnp.asarray(np.random.default_rng(2).integers(0, 16, (T, k)),
                      jnp.int32)
    first = moe_ops.grouped_layout(idx, None, tile, held=held, rows=rows)
    M = first["rows"]
    assert int(first["total"]) > M
    v = jnp.asarray(np.random.default_rng(5).normal(size=(M, 256)),
                    jnp.float32)

    @jax.jit
    def both(offset):
        lay = moe_ops.grouped_layout(idx, None, tile, held=held, rows=rows,
                                     offset=offset)
        tok = jnp.where(lay["pair_of_slot"] >= 0, lay["pair_of_slot"] // k, T)
        seg = moe_ops.token_tiles(tok, T, 8, tile)
        return (tok, moe_ops._sum_rows(v, tok, T, seg),
                moe_ops._sum_rows(v, tok, T))
    tok0, _, _ = both(0)
    tok1, got, want = both(M)
    assert (np.asarray(tok1) < T).any() and (np.asarray(tok1)
                                             != np.asarray(tok0)).any()
    np.testing.assert_allclose(got, want, atol=2e-6)


# -- which form runs, and that it is counted ----------------------------------

def rows_choices():
    from hetu_tpu.ops.pallas import dispatch
    return {k: n for k, n in dispatch.choices().items() if k[0] == "moe_rows"}


@pytest.mark.parametrize("platform,args,tt,counted", [
    ("tpu", ("pallas", 128, None, 8192, 2048, jnp.bfloat16), 512,
     ("pallas", "")),
    ("tpu", ("pallas", 128, None, 8192, 2688, jnp.float32), 512,
     ("pallas", "")),
    ("tpu", ("ragged", None, object(), 8192, 2048, jnp.bfloat16), None,
     ("jnp", "mesh")),
    ("tpu", ("ragged", None, None, 8192, 2048, jnp.bfloat16), None,
     ("jnp", "moe_gmm:ragged")),
    ("tpu", ("pallas", 128, None, 8192 + 256, 2048, jnp.bfloat16), None,
     ("jnp", "tokens_not_tile_aligned:8448%512")),
    ("tpu", ("pallas", 128, None, 8192, 2000, jnp.bfloat16), None,
     ("jnp", "dims_not_128_aligned")),
    ("tpu", ("pallas", 8, None, 8192, 2048, jnp.bfloat16), None,
     ("jnp", "dims_not_128_aligned")),
    ("tpu", ("pallas", 128, None, 8192, 2048, jnp.float16), None,
     ("jnp", "dtype:float16")),
    ("cpu", ("pallas", 8, None, 64, 32, jnp.float32), 8, ("pallas", "")),
    ("cpu", ("pallas", 8, None, 60, 32, jnp.float32), None,
     ("jnp", "tokens_not_tile_aligned:60%8")),
    ("cpu", ("ragged", None, None, 8192, 2048, jnp.bfloat16), None, None),
    ("cpu", ("ragged", None, object(), 8192, 2048, jnp.bfloat16), None, None),
])
def test_rows_impl_counts_its_choice(live_registry, monkeypatch, platform,
                                     args, tt, counted):
    """``hetu_kernel_choice_total{kernel="moe_rows"}``: ``pallas``, each
    ``jnp`` reason from a shape that causes it, and nothing on a platform
    without Mosaic unless the caller asked for the kernels."""
    from hetu_tpu.ops.pallas import dispatch
    monkeypatch.setattr(dispatch, "platform", lambda: platform)
    before = rows_choices()
    assert moe_ops.rows_impl(*args) == tt
    after = rows_choices()
    grew = {k[1:]: n - before.get(k, 0) for k, n in after.items()
            if n != before.get(k, 0)}
    assert grew == ({counted: 1} if counted else {})


@pytest.mark.parametrize("impl,counted", [("pallas", 1), (None, 0),
                                          ("ragged", 0)])
def test_a_held_layer_counts_its_rows_choice_once_a_call(live_registry, impl,
                                                         counted):
    key = ("moe_rows", "pallas", "")
    before = rows_choices()
    args = held_inputs(seed=1)
    y, _ = held_op(3, (0, 4), impl)(*args)
    np.testing.assert_allclose(y, dense_share(*args, 3, (0, 4)), atol=2e-6)
    after = rows_choices()
    assert after.get(key, 0) - before.get(key, 0) == counted
    assert {k for k in after if k != key} == {k for k in before if k != key}


def test_the_kernel_stands_in_a_held_layers_step_and_in_no_other(
        live_registry, monkeypatch):
    """The platform read as ``tpu``: a held layer at aligned sizes lowers to
    ``hetu_moe_rows_sum`` forward and backward and counts ``pallas``; a layer
    that holds every expert (the OLMoE builder's toy step, ``held=None``)
    records no ``moe_rows`` choice and lowers to no such kernel."""
    import importlib
    from chipbench import run
    from hetu_tpu.ops.pallas import dispatch
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    jax.clear_caches()                  # traces made in interpret mode
    try:
        before = rows_choices()
        shapes = [jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (
            (512, 128), (128, 16), (16, 128, 128), (16, 128, 128),
            (16, 128, 128))]
        held, rows = (0, 8), moe_ops.held_rows(512 * 4, 16, 8)
        text = jax.jit(jax.grad(lambda *a: jnp.sum(
            held_op(4, held, None, rows)(*a)[0].astype(jnp.float32)),
            argnums=(0, 2))).trace(*shapes).lower(
                lowering_platforms=("tpu",)).as_text()
        assert text.count("hetu_moe_rows_sum") >= 2
        assert "hetu_moe_gmm_fwd" in text
        grew = {k: n - before.get(k, 0) for k, n in rows_choices().items()
                if n != before.get(k, 0)}
        assert grew == {("moe_rows", "pallas", ""): 1}

        before = rows_choices()
        _, _, config, mix = run.load_cell("olmoe-1b-7b.b2-s4096")
        config, mix = run.merge(config, config["toy"]), run.merge(mix,
                                                                  mix["toy"])
        builder = importlib.import_module("chipbench.builders."
                                          + config["builder"])
        with ht.name_scope():
            prog = builder.build(config, mix, 2 ** 31 + 7, lambda msg: None)
        try:
            assert all(m.held is None for m in prog.model.moe_layers())
            sub = prog.ex.subexecutor["train"]
            if sub._jitted is None:
                sub._build()
            text = sub._jitted.trace(*sub._abstract_args(None)).lower(
                lowering_platforms=("tpu",)).as_text()
        finally:
            prog.close()
        assert "hetu_moe_rows_sum" not in text
        assert rows_choices() == before
    finally:
        jax.clear_caches()


def test_two_shares_of_a_hyper_connected_ffn_add_up_to_the_uncut_sublayer():
    """Xing4.0's FFN sublayer at its toy size: the shares ``experts_held [0,
    8]`` and ``[8, 8]`` of a 16-expert layer behind the same hyper-connection,
    the shared expert in the first alone and the streams' own part ``Hres X``
    counted once, add up to the uncut reference's output of the whole
    hyper-connected sublayer (``chipbench/reference/xing4.py``)."""
    from hetu_tpu.layers import RMSNorm
    from hetu_tpu.layers.hyper_connection import HyperConnection
    from chipbench.reference import xing4 as ref_xing4
    C, Fx, Ex, n, Tx = 64, 32, 16, 4, 40
    c = {"num_experts_per_tok": 2, "n_group": 1, "topk_group": 1,
         "norm_topk_prob": True, "routed_scaling_factor": 2.0,
         "hc_eps": 1e-6, "hc_sinkhorn_iters": 8, "mhc_h_res_clamp_min": -30,
         "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-6}
    norm = RMSNorm(C, eps=1e-6, name="hcshare_norm")
    x = ht.placeholder_op("hcshare_x", (1, Tx, n * C))
    hcs, moes, outs = [], [], []
    for j, shared in enumerate((Fx, None)):
        hcs.append(HyperConnection(C, n, 8, 1e-6, (-30, 30),
                                   name=f"hcshare_hc{j}"))
        moes.append(MoELayer(
            C, Fx, Ex, k=2, capacity_factor=None, expert_act="swiglu",
            renorm_topk=True, held=(8 * j, 8), shared_width=shared,
            shared_gate=False, router_score="sigmoid", router_scale=2.0,
            router_groups=(1, 1), name=f"hcshare_moe{j}"))
        outs.append(hcs[j].sublayer(x, norm, moes[j]))
    ex = ht.Executor({"f": outs + [hcs[0].hres]}, seed=13)
    r = np.random.default_rng(13)
    w = {"router": r.normal(0, 0.5, (C, Ex)), "router_bias": r.normal(
            0, 0.1, (Ex,)),
         "w_gate": r.normal(0, 0.1, (Ex, C, Fx)), "w_up": r.normal(
             0, 0.1, (Ex, C, Fx)), "w_down": r.normal(0, 0.1, (Ex, Fx, C)),
         "shared_gate": r.normal(0, 0.1, (C, Fx)), "shared_up": r.normal(
             0, 0.1, (C, Fx)), "shared_down": r.normal(0, 0.1, (Fx, C))}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    maps = (r.normal(0, (n * C) ** -0.5, (n * C, 2 * n + n * n)),
            r.normal(0, 0.5, 2 * n + n * n), r.uniform(0.5, 1.0, 3))
    maps = tuple(jnp.asarray(m, jnp.float32) for m in maps)
    scale = jnp.asarray(r.normal(1, 0.2, (C,)), jnp.float32)
    ex.params[norm.scale.name] = scale
    for j, (hc, moe) in enumerate(zip(hcs, moes)):
        for var, value in zip((hc.phi, hc.b, hc.alpha), maps):
            ex.params[var.name] = value
        ex.params[moe.gate.wg.name] = w["router"]
        ex.params[moe.gate.bias.name] = w["router_bias"]
        for var, key in ((moe.w1, "w_gate"), (moe.w3, "w_up"),
                         (moe.w2, "w_down")):
            ex.params[var.name] = w[key][8 * j:8 * j + 8]
    for var, key in zip(moes[0].shared, ("shared_gate", "shared_up",
                                         "shared_down")):
        ex.params[var.name] = w[key]
    X = r.normal(0, 1, (1, Tx, n, C)).astype(np.float32)
    first, second, hres = ex.run(
        "f", feed_dict={x: X.reshape(1, Tx, n * C)},
        convert_to_numpy_ret_vals=True)
    mm = lambda a, b: a @ b

    def ffn(u):
        h = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True)
                              + 1e-6) * scale
        return ref_xing4.expert_block(h.reshape(Tx, C), w, c,
                                      mm)[0].reshape(1, Tx, C)
    with jax.default_matmul_precision("highest"):
        whole, res = ref_xing4.hyper_connection(jnp.asarray(X), maps, c, mm,
                                                ffn)
    np.testing.assert_allclose(hres, np.asarray(res), atol=2e-6)
    own = np.einsum("bsij,bsjc->bsic", hres, X).reshape(1, Tx, n * C)
    total = first + second - own
    np.testing.assert_allclose(total.reshape(1, Tx, n, C), np.asarray(whole),
                               atol=2e-5)
    # neither share is the whole: the sum needs both
    assert np.abs(first.reshape(whole.shape) - np.asarray(whole)).max() > 1e-2
    assert np.abs(second - own).max() > 1e-2


def test_two_shares_behind_a_state_router_add_up_to_the_uncut_sublayer():
    """ZAYA1's expert sublayer at its toy size: the shares ``experts_held [0,
    4]`` and ``[4, 4]`` of an 8-expert layer behind the same router (9
    outputs: the last computes nothing) and the same scaled residual, the
    skipped tokens' zero and the residual's own part ``s_r (x + b_r) + s_f
    b_f`` counted once, add up to the uncut reference's output of the whole
    expert sublayer (``chipbench/reference/zaya1.py``)."""
    from hetu_tpu.layers import RMSNorm
    from hetu_tpu.layers.moe import StateRouter
    from hetu_tpu.models.llama import ResidualMerge, residual_sublayer
    from chipbench.reference import zaya1 as ref_zaya1
    C, Fx, Ex, R, Tx = 64, 32, 8, 16, 128
    c = {"rms_norm_eps": 1e-5}
    norm = RMSNorm(C, eps=1e-5, name="srshare_norm")
    x = ht.placeholder_op("srshare_x", (1, Tx, C))
    routers, moes, merges, outs = [], [], [], []
    for j in range(2):
        routers.append(StateRouter(C, Ex, R, skip=1, name=f"srshare_r{j}"))
        moes.append(MoELayer(C, Fx, Ex, k=1, capacity_factor=None,
                             expert_act="swiglu", renorm_topk=False,
                             track_load=True, held=(4 * j, 4),
                             router=routers[j],
                             name=f"srshare_moe{j}"))
        merges.append(ResidualMerge(C, name=f"srshare_merge{j}"))
        outs.append(residual_sublayer(x, norm, moes[j], merge=merges[j]))
    ex = ht.Executor({"f": outs + [m.load() for m in moes]}, seed=17)
    r = np.random.default_rng(17)
    w = {"router.down": r.normal(0, C ** -0.5, (C, R)),
         "router.down_bias": r.normal(0, 0.1, R),
         "router.norm": r.normal(1, 0.2, R),
         "router.w1": r.normal(0, R ** -0.5, (R, R)),
         "router.b1": r.normal(0, 0.1, R),
         "router.w2": r.normal(0, R ** -0.5, (R, R)),
         "router.b2": r.normal(0, 0.1, R),
         "router.w3": r.normal(0, 2 * R ** -0.5, (R, Ex + 1)),
         "router.bias": r.normal(0, 0.02, Ex + 1),
         "w_gate": r.normal(0, 0.1, (Ex, C, Fx)),
         "w_up": r.normal(0, 0.1, (Ex, C, Fx)),
         "w_down": r.normal(0, 0.1, (Ex, Fx, C)),
         "mlp_merge.s_r": r.uniform(0.5, 1.5, C),
         "mlp_merge.b_r": r.normal(0, 0.1, C),
         "mlp_merge.s_f": r.uniform(0.5, 1.5, C),
         "mlp_merge.b_f": r.normal(0, 0.1, C)}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w.items()}
    scale = jnp.asarray(r.normal(1, 0.2, (C,)), jnp.float32)
    ex.params[norm.scale.name] = scale
    for j, (rt, moe, mg) in enumerate(zip(routers, moes, merges)):
        for var, key in ((rt.down, "down"), (rt.down_bias, "down_bias"),
                         (rt.norm, "norm"), (rt.w1, "w1"), (rt.b1, "b1"),
                         (rt.w2, "w2"), (rt.b2, "b2"), (rt.w3, "w3"),
                         (rt.bias, "bias")):
            ex.params[var.name] = w[f"router.{key}"]
        for var, key in ((moe.w1, "w_gate"), (moe.w3, "w_up"),
                         (moe.w2, "w_down")):
            ex.params[var.name] = w[key][4 * j:4 * j + 4]
        for var, key in ((mg.s_r, "s_r"), (mg.b_r, "b_r"), (mg.s_f, "s_f"),
                         (mg.b_f, "b_f")):
            ex.params[var.name] = w[f"mlp_merge.{key}"]
    X = r.normal(0, 1, (1, Tx, C)).astype(np.float32)
    first, second, load0, load1 = ex.run(
        "f", feed_dict={x: X}, convert_to_numpy_ret_vals=True)
    with jax.default_matmul_precision("highest"):
        u = ref_zaya1._norm(jnp.asarray(X), scale, 1e-5)
        y, chosen, _ = ref_zaya1.experts(u, w, None, c,
                                         ref_zaya1._Products(None))
        whole = ref_zaya1.merge(jnp.asarray(X), y, w, "mlp_merge")
    own = np.asarray(w["mlp_merge.s_r"] * (X + w["mlp_merge.b_r"])
                     + w["mlp_merge.s_f"] * w["mlp_merge.b_f"])
    np.testing.assert_allclose(first + second - own, np.asarray(whole),
                               atol=2e-5)
    # a token that chose no expert is its scaled residual and b_f, in both
    skipped = np.asarray(chosen) == Ex
    assert 0 < skipped.sum() < Tx
    np.testing.assert_allclose(first[0][skipped], own[0][skipped], atol=1e-6)
    np.testing.assert_allclose(second[0][skipped], own[0][skipped],
                               atol=1e-6)
    # neither share is the whole, and the counters add up to every token once
    assert np.abs(first - np.asarray(whole)).max() > 1e-3
    assert np.abs(second - np.asarray(whole)).max() > 1e-3
    assert load0[4, 0] == load1[4, 0] == skipped.sum()
    assert load0[0].sum() + load1[0].sum() + skipped.sum() == Tx
    assert load0[2, 0] == load1[0].sum() and load1[2, 0] == load0[0].sum()
