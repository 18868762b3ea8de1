"""The router that is a layer of its own (``layers/moe.py StateRouter``) and
the expert layer behind it (``MoELayer(router=)``): the state's
sum down three layers, the top-1 weight and its gradient, the skip choice's
exact zeros and its count, the bias's move, the counters; and that the
programs of ``TopKGate``'s callers are what they were."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.layers.moe import MoELayer, StateRouter, record_moe_load
from hetu_tpu.ops.moe import top_k_route

sys.path.insert(0, os.path.dirname(__file__))

T, C, R, E, F = 48, 32, 8, 6, 16
X = np.random.default_rng(0).normal(0, 1, (1, T, C)).astype(np.float32)


def gelu(x):
    return jax.nn.gelu(x, approximate=False)


def plain_router(u, p, name, prev=None):
    """The equations: ``(logits, r)``."""
    r = u @ p[f"{name}_down_weight"] + p[f"{name}_down_bias"]
    if prev is not None:
        r = r + p[f"{name}_eda_scale"] * prev
    h = r / jnp.sqrt(jnp.mean(r * r, -1, keepdims=True) + 1e-5) * p[
        f"{name}_norm_scale"]
    h = gelu(h @ p[f"{name}_mlp1_weight"] + p[f"{name}_mlp1_bias"])
    h = gelu(h @ p[f"{name}_mlp2_weight"] + p[f"{name}_mlp2_bias"])
    return h @ p[f"{name}_out_weight"], r


def stack(name, held=None, rate=None, layers=3, skip=1):
    """Three expert layers behind their routers, each handed the state of the
    one above: ``y = sum of the layers' outputs``."""
    x = ht.placeholder_op(f"{name}_x", (1, T, C))
    moes, state, ys = [], None, []
    for i in range(layers):
        moe = MoELayer(C, F, num_experts=E, k=1, capacity_factor=None,
                       expert_act="swiglu", renorm_topk=False, track_load=True,
                       held=held,
                       router=StateRouter(C, E, R, skip=skip, bias_rate=rate,
                                          name=f"{name}_r{i}"),
                       name=f"{name}_moe{i}")
        ys.append(moe(x, state=state))
        state = moe.state
        moes.append(moe)
    return x, moes, ys


def perturb(ex, seed=5, bias_spread=0.05):
    r = np.random.default_rng(seed)
    for key, value in list(ex.params.items()):
        if key.endswith(("_scale", "_bias")) and "_load" not in key:
            spread = bias_spread if key.endswith(("0_bias", "1_bias",
                                                  "2_bias")) else 0.3
            ex.params[key] = value + jnp.asarray(
                r.normal(0, spread, value.shape), value.dtype)


def test_the_state_is_a_sum_down_three_layers():
    x, moes, ys = stack("sr_sum")
    ex = ht.Executor({"forward": [m.state for m in moes]
                      + [m.last_op.inputs[m.last_op.at["router"]] for m in moes]}, seed=1)
    perturb(ex)
    out = ex.run("forward", feed_dict={x: X}, convert_to_numpy_ret_vals=True)
    p = {k: jnp.asarray(v) for k, v in ex.params.items()}
    prev = None
    with jax.default_matmul_precision("highest"):
        for i in range(3):
            logits, prev = plain_router(jnp.asarray(X), p, f"sr_sum_r{i}",
                                        prev)
            np.testing.assert_allclose(out[i], prev, atol=1e-5)
            np.testing.assert_allclose(out[3 + i], logits.reshape(T, E + 1),
                                       atol=1e-5)
    # gamma enters where a state comes in, and only there
    names = {v.name for v in graph_variables([moes[2].state])}
    assert "sr_sum_r0_eda_scale" not in names
    assert {"sr_sum_r1_eda_scale", "sr_sum_r2_eda_scale"} <= names
    assert out[2].dtype == np.float32 and out[2].shape == (1, T, R)
    assert {m.state.scope for m in moes} == {"hetu_moe_route"}


def test_top1_weight_zeros_of_the_skip_choice_and_the_count():
    """``y = p_e SwiGLU_e(u)`` for the ONE choice of ``softmax + bias``; a
    token whose choice is the last output gets exact zeros; the load's fifth
    row counts them and holds the state's RMS."""
    x, moes, ys = stack("sr_top1", layers=1)
    moe = moes[0]
    ex = ht.Executor({"forward": [ys[0], moe.chosen(), moe.load(), moe.state,
                                  moe.last_op.inputs[
                                      moe.last_op.at["router"]]]}, seed=2)
    perturb(ex, bias_spread=0.1)
    y, chosen, load, state, logits = ex.run(
        "forward", feed_dict={x: X}, convert_to_numpy_ret_vals=True)
    p = {k: np.asarray(v) for k, v in ex.params.items()}
    probs = np.asarray(jax.nn.softmax(logits, -1))
    want = np.argmax(probs + p["sr_top1_r0_bias"], -1)
    np.testing.assert_array_equal(chosen[:, 0], want)
    skipped = want == E
    assert 0 < skipped.sum() < T
    assert (y[0][skipped] == 0).all() and (y[0][~skipped] != 0).any()
    w1, w2, w3 = (p[f"sr_top1_moe0_w{i}"] for i in (1, 2, 3))
    for t in np.flatnonzero(~skipped)[:8]:
        e = want[t]
        u = X[0, t]
        z = (np.asarray(jax.nn.silu(u @ w1[e])) * (u @ w3[e])) @ w2[e]
        np.testing.assert_allclose(y[0, t], probs[t, e] * z, atol=1e-5)
    assert load.shape == (5, E)
    np.testing.assert_array_equal(load[0], np.bincount(want, minlength=E + 1)[:E])
    np.testing.assert_array_equal(load[1], load[0])
    assert load[2, 0] == 0 and load[4, 0] == skipped.sum()
    np.testing.assert_allclose(load[4, 1], np.sqrt(np.mean(state ** 2)),
                               rtol=1e-5)
    assert moe.held == (0, E)           # laid out as a layer holding them all


def test_the_gradient_reaches_the_router_through_the_weight_alone():
    x, moes, ys = stack("sr_grad", layers=2)
    loss = ht.reduce_sum_op(ys[1] * ys[1], axes=None)
    variables = graph_variables([loss], trainable_only=True)
    ex = ht.Executor({"forward": [ys[1], moes[1].chosen()],
                      "grads": [loss] + ht.gradients(loss, variables)},
                     seed=3)
    perturb(ex)
    p = {k: jnp.asarray(v) for k, v in ex.params.items()}
    _, chosen = ex.run("forward", feed_dict={x: X},
                       convert_to_numpy_ret_vals=True)
    choice = jnp.asarray(chosen[:, 0])

    def plain(p):
        u = jnp.asarray(X)
        _, r0 = plain_router(u, p, "sr_grad_r0")
        logits, _ = plain_router(u, p, "sr_grad_r1", r0)
        w = jnp.take_along_axis(jax.nn.softmax(logits.reshape(T, -1), -1),
                                choice[:, None], 1)[:, 0]
        held = choice < E
        e = jnp.minimum(choice, E - 1)
        w1, w2, w3 = (p[f"sr_grad_moe1_w{i}"][e] for i in (1, 2, 3))
        z = jnp.einsum("tf,tfc->tc", jax.nn.silu(jnp.einsum(
            "tc,tcf->tf", u[0], w1)) * jnp.einsum("tc,tcf->tf", u[0], w3), w2)
        y = jnp.where(held[:, None], w[:, None] * z, 0.0)
        return jnp.sum(y * y)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(plain)(p)
    got = ex.run("grads", feed_dict={x: X}, convert_to_numpy_ret_vals=True)
    names = [v.name for v in variables]
    assert "sr_grad_r1_bias" not in names and "sr_grad_r0_eda_scale" not in names
    assert "sr_grad_r1_eda_scale" in names and "sr_grad_r0_down_weight" in names
    # of the layer above only the STATE reaches this layer's output: what
    # lies behind the state in its router gets no gradient
    dead = ("sr_grad_r0_norm", "sr_grad_r0_mlp", "sr_grad_r0_out")
    for var, g in zip(variables, got[1:]):
        w = np.asarray(want[var.name])
        assert (np.abs(w).max() > 0) != var.name.startswith(dead), var.name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, var.name


def test_the_bias_moves_against_the_load_over_every_choice():
    x, moes, ys = stack("sr_bias", rate=1e-2, layers=1)
    moe = moes[0]
    loss = ht.reduce_sum_op(ys[0] * ys[0], axes=None)
    opt = ht.SGDOptimizer(learning_rate=0.0)
    ex = ht.Executor({"train": [loss, opt.minimize(loss), moe.router_bias(),
                                moe.chosen()]}, seed=4)
    perturb(ex, bias_spread=0.1)
    before = np.asarray(ex.params["sr_bias_r0_bias"])
    _, _, bias, chosen = ex.run("train", feed_dict={x: X},
                                convert_to_numpy_ret_vals=True)
    load = np.bincount(chosen[:, 0], minlength=E + 1)
    assert load[E] > 0
    want = before + 1e-2 * np.sign(load.mean() - load)
    np.testing.assert_allclose(bias, want, atol=1e-7)
    np.testing.assert_allclose(ex.params["sr_bias_r0_bias"], want, atol=1e-7)


def test_without_a_bias_the_softmax_route_is_what_it_was():
    logits = jnp.asarray(np.random.default_rng(1).normal(0, 1, (64, 8)),
                         jnp.float32)
    idx, gate, probs = top_k_route(logits, 2)
    zero = top_k_route(logits, 2, bias=jnp.zeros(8))
    for a, b in zip((idx, gate, probs), zero):
        np.testing.assert_array_equal(a, b)
    bias = jnp.zeros(8).at[3].set(10.0)
    idx_b, gate_b, _ = top_k_route(logits, 2, bias=bias)
    assert (np.asarray(idx_b)[:, 0] == 3).all()
    # the gate is the chosen probability, never the biased one
    np.testing.assert_allclose(np.asarray(gate_b)[:, 0],
                               np.asarray(probs)[:, 3], rtol=1e-6)


def test_the_counters_of_a_layer_with_a_skip_choice():
    telemetry.enable()
    try:
        telemetry.get_registry().reset()
        load = np.zeros((5, 4))
        load[0] = load[1] = [5, 3, 0, 2]
        load[2, 0], load[4, 0], load[4, 1] = 7, 4, 1.5
        record_moe_load("sr_layer", load)
        record_moe_load("sr_layer", load)
        snap = telemetry.get_registry().snapshot()

        def value(name):
            (s,) = [s for s in snap[name]["samples"]
                    if s["labels"] == {"layer": "sr_layer"}]
            return s["value"]
        assert value("hetu_moe_pairs_skipped_total") == 8
        assert value("hetu_moe_pairs_elsewhere_total") == 14
        assert value("hetu_moe_pairs_routed_total") == 20
        assert value("hetu_moe_pairs_dropped_total") == 0
        assert value("hetu_moe_router_state_rms") == 1.5
        # a load of four rows (no skip choice) sets neither
        telemetry.get_registry().reset()
        record_moe_load("sr_plain", load[:4])
        snap = telemetry.get_registry().snapshot()
        assert "hetu_moe_pairs_skipped_total" not in snap
        assert "hetu_moe_router_state_rms" not in snap
    finally:
        telemetry.shutdown()


def test_the_loss_side_nodes_read_the_ops_inputs_by_name():
    """A layer whose op has a state, a bias and the load's variable among its
    inputs at once (the second of a stack): the balance loss, the z-loss and
    the chosen experts are those of the logits and the bias the op's names
    find, whatever stands between them in the list."""
    from hetu_tpu.ops.moe import expert_load, load_balancing_loss
    x, moes, ys = stack("sr_names", layers=2, skip=0)
    moe = moes[1]
    op = moe.last_op
    assert list(op.at) == ["x", "w1", "w2", "w3", "router", "state", "bias",
                           "load"]
    named = {n: op.inputs[i] for n, i in op.at.items()}
    assert named["state"] is moe.state and named["bias"] is moe.gate.bias
    assert named["load"] is moe.load_var
    nodes = [moe.aux_loss(), moe.z_loss(), moe.chosen()]
    assert all(n.inputs == op.inputs for n in nodes)
    ex = ht.Executor({"forward": nodes + [named["router"], ys[1]]}, seed=3)
    perturb(ex, bias_spread=0.2)
    aux, z, chosen, logits, _ = ex.run("forward", feed_dict={x: X},
                                       convert_to_numpy_ret_vals=True)
    probs = jax.nn.softmax(jnp.asarray(logits), -1)
    want = np.argmax(np.asarray(probs) + np.asarray(
        ex.params["sr_names_r1_bias"]), -1)
    assert (want != np.argmax(logits, -1)).any()        # the bias was read
    np.testing.assert_array_equal(chosen[:, 0], want)
    lse = jax.nn.logsumexp(jnp.asarray(logits), -1)
    np.testing.assert_allclose(z, jnp.mean(lse * lse), rtol=1e-6)
    np.testing.assert_allclose(aux, load_balancing_loss(
        probs, expert_load(jnp.asarray(want), E)), rtol=1e-6)


#: read at the parent of PR 58 (commit aa26f6b) from the toy programs of
#: ``tests/test_<family>_reference.py build``: nodes of the forward and the
#: gradient program, the loss, the sum of the logits, the sum of all gradients
PINNED = {
    "olmoe": (127, 113, "0x1.6d9fb20000000p+2", "-0x1.93fa652c5a780p+6",
              "-0x1.19b9a90a06f20p+1"),
    "laguna": (208, 170, "0x1.64c7260000000p+2", "0x1.8aa3cc47612b4p+5",
               "0x1.2b83365ff0b60p-1"),
    "qwen3_next": (242, 234, "0x1.666e9a0000000p+2", "-0x1.2ddf556673a00p+5",
                   "0x1.43bb4d3db8708p+5"),
}


@pytest.mark.parametrize("family", sorted(PINNED))
def test_a_topkgate_program_is_what_it_was_to_the_bit(family):
    """``MoELayer``, ``residual_sublayer`` and ``causal_conv`` changed under
    these three: their toy programs have the node counts and, to the bit, the
    loss, the logits and the gradients they had."""
    mod = __import__(f"test_{family}_reference")
    built = (mod.build("lagunapin") if family == "laguna" else mod.build())
    ex, feed = built[1], built[3]
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    grads = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    got = (len(ex.subexecutor["forward"].topo),
           len(ex.subexecutor["grads"].topo), float(out[1]).hex(),
           float(np.float64(out[0]).sum()).hex(),
           float(sum(np.float64(g).sum() for g in grads[1:])).hex())
    assert got == PINNED[family]
