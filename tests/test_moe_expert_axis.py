"""Dropless experts spread over a mesh axis (``ops/moe.py
dropless_moe_over_axis``, ``MoELayer(ep_axis=)``, ``parallel.ExpertParallel``)
on four CPU devices: the op under the axis is the uncut ``dropless_moe``,
forward and in every gradient; the four ``held=`` shares sum to the same,
which ties the one-chip cells' cut to this one; a routing that sends every
pair to one device drops nothing and counts its further passes; the load is
the host's; the layer through ``ht.Executor`` under the strategy is the layer
on one device; and on a TPU the experts' kernels see no mesh inside the
``shard_map`` (their choices are recorded ``pallas``, none ``mesh``)."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

import hetu_tpu as ht
from hetu_tpu.layers.moe import LATER, MoELayer, record_moe_load
from hetu_tpu.ops.moe import (dropless_moe, dropless_moe_over_axis,
                              exchange_bytes, exchange_bytes_a_step,
                              held_rows, top_k_route)
from hetu_tpu.ops.pallas import dispatch
from hetu_tpu.parallel import ExpertParallel
from hetu_tpu.parallel.mesh import make_mesh

N, T, H, F, E, K = 4, 64, 16, 8, 16, 4


@pytest.fixture(scope="module")
def mesh():
    return make_mesh({"dp": N}, devices=jax.devices()[:N])


@pytest.fixture(scope="module")
def operands():
    r = np.random.default_rng(0)
    return tuple(jnp.asarray(a, jnp.float32) for a in (
        r.standard_normal((T, H)), r.standard_normal((T, E)),
        r.standard_normal((E, H, F)) * 0.3, r.standard_normal((E, H, F)) * 0.3,
        r.standard_normal((E, F, H)) * 0.3))


def over_axis(mesh, tokens, idx, gate, *weights):
    """``(y, load, computed, later)`` of the op under the axis."""
    def local(t, i, g, *w):
        y, host = dropless_moe_over_axis(t, i, g, *w, axis="dp",
                                         num_experts=E)
        return y, host["load"], host["computed"], host["later"]
    return shard_map(local, mesh=mesh, in_specs=(P("dp"),) * 6,
                     out_specs=(P("dp"), P(), P(), P()),
                     check_vma=False)(tokens, idx, gate, *weights)


def routed(logits):
    idx, gate, _ = top_k_route(logits, K, renorm=True)
    return idx, gate


def test_the_op_under_the_axis_is_the_uncut_op(mesh, operands):
    """Forward, the host's load, and the gradient of every operand (the
    tokens, the router's logits through the gates, the three stacks)."""
    tokens, logits, *weights = operands

    def whole(tokens, logits, *w):
        return dropless_moe(tokens, *routed(logits), *w)

    def spread(tokens, logits, *w):
        return over_axis(mesh, tokens, *routed(logits), *w)
    y0, counts = jax.jit(whole)(*operands)
    y1, load, computed, later = jax.jit(spread)(*operands)
    assert np.abs(np.asarray(y0 - y1)).max() < 1e-5
    np.testing.assert_array_equal(load, counts["load"])
    np.testing.assert_array_equal(computed, load)
    assert load.sum() == T * K and not np.asarray(later).any()
    every = tuple(range(len(operands)))
    g0 = jax.jit(jax.grad(lambda *a: jnp.sum(whole(*a)[0] ** 2), every))(
        *operands)
    g1 = jax.jit(jax.grad(lambda *a: jnp.sum(spread(*a)[0] ** 2), every))(
        *operands)
    for a, b in zip(g0, g1):
        assert np.abs(np.asarray(a)).max() > 0
        assert np.abs(np.asarray(a - b)).max() < 1e-5 * np.abs(
            np.asarray(a)).max() + 1e-6


def test_the_four_held_shares_sum_to_the_uncut_op(operands):
    """What ``MoELayer(held=)`` leaves out on one chip is what the other
    chips add: the shares' sum is the whole."""
    tokens, logits, *weights = operands
    idx, gate = routed(logits)
    whole, _ = dropless_moe(tokens, idx, gate, *weights)
    count = E // N
    parts = [dropless_moe(tokens, idx, gate,
                          *(w[r * count:(r + 1) * count] for w in weights),
                          held=(r * count, count),
                          rows=held_rows(idx.size, E, count))[0]
             for r in range(N)]
    assert np.abs(np.asarray(sum(parts) - whole)).max() < 1e-5


def test_every_pair_on_one_device_is_computed_by_further_passes(mesh,
                                                                operands):
    tokens, _, *weights = operands
    idx = jnp.asarray(np.tile(np.arange(4, 8), (T, 1)), jnp.int32)
    gate = jnp.full((T, K), 0.25, jnp.float32)
    want, _ = dropless_moe(tokens, idx, gate, *weights)
    y, load, computed, later = jax.jit(
        lambda *a: over_axis(mesh, *a))(tokens, idx, gate, *weights)
    assert np.abs(np.asarray(y - want)).max() < 1e-5
    np.testing.assert_array_equal(computed, load)      # nothing dropped
    assert load[4:8].tolist() == [T] * 4 and load.sum() == T * K
    # one pass lays out twice the mean share: the rest came later
    assert later.sum() == T * K - held_rows(T * K, E, E // N)


def test_what_spills_over_the_first_window_has_the_uncut_ops_gradients(
        mesh, operands):
    """Three of four choices on one device's experts: its first window holds
    twice the mean share and two smaller windows the rest; forward and every
    gradient are the uncut op's, through the loops."""
    tokens, logits, *weights = operands
    skew = logits.at[:, 4:7].add(20.0)      # experts 4, 5, 6: device 1's
    idx, gate = routed(skew)
    assert (np.asarray(idx)[:, :3] // (E // N) == 1).all()

    def whole(tokens, gate, *w):
        return dropless_moe(tokens, idx, gate, *w)[0]

    def spread(tokens, gate, *w):
        return over_axis(mesh, tokens, idx, gate, *w)
    *_, later = jax.jit(spread)(tokens, gate, *weights)
    first = held_rows(T * K, E, E // N)
    assert later.sum() >= 3 * T - first > 0
    every = tuple(range(5))
    g0 = jax.jit(jax.grad(lambda *a: jnp.sum(whole(*a) ** 2), every))(
        tokens, gate, *weights)
    g1 = jax.jit(jax.grad(lambda *a: jnp.sum(spread(*a)[0] ** 2), every))(
        tokens, gate, *weights)
    for a, b in zip(g0, g1):
        assert np.abs(np.asarray(a - b)).max() < 1e-5 * np.abs(
            np.asarray(a)).max() + 1e-6


def test_exchange_bytes_are_what_a_device_receives():
    """Three other devices' 8,192 tokens of 2,304 bf16 numbers: the issue's
    113 MB a collective, and the choices and weights beside the gather."""
    got = exchange_bytes(8192, 2304, 8, 4, 2)
    assert got["scatter"] == 3 * 8192 * 2304 * 2 == 113_246_208
    assert got["gather"] == got["scatter"] + 3 * 8192 * 8 * 8


TENSOR = re.compile(r"tensor<((?:\d+x)+)([a-z]+)(\d+)>")


def received(text, op, side):
    """How many ``stablehlo.<op>`` a lowered program runs and the bytes one
    of ``N`` devices receives in them: all but its own ``N``-th of what is
    whole, an all-gather's result (``side`` 1) or a reduce-scatter's operand
    (``side`` 0)."""
    whole = [m[side] for m in re.findall(
        rf'"stablehlo\.{op}"\(.*?: \((tensor<[^>]*>)\) -> (tensor<[^>]*>)',
        text, flags=re.S)]
    nbytes = 0
    for t in whole:
        dims, _, bits = TENSOR.fullmatch(t).groups()
        nbytes += int(np.prod([int(d) for d in dims[:-1].split("x")])
                      * int(bits) // 8)
    return len(whole), nbytes * (N - 1) // N


@pytest.mark.parametrize("forward_passes", [1, 2], ids=["kept", "recomputed"])
def test_a_steps_exchange_is_what_the_lowered_step_runs(mesh, operands,
                                                        forward_passes):
    """``exchange_bytes_a_step`` against the program: a training step gathers
    the tokens (with their choices and weights) and the sums' cotangent, and
    scatters the sums and the tokens' and weights' cotangents; a layer that
    is recomputed in the backward pass gathers its tokens a THIRD time and
    scatters nothing more (the v5e's trace: twelve all-gathers of tokens and
    eight reduce-scatters a step of four recomputed layers; PERF.md, PR 72).
    Read off the lowered text: XLA on the CPU merges the recomputation into
    the forward pass, a TPU does not."""
    tokens, logits, *weights = operands
    idx, gate = routed(logits)

    def layer(tokens, gate, *w):
        return over_axis(mesh, tokens, idx, gate, *w)[0]
    if forward_passes == 2:
        layer = jax.checkpoint(layer)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(layer(*a) ** 2),
                            tuple(range(5)))).lower(
        tokens, gate, *weights).as_text()
    gathers, gathered = received(text, "all_gather", 1)
    scatters, scattered = received(text, "reduce_scatter", 0)
    # tokens, choices, weights a forward pass, and the sums' cotangent
    assert gathers == 3 * forward_passes + 1
    # the sums; the tokens' and the weights' cotangents
    assert scatters == 3
    assert {"gather": gathered, "scatter": scattered} == exchange_bytes_a_step(
        exchange_bytes(T // N, H, K, N, 4), forward_passes)


@pytest.fixture(scope="module")
def layers(mesh):
    """The same layer on one device and under ``ExpertParallel``."""
    def build(name, strategy=None):
        x = ht.placeholder_op(f"{name}_x", (N, 16, H))
        layer = MoELayer(H, F, num_experts=E, k=K, capacity_factor=None,
                         expert_act="swiglu", renorm_topk=True,
                         track_load=True, ep_axis="dp", name=name)
        y = layer(x)
        loss = ht.reduce_sum_op(y * y, axes=None)
        variables = [layer.gate.wg, layer.w1, layer.w2, layer.w3]
        ex = ht.Executor({"run": [y, layer.load(), layer.chosen()]
                          + ht.gradients(loss, variables)}, seed=2,
                         dist_strategy=strategy)
        return x, layer, ex
    return build("epone"), build("epfour", ExpertParallel(mesh=mesh))


def test_the_layer_under_the_strategy_is_the_layer_on_one_device(layers):
    (x0, one, ex0), (x1, four, ex1) = layers
    for a, b in zip((one.gate.wg, one.w1, one.w2, one.w3),
                    (four.gate.wg, four.w1, four.w2, four.w3)):
        ex1.params[b.name] = jax.device_put(
            np.asarray(ex0.params[a.name]), ex1.params[b.name].sharding)
    u = np.random.default_rng(4).normal(0, 1, (N, 16, H)).astype(np.float32)
    got0 = ex0.run("run", feed_dict={x0: u}, convert_to_numpy_ret_vals=True)
    got1 = ex1.run("run", feed_dict={x1: u}, convert_to_numpy_ret_vals=True)
    assert four.w1.dist_state.splits == {0: "dp"}
    assert len(ex1.params[four.w1.name].addressable_shards[0].data) == E // N
    for a, b in zip(got0, got1):
        assert np.abs(a - b).max() < 1e-5 * max(np.abs(a).max(), 1.0)
    load = got1[1]
    assert load.shape == (4, E) and load[0].sum() == N * 16 * K
    # the exchange was sized when the step was traced, and is counted a step
    assert one.last_op.exchange is None
    assert four.last_op.exchange == exchange_bytes(16, H, K, N, 4)


def test_the_hosts_load_and_the_exchange_are_counted(live_registry):
    load = np.zeros((4, E))
    load[0] = load[1] = 16
    load[LATER, 3] = 5
    for _ in range(2):        # two steps: what a step is handed, added
        record_moe_load("ep", load, exchange={"gather": 10, "scatter": 7})
    from hetu_tpu import telemetry
    snap = telemetry.get_registry().snapshot()

    def value(name, **labels):
        return sum(s["value"] for s in snap[name]["samples"]
                   if all(s["labels"].get(k) == v for k, v in labels.items()))
    assert value("hetu_moe_pairs_routed_total", layer="ep") == 2 * 16 * E
    assert value("hetu_moe_pairs_dropped_total", layer="ep") == 0
    assert value("hetu_moe_pairs_elsewhere_total", layer="ep") == 0
    assert value("hetu_moe_pairs_over_bound_total", layer="ep") == 2 * 5
    assert value("hetu_moe_exchange_bytes_total", layer="ep",
                 direction="gather") == 20
    assert value("hetu_moe_exchange_bytes_total", layer="ep",
                 direction="scatter") == 14
