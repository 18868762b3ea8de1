"""Mamba-1's selective scan (``ops/selective_scan.py``): the chunked
``jax.numpy`` form and the Pallas kernel pair ``hetu_s6_fwd`` / ``hetu_s6_bwd``
(interpret mode on the CPU) against the recurrence one position at a time,
outputs and all five cotangents, with decays from 1e-4 to 5 a position and a
length that is no multiple of the chunk; the state's type; what the rule
refuses; how a call is dispatched and counted; and the kernels compiled for a
described v5e at the published widths."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu import telemetry
from hetu_tpu.ops import selective_scan as ss
from hetu_tpu.ops.pallas import dispatch, selective_scan as kernels

B, S, C, N = 2, 50, 256, 16


def inputs(dtype=jnp.float32, s=S, c=C, b=B):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    u = jax.random.normal(ks[0], (b, s, c)).astype(dtype)
    # delta A from about 1e-4 to 5 a position
    A = -jnp.exp(jax.random.uniform(ks[1], (c, N), minval=np.log(0.01),
                                    maxval=np.log(16.0)))
    delta = jnp.exp(jax.random.uniform(ks[2], (b, s, c),
                                       minval=np.log(0.01),
                                       maxval=np.log(0.3)))
    Bm, Cm = (jax.random.normal(k, (b, s, N)) for k in ks[3:5])
    dy = jax.random.normal(ks[5], (b, s, c))
    return (u, delta, A, Bm, Cm), dy


def gap(a, b):
    a, b = (np.asarray(t, np.float64) for t in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def oracle():
    args, dy = inputs()
    assert float((args[1][..., None] * -args[2]).min()) < 2e-4
    assert float((args[1][..., None] * -args[2]).max()) > 4.0
    y, vjp = jax.vjp(lambda *a: ss.recurrent_selective_scan(*a)[0], *args)
    return args, dy, y, vjp(dy)


def test_the_chunked_form_is_the_recurrence_and_keeps_chunk_edges(oracle):
    args, dy, y, grads = oracle
    got, vjp = jax.vjp(lambda *a: ss.selective_scan_jnp(*a, chunk=16), *args)
    assert got.shape == y.shape and got.dtype == jnp.float32
    assert gap(got, y) < 1e-6
    for name, g, w in zip("u delta A B C".split(), vjp(dy), grads):
        assert gap(g, w) < 1e-5, name
    # what the backward pass keeps: the states in front of the chunks, not
    # one a position
    text = jax.make_jaxpr(jax.grad(
        lambda u: ss.selective_scan_jnp(u, *args[1:], chunk=16).sum()))(
        args[0]).pretty_print()
    held = {int(n) for n in re.findall(rf"f32\[(\d+),{B},{C},{N}\]", text)}
    assert held == {16}, held      # a chunk's states again, never all 64


@pytest.mark.parametrize("lanes", [128, 256])
def test_the_kernel_pair_is_the_recurrence(oracle, lanes):
    args, dy, y, grads = oracle
    got, hs = kernels.hetu_s6_fwd(*args, interpret=True, chunk=16,
                                  lanes=lanes)
    assert hs.shape == (B, 4, N, C)          # 50 positions: four chunks of 16
    assert gap(got, y) < 1e-6
    back = kernels.hetu_s6_bwd(*args, hs, dy, interpret=True, chunk=16,
                               lanes=lanes)
    for name, g, w in zip("u delta A B C".split(), back, grads):
        assert g.shape == w.shape and gap(g, w) < 1e-5, name


def test_the_custom_vjp_in_bf16_at_the_default_chunk():
    args, dy = inputs(jnp.bfloat16, s=40, c=128, b=1)
    want, vjp_w = jax.vjp(lambda *a: ss.recurrent_selective_scan(*a)[0],
                          *args)
    got, vjp = jax.vjp(kernels.s6, *args)
    assert gap(got, want) < 1e-6
    for name, g, w in zip("u delta A B C".split(), vjp(dy), vjp_w(dy)):
        assert g.dtype == w.dtype, name
        assert gap(g, w) < (1e-2 if name == "u" else 1e-5), name


def test_a_bf16_state_is_another_result(oracle):
    args, _, y, _ = oracle
    low = ss.recurrent_selective_scan(*args, state_dtype=jnp.bfloat16)[0]
    assert gap(low, y) > 1e-3


@pytest.mark.parametrize("channels, states, dtype, reason", [
    (192, 16, jnp.float32, "channels_not_128_aligned"),
    (128, 12, jnp.float32, "state_not_a_power_of_two_in_8_128"),
    (128, 256, jnp.float32, "state_not_a_power_of_two_in_8_128"),
    (128, 16, jnp.float16, "dtype:float16"),
    (128, 16, jnp.bfloat16, None),
    (5120, 16, jnp.bfloat16, None)])
def test_what_the_rule_refuses(channels, states, dtype, reason):
    u = jax.ShapeDtypeStruct((1, 64, channels), dtype)
    A = jax.ShapeDtypeStruct((channels, states), jnp.float32)
    assert kernels.unsupported(u, A) == reason


def test_dispatch_and_counters(monkeypatch):
    args, _ = inputs(s=32, c=128, b=1)
    telemetry.enable()
    try:
        before, chose = ss.entries(), dispatch.choices()
        y = ss.selective_scan(*args)          # the CPU: no choice, the form
        assert ss.entries().get("xla", 0) == before.get("xla", 0) + 1
        assert dispatch.choices() == chose
        taken = []

        def take(kernel, mesh, reason=None, asked=False):
            taken.append((kernel, reason))
            return dispatch.record(kernel, reason)
        monkeypatch.setattr(dispatch, "take", take)
        z = ss.selective_scan(*args)          # asked: the kernels, interpreted
        assert taken == [("selective_scan", None)]
        assert ss.entries()["pallas"] == before.get("pallas", 0) + 1
        assert dispatch.choices()[("selective_scan", "pallas", "")] == (
            chose.get(("selective_scan", "pallas", ""), 0) + 1)
        assert gap(z, y) < 1e-6
    finally:
        telemetry.shutdown()
    assert "selective_scan" in dispatch.NO_CHOICE_OFF_TPU


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_pair_compiles_for_a_v5e_at_the_published_widths(v5e):
    """Mosaic takes both kernels at 5,120 channels x 16 states (a shorter
    sequence: the grid's length changes nothing a program holds)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        def sds(shape, dt):
            return jax.ShapeDtypeStruct(shape, dt, sharding=v5e)
        s, c = 1024, 5120
        args = (sds((1, s, c), jnp.bfloat16), sds((1, s, c), jnp.float32),
                sds((c, N), jnp.float32), sds((1, s, N), jnp.float32),
                sds((1, s, N), jnp.float32))
        fwd = jax.jit(lambda *a: kernels.hetu_s6_fwd(
            *a, interpret=False)).lower(*args).compile()
        assert "hetu_s6_fwd" in fwd.as_text()
        bwd = jax.jit(lambda *a: kernels.hetu_s6_bwd(
            *a, interpret=False)).lower(
            *args, sds((1, s // kernels.T, N, c), jnp.float32),
            sds((1, s, c), jnp.float32)).compile()
        assert "hetu_s6_bwd" in bwd.as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
