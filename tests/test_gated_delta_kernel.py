"""The gated delta rule's Pallas kernel pair (``hetu_tpu/ops/pallas/
gated_delta.py``), in interpret mode on the CPU: against the token-by-token
recurrence and against the ``jax.numpy`` chunked form at the published head
size, outputs, last state and the gradient of all five operands; a long
memory, where a bf16 state is seen and the kernels are not; the rule by which
``chunk_gated_delta_rule`` takes them; the ``GatedDeltaNet`` layer through
them against the same layer through the ``jax.numpy`` form; and the layer's
step compiled for a described v5e."""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.ops import gated_delta
from hetu_tpu.ops.gated_delta import (chunk_gated_delta_rule,
                                      chunk_gated_delta_rule_jnp,
                                      recurrent_gated_delta_rule)
from hetu_tpu.ops.pallas import common, dispatch, gated_delta as kernels

D = 128                       # the published head size, keys and values
#: the kept inverses of a layer of 32 heads over 8,192 positions, eight
#: chunks a program, as an HLO shape
KEPT = "1,32,16,8,64,64"


def delta_inputs(T, B=1, H=1, dtype=jnp.float32, seed=0, rate=None):
    """Unit keys, queries scaled by ``D ** -0.5``; decays of about a third a
    position, or of ``rate`` (first head, last head) log-spaced."""
    r = np.random.default_rng(seed)
    q, k = (r.normal(size=(B, T, H, D)) for _ in range(2))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * D ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = r.normal(size=(B, T, H, D))
    if rate is None:
        g = -np.exp(r.normal(size=(B, T, H))) * 0.3
    else:
        g = -np.geomspace(*rate, H) * np.logaddexp(
            0.0, r.normal(size=(B, T, H)) + 1.0)
    beta = 1 / (1 + np.exp(-r.normal(size=(B, T, H))))
    return (tuple(jnp.asarray(x, dtype) for x in (q, k, v))
            + tuple(jnp.asarray(x, jnp.float32) for x in (g, beta)))


def scalar_grad(fn):
    def f(*a):
        o, s = fn(*a)
        return jnp.sum(jnp.sin(o.astype(jnp.float32))) + jnp.sum(s ** 2)
    return jax.grad(f, argnums=range(5))


@pytest.mark.parametrize("T,B,H", [(64, 1, 1), (128, 2, 1), (100, 1, 4),
                                   (7, 2, 1), (640, 1, 1)])
def test_kernels_are_the_recurrence(T, B, H):
    """f32 operands, at the tolerances ``tests/test_gated_delta_rule.py``
    holds the ``jax.numpy`` form to: outputs, last state and the gradient of
    q, k, v, g and beta; lengths that are and are not a multiple of the
    chunk, one and several chunks a program, heads that do and do not fill
    a program."""
    x = delta_inputs(T, B, H)
    o1, s1 = recurrent_gated_delta_rule(*x)
    o2, s2 = kernels.gated_delta_rule(*x)
    assert o2.shape == o1.shape and o2.dtype == o1.dtype
    np.testing.assert_allclose(o2, o1, atol=2e-6)
    np.testing.assert_allclose(s2, s1, atol=5e-6)
    want = scalar_grad(recurrent_gated_delta_rule)(*x)
    got = scalar_grad(kernels.gated_delta_rule)(*x)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert np.abs(g - w).max() < 1e-5 * np.abs(w).max()


@pytest.mark.parametrize("T,B,H", [(64, 2, 1), (128, 1, 4), (100, 1, 1),
                                   (7, 1, 2)])
def test_kernels_are_the_chunked_form_in_bf16(T, B, H):
    """bf16 operands against the ``jax.numpy`` chunked form on the same
    operands: the two round the products inside a chunk alike, so they differ
    by how they order sums, not by bf16's 0.4%."""
    x = delta_inputs(T, B, H, jnp.bfloat16, seed=1)
    o1, s1 = jax.jit(chunk_gated_delta_rule_jnp)(*x)
    o2, s2 = kernels.gated_delta_rule(*x)
    assert o2.dtype == jnp.bfloat16 and s2.dtype == jnp.float32

    def close(got, want, tol):
        got, want = (np.asarray(t, np.float32) for t in (got, want))
        assert np.abs(got - want).max() <= tol * np.abs(want).max()
    close(o2, o1, 2e-3)                  # half a bf16 step of the largest
    close(s2, s1, 5e-6)                  # f32, as beside f32 operands
    want = jax.jit(scalar_grad(chunk_gated_delta_rule_jnp))(*x)
    got = scalar_grad(kernels.gated_delta_rule)(*x)
    # dq, dk, dv are bf16 (a step or two of the largest); dg, dbeta f32
    for g, w, tol in zip(got, want, (1e-2, 1e-2, 1e-2, 1e-4, 1e-4)):
        assert g.shape == w.shape and g.dtype == w.dtype
        close(g, w, tol)


def l2_gap(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.sqrt(((got - want) ** 2).sum() / (want ** 2).sum())


@pytest.mark.parametrize("rate,seed", [((1e-3, 1e-1), 1), ((1e-4, 1e-2), 3)])
def test_bf16_operands_keep_every_pass_of_the_state_products(rate, seed,
                                                             monkeypatch):
    """bf16 q, k, v (what the chip runs) over a long memory, against the
    ``jax.numpy`` form at ``HIGHEST`` on the same operands: the last state,
    which no bf16 rounding inside a chunk reaches but ``K_beta``'s, that both
    forms make alike.  All 24 bits of the f32 operands give 2.4e-7 to 2.9e-7
    of it; with a part dropped (16 bits, three passes) 4.5e-6, which has to
    fail."""
    x = delta_inputs(256, 1, 2, jnp.bfloat16, seed=seed, rate=rate)
    want = jax.jit(chunk_gated_delta_rule_jnp)(*x)[1]
    assert l2_gap(kernels.gated_delta_rule(*x)[1], want) < 1e-6
    monkeypatch.setattr(common, "PARTS", 2)
    jax.clear_caches()
    try:
        assert l2_gap(kernels.gated_delta_rule(*x)[1], want) > 2e-6
    finally:
        monkeypatch.undo()
        jax.clear_caches()


@pytest.mark.parametrize("left,right", [
    (jnp.float32, jnp.float32), (jnp.bfloat16, jnp.float32),
    (jnp.float32, jnp.bfloat16)])
def test_an_f32_product_is_six_passes_and_none_less(left, right, monkeypatch):
    """``common.dot32``, which every product with the state, ``T``, ``W`` and ``V'``
    goes through, forward and backward: an f32 operand enters with all of its
    mantissa and a bf16 one as it is; one part fewer is seen."""
    r = np.random.default_rng(11)
    a = jnp.asarray(r.normal(size=(64, 128)), left)
    b = jnp.asarray(r.normal(size=(128, 128)), right)
    want = np.asarray(a, np.float64) @ np.asarray(b, np.float64)

    def gap():
        got = common.dot32(a, b, common.NN)
        assert got.dtype == jnp.float32
        return l2_gap(got, want)
    assert gap() < 1e-7
    monkeypatch.setattr(common, "PARTS", 2)
    assert gap() > 1e-6


def test_a_long_memory_sees_a_bf16_state_and_not_the_kernels():
    """Decays of 1e-4 to 1e-1 a position over 384 positions (the benchmark's
    probe at a length the CPU can walk): the last state of a recurrence that
    carries its state in bf16 is off by more than 1%, the kernels' on bf16
    operands by what the ``jax.numpy`` form is, and on f32 operands by
    rounding."""
    def gap(got, want):
        got, want = (np.asarray(t, np.float64) for t in (got, want))
        return np.sqrt(((got - want) ** 2).sum((0, 2, 3))
                       / (want ** 2).sum((0, 2, 3))).max()
    x = delta_inputs(384, 1, 4, jnp.bfloat16, seed=7, rate=(1e-4, 1e-1))
    xf = tuple(t.astype(jnp.float32) for t in x)
    exact = recurrent_gated_delta_rule(*xf)[1]
    low = recurrent_gated_delta_rule(*xf, state_dtype=jnp.bfloat16)[1]
    assert gap(low, exact) > 1e-2
    plain = gap(jax.jit(chunk_gated_delta_rule_jnp)(*x)[1], exact)
    mine = gap(kernels.gated_delta_rule(*x)[1], exact)
    assert mine < 1e-3 and mine < 1.2 * plain
    assert gap(kernels.gated_delta_rule(*xf)[1], exact) < 2e-6


# -- the rule of dispatch -----------------------------------------------------

def rule_inputs(dk=D, dv=D, dtype=jnp.bfloat16, H=2, T=64):
    sds = jax.ShapeDtypeStruct
    return (sds((1, T, H, dk), dtype), sds((1, T, H, dk), dtype),
            sds((1, T, H, dv), dtype), sds((1, T, H), jnp.float32),
            sds((1, T, H), jnp.float32))


@pytest.fixture
def gdn_choices(live_registry):
    """``{(impl, reason): count}`` of the rule's choices since the test
    began (the registry is the process's: ``conftest.live_registry``)."""
    before = dispatch.choices()

    def since():
        return {k[1:]: n - before.get(k, 0)
                for k, n in dispatch.choices().items()
                if k[0] == "gated_delta" and n > before.get(k, 0)}
    return since


def test_nothing_is_recorded_on_the_cpu(gdn_choices, monkeypatch):
    """No Mosaic, no choice: the ``jax.numpy`` form runs, bit for bit, and the
    counter stays empty (the benchmark's rehearsal counts every ``jnp`` sample
    it does not know as unexplained)."""
    monkeypatch.setattr(kernels, "gated_delta_rule", None)    # never reached
    x = delta_inputs(70, 1, 2, seed=2)
    for a, b in zip(chunk_gated_delta_rule(*x),
                    chunk_gated_delta_rule_jnp(*x)):
        np.testing.assert_array_equal(a, b)
    assert gdn_choices() == {}


@pytest.mark.parametrize("why,kw,chunk", [
    (None, {}, 64),
    (None, dict(dtype=jnp.float32, H=32, T=100), 64),
    ("head_dim_not_128_aligned", dict(dk=16, dv=8), 64),
    ("head_dim_not_128_aligned", dict(dv=64), 64),
    ("chunk!=64", {}, 16),
    ("dtype:float16", dict(dtype=jnp.float16), 64),
])
def test_rule_reads_its_operands_as_on_tpu(gdn_choices, monkeypatch, why,
                                           kw, chunk):
    """With the platform patched to ``tpu``: the kernels where both head
    sizes are multiples of 128, the chunk is 64 and the type bf16 or f32 (any
    T, B, H), else the ``jax.numpy`` form with its reason; one sample a
    call."""
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    taken = []
    monkeypatch.setattr(kernels, "gated_delta_rule",
                        lambda *a: taken.append(a) or
                        chunk_gated_delta_rule_jnp(*a))
    x = rule_inputs(**kw)
    jax.eval_shape(lambda *a: chunk_gated_delta_rule(*a, chunk=chunk), *x)
    if why is None:
        assert len(taken) == 1 and gdn_choices() == {("pallas", ""): 1}
    else:
        assert not taken and gdn_choices() == {("jnp", why): 1}


# -- the rule from the convolution's output (PR 69) ----------------------------

def mixed_inputs(T, key_heads, rep, dtype, B=1, seed=0):
    """``mixed [B, T, 2 key_dim + value_dim]`` as a convolution might leave
    it (``q~ | k~ | v``, no row of unit length), ``g`` and ``beta`` a value
    head."""
    r = np.random.default_rng(seed)
    H = key_heads * rep
    mixed = r.normal(size=(B, T, (2 * key_heads + H) * D)) * 0.7
    g = -np.exp(r.normal(size=(B, T, H))) * 0.3
    beta = 1 / (1 + np.exp(-r.normal(size=(B, T, H))))
    return (jnp.asarray(mixed, dtype), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32))


def from_mixed(rule, mixed, g, beta, *, key_heads, rep):
    """The layer's ``jax.numpy`` prologue (``layers/gated_delta_net.py
    _scan``) around ``rule``: q and k L2-normalised over a head in f32, q
    scaled, one copy a value head, rounded to the compute type."""
    B, T, _ = mixed.shape
    kd = key_heads * D

    def unit(t):
        t = t.reshape(B, T, key_heads, D).astype(jnp.float32)
        t = t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
        return jnp.repeat(t, rep, axis=2)
    q = (unit(mixed[..., :kd]) * D ** -0.5).astype(mixed.dtype)
    k = unit(mixed[..., kd:2 * kd]).astype(mixed.dtype)
    v = mixed[..., 2 * kd:].reshape(B, T, key_heads * rep, D)
    return rule(q, k, v, g, beta)[0].reshape(B, T, -1)


@pytest.mark.parametrize("T,B,key_heads,rep,dtype", [
    (100, 1, 2, 2, "float32"), (600, 1, 2, 2, "bfloat16"),
    (64, 2, 2, 1, "bfloat16"), (130, 1, 1, 2, "float32"),
    (70, 1, 2, 4, "bfloat16"), (128, 1, 4, 1, "float32")])
def test_in_place_is_the_prologue_around_the_chunked_form(T, B, key_heads,
                                                          rep, dtype):
    """``gated_delta_rule_in_place`` on ``mixed`` against today's prologue
    around ``chunk_gated_delta_rule_jnp``: the output and the gradients of
    ``mixed`` (all three windows), g and beta; one and two value heads a key
    head and four (one key head a program), one and two programs of heads,
    lengths that are no multiple of a program's 512 rows or of a chunk; f32 at
    the tolerance the plain entry is held to; bf16 within a step or two of
    the largest (the two round q and k alike but for the order of a 128-lane
    sum, and a flipped rounding of q or k is a bf16 step in dg and dbeta) and
    no farther than 1.5 times the prologue's form from that form on the operands in
    f32 (``dq~`` and ``dk~`` are nearer: a key head's value heads are summed
    before they are rounded)."""
    x = mixed_inputs(T, key_heads, rep, jnp.dtype(dtype), B, seed=T)
    dims = dict(key_heads=key_heads, rep=rep)
    want_fn = lambda *a: from_mixed(chunk_gated_delta_rule_jnp, *a, **dims)
    got_fn = lambda *a: kernels.gated_delta_rule_in_place(
        *a, dk=D, dv=D, rep=rep)

    def both(fn, x):
        def loss(*a):
            o = fn(*a)
            return jnp.sum(jnp.sin(o.astype(jnp.float32))), o
        (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True)(*x)
        return (o,) + grads
    form = jax.jit(both, static_argnums=0)
    got, want = both(got_fn, x), form(want_fn, x)
    f32 = dtype == "float32"
    exact = want if f32 else form(
        want_fn, (x[0].astype(jnp.float32),) + x[1:])
    kd = key_heads * D
    for name, a, b, c in zip(("o", "dmixed", "dg", "dbeta"), got, want,
                             exact):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b, c = (np.asarray(t, np.float32) for t in (a, b, c))
        windows = ((slice(0, kd), slice(kd, 2 * kd), slice(2 * kd, None))
                   if name == "dmixed" else (slice(None),))
        for w in windows:
            a_, b_, c_ = (t[..., w] for t in (a, b, c))
            top = np.abs(c_).max()
            assert top > 0
            gap = lambda s, t: np.abs(s - t).max() / top
            assert gap(a_, b_) <= (2e-5 if f32 else 1.5e-2), (name, w)
            assert gap(a_, c_) <= 1.5 * gap(b_, c_) + 1e-4, (name, w)


@pytest.mark.parametrize("why,dims", [
    (None, dict(key_heads=16, rep=2)),
    (None, dict(key_heads=2, rep=1)),
    (None, dict(key_heads=2, rep=4)),
    ("key_head_split_across_programs", dict(key_heads=1, rep=3)),
    ("key_head_split_across_programs", dict(key_heads=2, rep=8)),
    ("value_window_not_block_aligned", dict(key_heads=1, rep=4)),
    ("head_dim_not_128_aligned", dict(key_heads=2, rep=2, dk=64)),
    ("dtype:float16", dict(key_heads=2, rep=2, dtype=jnp.float16)),
])
def test_scan_reads_mixed_in_place_where_a_program_holds_whole_key_heads(
        gdn_choices, monkeypatch, why, dims):
    """``_scan`` with the platform patched to ``tpu``: the in-place entry
    where the kernels take the heads, a program's value heads are whole key
    heads' and v's window starts at a block, counted ``pallas`` once; a
    refusal of its own is counted with its reason and the prologue runs
    around the plain kernels (``pallas`` once more); where the kernels do not
    take the heads the plain entry counts why, as it did."""
    from hetu_tpu.layers.gated_delta_net import _scan
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    dims = dict(dict(dk=D, dv=D), **dims)
    dtype = dims.pop("dtype", jnp.bfloat16)
    H = dims["key_heads"] * dims["rep"]
    taken = []
    monkeypatch.setattr(
        kernels, "gated_delta_rule_in_place",
        lambda mixed, g, beta, **kw: taken.append("in_place") or
        jnp.zeros(mixed.shape[:2] + (H * kw["dv"],), mixed.dtype))
    monkeypatch.setattr(kernels, "gated_delta_rule",
                        lambda *a: taken.append("plain") or
                        chunk_gated_delta_rule_jnp(*a))
    sds = jax.ShapeDtypeStruct
    o = jax.eval_shape(
        lambda *a: _scan(*a, **dims),
        sds((1, 64, 2 * dims["key_heads"] * dims["dk"] + H * dims["dv"]),
            dtype),
        sds((1, 64, 2 * H), dtype), sds((H,), jnp.float32),
        sds((H,), jnp.float32))
    assert o.shape == (1, 64, H * dims["dv"]) and o.dtype == dtype
    if why is None:
        assert taken == ["in_place"]
        assert gdn_choices() == {("pallas", ""): 1}
    elif "128" in why or "dtype" in why:
        assert taken == [] and gdn_choices() == {("jnp", why): 1}
    else:
        assert taken == ["plain"]
        assert gdn_choices() == {("jnp", why): 1, ("pallas", ""): 1}


def test_scan_through_the_in_place_kernels_is_the_scan(monkeypatch):
    """The scan node's function as it runs on a TPU (interpret mode: Mosaic
    read as there, the platform the CPU's) against itself around the
    ``jax.numpy`` form, f32, two value heads a key head over 100 positions:
    the output and the gradient of ``mixed``, ``ba``, ``A_log`` and
    ``dt_bias``."""
    from hetu_tpu.layers.gated_delta_net import _scan
    dims = dict(key_heads=2, dk=D, dv=D, rep=2)
    r = np.random.default_rng(4)
    x = (jnp.asarray(r.normal(size=(1, 100, 8 * D)), jnp.float32),
         jnp.asarray(r.normal(size=(1, 100, 8)), jnp.float32),
         jnp.asarray(r.normal(size=(4,)), jnp.float32),
         jnp.asarray(r.normal(1.0, 0.1, size=(4,)), jnp.float32))

    def both(rule):
        def loss(*a):
            o = _scan(*a, rule=rule, **dims)
            return jnp.sum(jnp.sin(o)), o
        (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                           has_aux=True)(*x)
        return (o,) + grads
    want = both(chunk_gated_delta_rule_jnp)
    monkeypatch.setattr(dispatch, "mosaic", lambda: True)
    seen = []
    real = kernels.gated_delta_rule_in_place
    monkeypatch.setattr(kernels, "gated_delta_rule_in_place",
                        lambda *a, **kw: seen.append(kw) or real(*a, **kw))
    got = both(None)
    assert seen == [dict(dk=D, dv=D, rep=2)]
    for a, b in zip(got, want):
        assert a.shape == b.shape and np.abs(b).max() > 0
        assert np.abs(a - b).max() < 2e-5 * np.abs(b).max()


# -- the layer through the kernels ---------------------------------------------

def layer_loss_and_grads(through_kernels, monkeypatch):
    """Loss and every weight's gradient of one ``GatedDeltaNet`` at the
    published head size (2 key heads, 4 value heads of 128, 100 positions),
    through the executor."""
    import hetu_tpu as ht
    from hetu_tpu.graph.node import graph_variables
    from hetu_tpu.layers.gated_delta_net import GatedDeltaNet
    if through_kernels:
        monkeypatch.setattr(gated_delta, "chunk_gated_delta_rule",
                            kernels.gated_delta_rule)
    name = f"gdk_layer_{int(through_kernels)}"
    layer = GatedDeltaNet(256, 2, 4, D, D, name=name)
    x = ht.placeholder_op(f"{name}_x", (2, 100, 256))
    loss = ht.reduce_sum_op(ht.sin_op(layer(x)), axes=[0, 1, 2])
    variables = graph_variables([loss], trainable_only=True)
    assert len(variables) == 7
    ex = ht.Executor({"grads": [loss] + ht.gradients(loss, variables)},
                     seed=3)
    r = np.random.default_rng(5)
    for var in variables:           # the same weights for both, off their
        value = ex.params[var.name]     # initial ones and zeros
        ex.params[var.name] = jnp.asarray(
            r.normal(0.2 if var.shape == (4,) else 0.0, 0.1, var.shape),
            value.dtype)
    feed = {x: r.normal(size=(2, 100, 256)).astype(np.float32)}
    out = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    return out[0], out[1:]


def test_layer_through_the_kernels_is_the_layer(monkeypatch):
    """Loss and the gradient of all seven weights, f32."""
    l1, g1 = layer_loss_and_grads(False, monkeypatch)
    l2, g2 = layer_loss_and_grads(True, monkeypatch)
    assert abs(float(l2 - l1)) < 1e-5 * abs(float(l1))
    for a, b in zip(g2, g1):
        assert a.shape == b.shape and np.abs(b).max() > 0
        assert np.abs(a - b).max() < 2e-5 * np.abs(b).max()


# -- the layer's step compiled for the chip -------------------------------------
# Interpret mode cannot see what Mosaic refuses.  libtpu is installed, so the
# layer's step compiles here for a described, not attached, v5e at the cell's
# shapes; nothing runs.  (The flash kernels' compile cases are in
# tests/test_flash_attention.py: one process at a time may load libtpu, and
# the driver's workers are given whole files.)

@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def test_layer_step_compiles_for_v5e(v5e, gdn_choices, monkeypatch):
    """The Qwen3-Next cell's mixer (16 key heads, 32 value heads of 128,
    8,192 positions, bf16), forward and backward from the node's inputs:
    ``hetu_gdn_fwd`` and ``hetu_gdn_bwd`` and nothing else of the rule's: no
    ``triangular_solve`` custom call, no ``while``, and of ``[.., 64, 64]``
    f32 arrays in HBM the chunks' kept inverses alone (PR 66); the kernels
    read and write ``[1, 8192, 4096]`` in place."""
    import re
    from jax.sharding import SingleDeviceSharding
    from hetu_tpu.layers.gated_delta_net import _scan
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    one = SingleDeviceSharding(v5e.devices[0])
    sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)
    dims = dict(key_heads=16, dk=D, dv=D, rep=2)

    def loss(mixed, ba, a_log, dt_bias):
        with jax.named_scope("hetu_gdn_scan"):
            o = _scan(mixed, ba, a_log, dt_bias, **dims)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        sds((1, 8192, 2 * 16 * D + 32 * D), jnp.bfloat16),
        sds((1, 8192, 64), jnp.bfloat16), sds((32,), jnp.float32),
        sds((32,), jnp.float32)).compile().as_text()
    assert gdn_choices() == {("pallas", ""): 1}
    kernels_ = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(kernels_) == 2
    assert "hetu_gdn_fwd" in kernels_[0] and "hetu_gdn_bwd" in kernels_[1]
    assert all("bf16[1,8192,4096]" in ln for ln in kernels_)
    assert "triangular_solve" not in hlo.lower()
    assert not re.findall(r"\bwhile\(", hlo)
    assert set(re.findall(r" = f32\[([\d,]*64,64)\]\S* ", hlo)) == {KEPT}


def test_kda_layer_step_compiles_for_v5e(v5e, monkeypatch):
    """The Ling-3.0 cell's KDA mixer (32 heads of 128, 8,192 positions, bf16,
    a decay a channel in f32), forward and backward from the scan node's
    inputs: ``hetu_kda_fwd`` and ``hetu_kda_bwd`` and nothing else of the
    rule's: no ``triangular_solve``, no ``while``, of ``[.., 64, 64]`` f32
    arrays in HBM the chunks' kept inverses alone; the kernels write ``[1,
    8192, 4096]`` as the output product
    reads it (``hetu_gdn_*``'s helpers inside: the Qwen3-Next case above is
    what holds those to what they were; what the in-place entry reads and
    leaves out of HBM is held in ``tests/test_flash_attention.py``)."""
    import re
    from jax.sharding import SingleDeviceSharding
    from hetu_tpu import telemetry
    from hetu_tpu.layers.kda import _scan
    telemetry.enable()
    try:
        before = {k: n for k, n in dispatch.choices().items()
                  if k[0] == "kda"}
        monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
        one = SingleDeviceSharding(v5e.devices[0])
        sds = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)
        H = 32

        def loss(proj, mixed, beta, a_log, dt_bias, w_norm):
            with jax.named_scope("hetu_kda_scan"):
                o = _scan(proj, mixed, beta, a_log, dt_bias, w_norm, heads=H,
                          d=D, lower_bound=-5.0, eps=1e-6)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))).lower(
            sds((1, 8192, 5 * H * D), jnp.bfloat16),
            sds((1, 8192, 3 * H * D), jnp.bfloat16),
            sds((1, 8192, H), jnp.bfloat16), sds((H,), jnp.float32),
            sds((H * D,), jnp.float32),
            sds((D,), jnp.float32)).compile().as_text()
        after = {k: n for k, n in dispatch.choices().items()
                 if k[0] == "kda"}
    finally:
        telemetry.disable()
    assert after.get(("kda", "pallas", ""), 0) == before.get(
        ("kda", "pallas", ""), 0) + 1
    assert not [k for k in after if k[1] == "jnp" and k not in before]
    kernels_ = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(kernels_) == 2
    assert "hetu_kda_fwd" in kernels_[0] and "hetu_kda_bwd" in kernels_[1]
    assert all("bf16[1,8192,4096]" in ln for ln in kernels_)
    assert "triangular_solve" not in hlo.lower()
    assert not re.findall(r"\bwhile\(", hlo)
    assert set(re.findall(r" = f32\[([\d,]*64,64)\]\S* ", hlo)) == {KEPT}
