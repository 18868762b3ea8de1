"""What an ``ht.remat()`` group keeps beside its arguments (PR 55): the flash
kernel's context and log-sum-exp, named INSIDE the kernel's forward rule
(``ops/pallas/dispatch.py KEPT``, ``named``) and saved by the group's policy
(``graph/trace.py``), so the backward pass of a recomputed layer runs the
forward kernel no second time; since PR 69 the gated delta rule's output,
chunk-start states and chunks' inverses as well (``"gdn"``), so a recomputed
DeltaNet mixer runs ``hetu_gdn_fwd`` once.

Lowered for a TPU (nothing compiled or run) the cells' toys at heads of the
kernels' width run as many flash forward calls as layer applications; a group
that holds no flash call lowers to the text it had with no policy; the
gradients are the un-recomputed layer's to the bit; and the registry says how
often the rule engaged: ``hetu_remat_kept_total{kernel}`` once a kernel call
a differentiated group holds, ``hetu_remat_kept_bytes`` the traced step's sum,
nothing for the ``jax.numpy`` form or a program with no backward pass.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.ops.pallas import dispatch, flash_attention as fa

from chipbench import run
from conftest import kernel_calls, lowered_for_tpu, without_locations
from test_rotary_kernel import layer_grads

OURO, LING, LAGUNA, QWEN = (
    "ouro-2.6b.b1-s8192", "ling-3.0-flash-vl.b1-s8192",
    "laguna-xs.2.b1-s8192", "qwen3-next-80b-a3b.b1-s8192")
D, S = 128, 256


@pytest.fixture
def kept(live_registry):
    """``({kernel: calls}, bytes of the last traced step)`` since the test
    began."""
    before = dict((lab["kernel"], n) for lab, n
                  in dispatch.counted("hetu_remat_kept_total"))
    dispatch.record_kept([])

    def since():
        calls = {lab["kernel"]: n - before.get(lab["kernel"], 0)
                 for lab, n in dispatch.counted("hetu_remat_kept_total")}
        return ({k: n for k, n in calls.items() if n},
                sum(n for _, n in dispatch.counted("hetu_remat_kept_bytes")))
    return since


def toy(cell, seq=S, **widths):
    """The cell's builder at its toy size with heads of 128 over ``seq``
    positions (the flash kernels' envelope), ``widths`` over it."""
    import importlib
    _, _, config, mix = run.load_cell(cell)
    config = run.merge(run.merge(config, config["toy"]),
                       dict({"head_dim": D}, **widths))
    mix = run.merge(run.merge(mix, mix["toy"]), {"seq": seq})
    builder = importlib.import_module(
        "chipbench.builders." + config["builder"])
    return builder.build(config, mix, 3, lambda msg: None)


def ouro_toy():
    # two layers walked four times, whole layers recomputed as in the cell
    return toy(OURO, hidden_size=2 * D, num_attention_heads=2,
               num_key_value_heads=2)


def ling_toy():
    # K K K K K A K behind one dense layer, whole layers recomputed
    return toy(LING, num_hidden_layers=7, layer_group_size=6,
               job={"remat": "layer"})


def laguna_toy():
    # two full and three window layers, whole layers recomputed
    return toy(LAGUNA, sliding_window=128, job={"remat": "layer"})


def qwen_toy():
    # the published period, D D D A, the DeltaNet mixers recomputed as in the
    # cell: two key heads, four value heads of 128 (the attention layer is
    # in no group)
    return toy(QWEN, num_hidden_layers=4, full_attention_interval=4,
               linear_key_head_dim=D, linear_value_head_dim=D)


def delta_words(batch, heads, seq, itemsize, dk=D, dv=D):
    """Bytes of a delta rule call's output and, f32 a chunk of 64 and head,
    its chunk-start state and the chunk's inverse."""
    return (batch * seq * heads * dv * itemsize
            + 4 * batch * heads * seq // 64 * (dk * dv + 64 * 64))


def words(batch, heads, seq, width, itemsize):
    """Bytes of a flash call's context and f32 log-sum-exp."""
    return batch * seq * heads * width * itemsize + batch * heads * seq * 4


@pytest.mark.parametrize("build,calls,nbytes", [
    (ouro_toy, {"hetu_flash": 2 * 4}, 8 * words(1, 2, S, D, 4)),
    (ling_toy, {"hetu_flash": 1}, None),
    (laguna_toy, {"hetu_flash": 2, "hetu_swa": 3}, None),
    (qwen_toy, {"hetu_gdn": 3}, 3 * delta_words(1, 4, S, 4)),
])
def test_a_toy_step_runs_one_forward_kernel_a_layer_application(
        monkeypatch, kept, build, calls, nbytes):
    """Every attention layer (Qwen3-Next: every DeltaNet mixer) in a
    recomputed group: as many forward calls as layer applications (the
    parent ran twice as many), as many backward calls, and the registry
    counted each once."""
    text = lowered_for_tpu(monkeypatch, build)
    for kernel, n in calls.items():
        assert kernel_calls(text, kernel + "_fwd") == n, kernel
        assert kernel_calls(text, kernel + "_bwd") == n, kernel
    counted, total = kept()
    assert counted == {k[len("hetu_"):]: n for k, n in calls.items()}
    assert total == nbytes if nbytes is not None else total > 0


def attention_layer(name, remat, dtype=None, seq=S):
    """Two heads of 128 under ``ht.remat()`` (or not): the executor of the
    loss and of every weight's gradient, the feed, and the layer's output."""
    return layer_grads(name, False, None, remat=remat, dtype=dtype, S=seq)


def traced(ex, key="grads"):
    sub = ex.subexecutor[key]
    if sub._jitted is None:
        sub._build()
    return sub._jitted.trace(*sub._abstract_args(None))


def test_a_group_saves_its_arguments_and_the_two_names_a_flash_call(
        monkeypatch):
    """``saved_residuals`` of the group as ``evaluate`` hands it to
    ``jax.checkpoint``: the group's arguments, the context ``[B, S, H d]`` of
    the compute type and the log-sum-exp ``[B, H, 1, S]`` f32, nothing
    else."""
    from jax._src.ad_checkpoint import saved_residuals
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    jax.clear_caches()
    groups, real = [], jax.checkpoint

    def spy(f, **kw):
        g = real(f, **kw)

        def call(*args):
            groups.append(saved_residuals(g, *(
                jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args)))
            return g(*args)
        return call
    monkeypatch.setattr(jax, "checkpoint", spy)
    try:
        ex, _, _ = attention_layer("rkept_saved", True, jnp.bfloat16)
        traced(ex)
        ex.close()
    finally:
        jax.clear_caches()
    group, = groups
    args = [aval for aval, what in group if "from the argument" in what]
    rest = sorted((aval.shape, str(aval.dtype)) for aval, what in group
                  if "from the argument" not in what)
    # x, the four weights and the rotary tables
    assert len(args) == 6
    assert rest == [((2, 2, 1, S), "float32"), ((2, S, 2 * D), "bfloat16")]


def test_a_group_with_no_flash_call_lowers_to_what_it_lowered_to(
        monkeypatch):
    """A recomputed MLP and a recomputed attention layer on the ``jax.numpy``
    form hold no name: with the policy and with none the program is the same
    text."""
    def mlp(name):
        x = ht.placeholder_op(f"{name}_x", (4, 32))
        w = ht.Variable(f"{name}_w", shape=(32, 32),
                        initializer=ht.init.normal(0.0, 0.1))
        with ht.remat():
            y = ht.tanh_op(ht.matmul_op(ht.tanh_op(ht.matmul_op(x, w)), w))
        loss = ht.reduce_sum_op(y, axes=[0, 1])
        return ht.Executor({"grads": [loss] + ht.gradients(loss, [w])})

    def layer(name):
        return attention_layer(name, True, seq=32)[0]
    for n, build in enumerate((mlp, layer)):
        texts = []
        for policy in (dispatch.KEEP_POLICY, None):
            monkeypatch.setattr(dispatch, "KEEP_POLICY", policy)
            ex = build(f"rkept_none{n}")
            # without the results' paths, which hold names the process
            # numbers (a fresh variable's)
            texts.append(re.sub(r'jax\.result_info = "[^"]*"', "",
                                without_locations(
                                    traced(ex).lower().as_text())))
            ex.close()
        assert texts[0] == texts[1] and "optimization_barrier" in texts[0]


def through_the_kernels(monkeypatch):
    """The attention node takes the flash kernels as it does on a TPU, in
    interpret mode (``dispatch.platform`` stays ``cpu``)."""
    monkeypatch.setattr(dispatch, "mosaic", lambda: True)


def test_gradients_of_a_recomputed_layer_are_the_layers_to_the_bit(
        monkeypatch, kept):
    """Through the flash kernels (interpret mode), f32: the backward kernel
    reads the context and the log-sum-exp the forward pass wrote where the
    parent's read a second evaluation of the same kernel; the loss and every
    weight's gradient are the un-recomputed layer's, bit for bit, and the
    group counted its one call."""
    through_the_kernels(monkeypatch)
    outs = []
    for remat in (False, True):
        ex, feed, _ = attention_layer(f"rkept_bits{int(remat)}", remat)
        outs.append(ex.run("grads", feed_dict=feed,
                           convert_to_numpy_ret_vals=True))
        assert kept()[0] == ({"flash": 1} if remat else {})
        ex.close()
    assert kept()[1] == words(2, 2, S, D, 4)
    for plain, recomputed in zip(*outs):
        assert np.abs(plain).max() > 0
        assert (np.asarray(plain) == np.asarray(recomputed)).all()


def test_the_name_inside_the_rule_keeps_the_kernel_off_the_backward_pass(
        monkeypatch):
    """The mechanism on the kernel alone, lowered for a TPU: under the groups'
    policy the differentiated function's program calls the forward kernel
    ONCE, with no policy twice (XLA cannot see through a Pallas call), and a
    name on the OUTPUT of the call, outside the ``custom_vjp``, keeps a copy
    and still runs the kernel again."""
    from jax.ad_checkpoint import checkpoint_name
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    q = jnp.ones((1, S, 2 * D), jnp.bfloat16)

    def attend(x):
        return jnp.sin(fa.flash_attention(x, x, x, causal=True, num_heads=2))

    def outside(x):
        o = fa._flash_call(
            x, x, x, None, jnp.zeros((1,), jnp.int32), True, D ** -0.5, 1.0,
            128, 2, None)
        return jnp.sin(checkpoint_name(o, "rkept_outside"))

    def kernels(f, policy):
        g = jax.checkpoint(lambda x: f(x).sum(), policy=policy)
        jax.clear_caches()
        try:
            return kernel_calls(jax.jit(jax.value_and_grad(g)).trace(q).lower(
                lowering_platforms=("tpu",)).as_text(), "hetu_flash_fwd")
        finally:
            jax.clear_caches()
    names = jax.checkpoint_policies.save_only_these_names
    assert kernels(attend, dispatch.KEEP_POLICY) == 1
    assert kernels(attend, None) == 2
    assert kernels(outside, names("rkept_outside")) == 2


def test_the_jnp_form_counts_nothing(kept):
    """On the CPU the attention node runs its ``jax.numpy`` form: the group
    holds no name, so nothing is counted and the gauge reads 0 for the traced
    step."""
    ex, feed, _ = attention_layer("rkept_jnp", True)
    ex.run("grads", feed_dict=feed)
    ex.close()
    assert kept() == ({}, 0)


def test_a_program_with_no_backward_pass_counts_nothing(monkeypatch, kept):
    """Through the kernels, the group of a bare forward pass keeps nothing
    (no backward pass reads it); the gradients' program counts its call."""
    through_the_kernels(monkeypatch)
    ex, feed, y = attention_layer("rkept_fwd", True)
    forward = ht.Executor({"forward": [y]})
    forward.run("forward", feed_dict=feed)
    forward.close()
    assert kept() == ({}, 0)
    ex.run("grads", feed_dict=feed)
    ex.close()
    assert kept() == ({"flash": 1}, words(2, 2, S, D, 4))


def delta_net_layer(name, remat):
    """A ``GatedDeltaNet`` (two key heads, four value heads of 128) over ``[2,
    100, 64]`` under ``ht.remat()`` (or not): the executor of its loss and of
    every weight's gradient, and the feed."""
    import contextlib
    from hetu_tpu.graph.node import graph_variables
    from hetu_tpu.layers.gated_delta_net import GatedDeltaNet
    layer = GatedDeltaNet(64, 2, 4, D, D, name=name)
    x = ht.placeholder_op(f"{name}_x", (2, 100, 64))
    with ht.remat() if remat else contextlib.nullcontext():
        y = layer(x)
    loss = ht.reduce_sum_op(ht.sin_op(y), axes=[0, 1, 2])
    variables = graph_variables([loss], trainable_only=True)
    ex = ht.Executor({"grads": [loss] + ht.gradients(loss, variables)},
                     seed=3)
    r = np.random.default_rng(5)
    for var in variables:
        ex.params[var.name] = jnp.asarray(
            r.normal(0.2 if var.shape == (4,) else 0.0, 0.1, var.shape),
            ex.params[var.name].dtype)
    return ex, {x: r.normal(size=(2, 100, 64)).astype(np.float32)}


def test_gradients_of_a_recomputed_delta_net_are_the_layers_to_the_bit(
        monkeypatch, kept):
    """Through the rule's kernels (interpret mode, ``mixed`` read in place),
    f32, 100 positions (28 of padding): the backward kernel reads the
    states and the inverses, and the mixer's norm the output, that the
    forward pass wrote where the parent's read a second evaluation of the
    same kernel; the loss and all seven weights' gradients are the
    un-recomputed layer's, bit for bit, and the group counted its one call
    and the bytes its shapes say (the padded 128 rows)."""
    through_the_kernels(monkeypatch)
    outs = []
    for remat in (False, True):
        ex, feed = delta_net_layer(f"rkept_gdn{int(remat)}", remat)
        outs.append(ex.run("grads", feed_dict=feed,
                           convert_to_numpy_ret_vals=True))
        assert kept()[0] == ({"gdn": 1} if remat else {})
        ex.close()
    assert kept()[1] == delta_words(2, 4, 128, 4)
    assert len(outs[0]) == 8
    for plain, recomputed in zip(*outs):
        assert np.abs(plain).max() > 0
        assert (np.asarray(plain) == np.asarray(recomputed)).all()


def test_the_names_live_in_one_place():
    """One table beside the kernels' dispatch: a forward rule names its
    residuals from it, the groups' policy saves exactly its names."""
    assert dispatch.KEPT == {
        "flash": ("attention_context", "attention_lse"),
        "gdn": ("delta_rule_output", "delta_rule_states",
                "delta_rule_inverses")}
    saved = str(jax.make_jaxpr(jax.checkpoint(
        lambda *a: sum(jnp.sin(t).sum() for t in (
            dispatch.named("flash", *a[:2]) + dispatch.named("gdn", *a[2:]))),
        policy=dispatch.KEEP_POLICY))(*(jnp.ones(n) for n in range(2, 7))))
    for names in dispatch.KEPT.values():
        for name in names:
            assert f"name={name}" in saved
    with pytest.raises(ValueError):
        dispatch.named("gdn", jnp.ones(2), jnp.ones(3))
    o, lse = dispatch.named("flash", jnp.ones(2), jnp.ones(3))
    assert o.shape == (2,) and lse.shape == (3,)
    with pytest.raises(ValueError):
        dispatch.named("flash", jnp.ones(2))
    jaxpr = str(jax.make_jaxpr(lambda a, b: dispatch.named("flash", a, b))(
        jnp.ones(2), jnp.ones(3)))
    assert "name=attention_context" in jaxpr and "name=attention_lse" in jaxpr
