"""The Ouro looped decoder through ``OuroForCausalLM`` against the plain
reference (``chipbench/reference/ouro.py``) at a small size on the CPU:
seeded weights with every norm weight and the gate's bias moved off its
initial value, f32 compute, ``k = 2`` layers walked ``P`` times, 48 positions.

Tolerances.  Program and reference both compute in f32 here, in different
orders (attention in one piece against blocks of query rows, the exit
distribution through ``log sigmoid`` against a running product), so they
differ by rounding alone: logits of size ~1 within 2e-4, the exit
distribution 1e-5, the loss and its terms 1e-5 relative, gradients 5e-4 of
the leaf's largest entry.  The negative controls show how far that is from
getting the architecture wrong: a pass left out, the norms behind the
sublayers, the final norm not fed back, the last pass not taking the rest,
the entropy term and the gate's gradient path each move a term, or a
gradient, by many times its limit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.graph.node import graph_variables
from hetu_tpu.models import (OuroConfig, OuroForCausalLM, LlamaConfig,
                             LlamaForCausalLM, record_exit_shares)
from hetu_tpu.models.llama import residual_sublayer

from chipbench.builders.common import counter
from chipbench.builders.ouro import reference_params
from chipbench.reference import ouro as ref

B, S, K, BETA = 2, 48, 2, 0.05
LOGIT_TOL = 2e-4
REF_CONFIG = dict(vocab_size=256, hidden_size=64, num_hidden_layers=K,
                  num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                  intermediate_size=128, rope_theta=1e6, rms_norm_eps=1e-6)

TOKENS = np.random.default_rng(0).integers(0, 256, (B, S + 1))
IDS, LABELS = TOKENS[:, :-1], TOKENS[:, 1:].copy()
LABELS[0, :5] = -1          # a few positions without a label


def config(passes):
    return dict(REF_CONFIG, total_ut_steps=passes)


def build(passes, remat):
    ids = ht.placeholder_op("ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op("labels", (B, S), dtype=np.int32)
    model = OuroForCausalLM(OuroConfig(
        vocab_size=256, hidden_size=64, num_layers=K, num_heads=4,
        intermediate_size=128, seq_len=S, total_ut_steps=passes,
        exit_entropy_coeff=BETA, remat=remat))
    loss, terms = model.loss_terms(ids, labels)
    variables = graph_variables([loss], trainable_only=True)
    ex = ht.Executor(
        {"forward": [loss, terms["ce"], terms["entropy"], model.exit_p,
                     model.exit_shares] + model.pass_logits,
         "grads": [loss] + ht.gradients(loss, variables)}, seed=3)
    # off the initial values: a norm weight of exactly 1 would hide a dropped
    # norm, a bias of exactly 0 a dropped bias; a head of 0.02 gives flat
    # logits and a Xavier gate on normed states a timid one
    r = np.random.default_rng(7)
    for name, value in list(ex.params.items()):
        if name.endswith("_scale") or name.endswith("exit_gate_bias"):
            ex.params[name] = value + jnp.asarray(
                r.normal(0, 0.2, value.shape), value.dtype)
        if name.endswith(("lm_head_weight", "_embed_table")):
            ex.params[name] = value * 8.0
    return model, ex, variables, {ids: IDS, labels: LABELS}


def run(passes, remat):
    model, ex, variables, feed = build(passes, remat)
    out = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    grads = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    params = {k: np.asarray(v) for k, v in reference_params(
        model, ex.params).items()}
    names = {v: k for k, v in reference_params(
        model, {n: n for n in ex.params}).items()}
    ex.close()
    return dict(out=out, params=params, names=names,
                grads=dict(zip((v.name for v in variables), grads[1:])))


def reference(params, steps, **how):
    return jax.jit(lambda p: ref.loss_parts(
        p, config(steps), IDS, LABELS, BETA, **how))(params)


def reference_grads(params, steps, **how):
    return jax.jit(jax.grad(lambda p: ref.pretraining_loss(
        p, config(steps), IDS, LABELS, BETA, **how)))(params)


@pytest.fixture(scope="module")
def four():
    return run(4, True)


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("passes", [1, 2, 4])
def test_loss_terms_distribution_logits_and_every_gradient(passes, remat):
    got = run(passes, remat)
    want = reference(got["params"], passes)
    loss, ce, entropy, p, shares, *logits = got["out"]
    for mine, term in ((loss, "loss"), (ce, "ce"), (entropy, "entropy")):
        theirs = float(want[term])
        assert abs(float(mine) - theirs) < 1e-5 * max(abs(theirs), 1.0), term
    assert p.shape == (passes, B * S)
    assert np.abs(p - np.asarray(want["p"])).max() < 1e-5
    assert np.abs(p.sum(0) - 1.0).max() < 1e-5
    assert np.abs(shares - np.asarray(want["shares"])).max() < 1e-5
    assert len(logits) == passes
    for mine, theirs in zip(logits, want["logits"]):
        assert np.abs(theirs).max() > 0.5
        assert np.abs(mine - np.asarray(theirs)).max() < LOGIT_TOL
    grads = reference_grads(got["params"], passes)
    assert len(got["grads"]) == len(got["params"]) == (
        len(ref.WEIGHTS) + K * len(ref.LAYER_WEIGHTS))
    for name, g in got["grads"].items():
        w = np.asarray(grads[got["names"][name]])
        if passes == 1 and "exit_gate" in name:
            assert not np.abs(w).any() and not np.abs(g).any(), name
            continue            # one pass takes all the mass: no gate to learn
        assert np.abs(w).max() > 0, name
        assert np.abs(g - w).max() < 5e-4 * np.abs(w).max() + 1e-9, name


def test_the_pieces_walked_by_hand_are_loss_terms():
    """``OuroModel.walk``, ``exit_terms`` and ``exit_loss`` driven a pass at
    a time from outside, each pass reading the state the run before fetched,
    give what ``loss_terms`` gives in one graph: loss, terms, distribution
    and every pass's logits."""
    model, ex, _, feed = build(3, True)
    whole = ex.run("forward", feed_dict=feed, convert_to_numpy_ret_vals=True)
    ids, labels = feed
    state = ht.placeholder_op("state", (B, S, 64))
    zs = [ht.placeholder_op(f"z{t}", (B * S,)) for t in range(3)]
    ces = [ht.placeholder_op(f"ce{t}", (B * S,)) for t in range(3)]
    flat = ht.array_reshape_op(labels, output_shape=(-1,))
    h = model.model.walk(state)
    logits, ce, z = model.exit_terms(h, flat)
    loss, terms = model.exit_loss(zs, ces, flat)
    by_hand = ht.Executor({"embed": [model.model._embed(ids)],
                           "pass": [h, z, ce, logits],
                           "exit": [loss, terms["ce"], terms["entropy"],
                                    model.exit_p]}, seed=3)
    by_hand.params.update(ex.params)
    x, = by_hand.run("embed", feed_dict={ids: feed[ids]})
    got = {"z": [], "ce": [], "logits": []}
    for _ in range(3):
        x, *rest = by_hand.run("pass", feed_dict={state: x,
                                                  labels: feed[labels]})
        for k, v in zip(("z", "ce", "logits"), rest):
            got[k].append(np.asarray(v))
    out = by_hand.run("exit", feed_dict={
        labels: feed[labels], **dict(zip(zs, got["z"])),
        **dict(zip(ces, got["ce"]))}, convert_to_numpy_ret_vals=True)
    for mine, theirs in zip(out, whole[:4]):
        assert np.abs(mine - theirs).max() < 1e-6
    for mine, theirs in zip(got["logits"], whole[5:]):
        assert np.abs(mine - theirs).max() < 1e-5
    ex.close()
    by_hand.close()


def test_the_tied_gradient_is_the_sum_over_untied_copies(four):
    """The reference with ``P x k`` distinct layers initialised alike gives
    the gradient of each use apart: the program's gradient of a layer's
    matrix is their sum over the passes, and no one of them alone."""
    params = dict(four["params"])
    for t in range(4):
        for i in range(K):
            for w in ref.LAYER_WEIGHTS:
                params[f"layers.{t * K + i}.{w}"] = four["params"][
                    f"layers.{i}.{w}"]
    g = reference_grads(params, 4, layer_of=lambda t, i: t * K + i)
    for name, mine in four["grads"].items():
        where = four["names"][name]
        if not where.startswith("layers."):
            continue
        _, i, w = where.split(".")
        parts = [np.asarray(g[f"layers.{t * K + int(i)}.{w}"])
                 for t in range(4)]
        total = sum(parts)
        assert np.abs(mine - total).max() < 5e-4 * np.abs(total).max(), name
        for part in parts:
            assert np.abs(mine - part).max() > 0.05 * np.abs(total).max(), (
                name)


@pytest.mark.parametrize("how, term, least", [
    (dict(passes=3), "ce", 1e-3),
    (dict(leave_out=("post_norms",)), "ce", 1e-2),
    (dict(leave_out=("fed_norm",)), "ce", 1e-3),
    (dict(leave_out=("last_takes_rest",)), "ce", 1e-2),
    (dict(leave_out=("entropy",)), "loss", 1e-2),
])
def test_a_piece_left_out_of_the_reference_shows(four, how, term, least):
    """Each piece of the mathematics moves a term by far more than the 1e-5
    the program is held to."""
    whole = reference(four["params"], 4)
    less = reference(four["params"], 4, **how)
    assert abs(float(whole[term]) - float(less[term])) > least, how


def test_the_gates_gradient_flows_through_the_weights(four):
    """Nothing is detached: the gate learns through ``p_t``.  With ``p``
    held constant the gate's gradient would be zero and the layers' another;
    the program's is the reference's whole gradient, and far from that."""
    def detached(p):
        parts = ref.loss_parts(p, config(4), IDS, LABELS, BETA)
        flat = jnp.asarray(LABELS).reshape(-1)
        ces = jnp.stack([ref.head(h, p, flat)[1] for h in (
            s.reshape(-1, 64) for s in ref.forward(p, config(4), IDS))])
        fixed = jax.lax.stop_gradient(parts["p"])
        return ref.finish(fixed, ces, flat, BETA)["loss"]
    cut = jax.jit(jax.grad(detached))(four["params"])
    assert not np.abs(np.asarray(cut["gate_w"])).any()
    name = next(n for n, where in four["names"].items()
                if where == "gate_w")
    assert np.abs(four["grads"][name]).max() > 1e-4
    name = next(n for n, where in four["names"].items()
                if where == "layers.0.o")
    mine = four["grads"][name]
    assert np.abs(mine - np.asarray(cut["layers.0.o"])).max() > (
        0.01 * np.abs(mine).max())


def test_residual_sublayer_without_a_post_norm_builds_what_it_built():
    """``post_norm=None`` (every other family's call): the node sequence of
    a Llama decoder's loss is what it was, norm, sublayer, sum, with the sum
    outside the recomputed group; with a post norm one ``rms_norm`` more and
    the sum inside."""
    from hetu_tpu.graph.node import find_topo_sort
    from hetu_tpu.layers import RMSNorm
    from hetu_tpu.models.llama import LlamaMLP

    def kinds(post, recompute):
        x = ht.placeholder_op("x", (2, 8, 16))
        norm, mlp = RMSNorm(16, name="n"), LlamaMLP(16, 32, name="m")
        y = residual_sublayer(x, norm, mlp, recompute=recompute,
                              post_norm=RMSNorm(16, name="p") if post
                              else None)
        nodes = [n for n in find_topo_sort([y])
                 if not type(n).__name__.endswith(("PlaceholderOp",
                                                   "VariableOp"))]
        return ([(type(n).__name__, n.attrs.get("op_name", n.name.split(
            "_")[0]), n.scope) for n in nodes],
                [n.remat_scope is not None for n in nodes])
    plain, inside = kinds(False, True)
    assert [s for *_, s in plain] == (["hetu_norm"] + ["hetu_mlp"] * 5
                                      + ["hetu_norm"])
    assert inside == [True] * 6 + [False]
    sandwich, inside = kinds(True, True)
    assert len(sandwich) == len(plain) + 1 and all(inside)
    assert [s for *_, s in sandwich][-2:] == ["hetu_norm"] * 2
    assert kinds(False, False)[1] == [False] * 7
    # a Llama decoder's train graph names no block it did not name before
    model = LlamaForCausalLM(LlamaConfig(
        vocab_size=64, hidden_size=16, num_layers=1, num_heads=2,
        intermediate_size=32, seq_len=8))
    ids = ht.placeholder_op("ids", (1, 8), dtype=np.int32)
    topo = find_topo_sort([model.loss(ids, ids)])
    assert "hetu_exit" not in {n.scope for n in topo}


def test_the_counter_the_gauge_and_the_scope():
    """``hetu_loop_layer_calls_total{pass}`` counts ``k`` applications a
    pass while the graph is built, ``record_exit_shares`` sets
    ``hetu_loop_exit_share{pass}``, and the exit block is a scope of its
    own that the graph's nodes carry."""
    from hetu_tpu.graph.node import find_topo_sort
    telemetry.enable()
    try:
        def calls():
            return [counter("hetu_loop_layer_calls_total",
                            **{"pass": str(t)}) for t in range(3)]
        before = calls()
        model, ex, _, _ = build(3, True)
        assert [a - b for a, b in zip(calls(), before)] == [K] * 3
        assert model.attention_layers == 3 * K
        record_exit_shares([0.5, 0.3, 0.2])
        assert [counter("hetu_loop_exit_share", **{"pass": str(t)})
                for t in range(3)] == [0.5, 0.3, 0.2]
        assert "hetu_exit" in ht.scopes()
        topo = find_topo_sort(ex.subexecutor["forward"]._all_eval)
        under = {type(n).__name__ for n in topo if n.scope == "hetu_exit"}
        assert under == {"ExitGateOp", "ExitDistributionOp",
                         "ExitExpectationOp", "ExitEntropyOp", "MoELoadOp"}
        ex.close()
    finally:
        telemetry.shutdown()


def test_a_pipeline_of_the_looped_stack_is_refused():
    with pytest.raises(NotImplementedError, match="go round"):
        OuroForCausalLM(OuroConfig(vocab_size=64, hidden_size=16,
                                   num_layers=2, num_heads=2,
                                   intermediate_size=32, seq_len=8),
                        pipeline_stages=2)


def test_a_variable_read_by_several_recomputed_groups_is_linked():
    """``graph/trace.py``: a variable that enters several ``ht.remat()``
    groups reads through ``_grad_link``, whose cotangent passes a barrier, so
    that XLA keeps ONE running sum of its gradient and not a product a group
    to the end of the backward pass; a variable of one group does not."""
    from hetu_tpu.graph import trace

    def barriers(passes):
        _, ex, _, _ = build(passes, True)
        sub = ex.subexecutor["grads"]
        if sub._jitted is None:
            sub._build()
        text = sub._jitted.trace(*sub._abstract_args(None)).lower().as_text()
        ex.close()
        return text.count("optimization_barrier")
    # jax.checkpoint brings barriers of its own: the links add to them
    one, two = barriers(1), barriers(2)
    assert two > 2 * one
    x = jnp.arange(4.0)
    y, vjp = jax.vjp(trace._grad_link, x)
    assert (y == x).all() and (vjp(x)[0] == x).all()
