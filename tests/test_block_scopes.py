"""``ht.scope``: a construction-time block name on graph nodes that
``evaluate`` opens as ``jax.named_scope`` around each node's computation.

(a) the semantics: the innermost name wins, None outside, the name survives
``ht.remat()`` groups and ``jax.vjp``, ``ScopedOp`` is what it was for its
callers, the gradient nodes carry none.  (b) scopes write metadata only: for
each of the benchmark's builders at toy widths the compiled train step with
``op_name`` and source metadata stripped is text for text the step traced with
every ``named_scope`` a no-op."""

import importlib
import re
from contextlib import nullcontext

import jax
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.graph import node as graph_node
from hetu_tpu.graph.autodiff import GradientSliceOp, GradientsBundleOp
from hetu_tpu.ops.base import ScopedOp
from hetu_tpu.optim import optimizer

#: every Mosaic kernel's name: they stand in ``op_name`` too
#: (``pallas_call[name=...]``), so no block's name may lie inside one
KERNELS = ("hetu_dropout_mask", "hetu_flash_fwd", "hetu_flash_bwd",
           "hetu_gdn_fwd", "hetu_gdn_bwd",
           "hetu_moe_gmm_fwd", "hetu_moe_gmm_dw", "hetu_moe_gmm_dx",
           "hetu_softmax_ce_fwd", "hetu_softmax_ce_bwd",
           "hetu_packed_embedding_write", "hetu_ssd_fwd", "hetu_ssd_bwd",
           "hetu_moe_rows_sum")
BLOCKS = ("hetu_attn", "hetu_mlp", "hetu_embed", "hetu_head", "hetu_loss",
          "hetu_optim", "hetu_param_cast", "hetu_norm", "hetu_moe_other",
          "hetu_moe_route", "hetu_moe_dispatch", "hetu_moe_experts",
          "hetu_moe_combine", "hetu_moe_shared", "hetu_gdn_proj",
          "hetu_gdn_conv", "hetu_gdn_scan", "hetu_gdn_out", "hetu_ssm_proj",
          "hetu_ssm_conv", "hetu_ssm_scan", "hetu_ssm_out")


def test_innermost_scope_wins_and_none_outside():
    x = ht.placeholder_op("x", (2, 4))
    assert x.scope is None and (x + 1.0).scope is None
    with ht.scope("hetu_norm"):
        a = x + 1.0
        with ht.scope("hetu_attn"):
            b = a * 2.0
        c = b + a
    assert (a.scope, b.scope, c.scope) == ("hetu_norm", "hetu_attn",
                                           "hetu_norm")
    assert (c + 1.0).scope is None
    assert {"hetu_norm", "hetu_attn"} <= set(ht.scopes())


@pytest.mark.parametrize("name", ["attn", "hetu_Attn", "hetu_a-b", "hetu_"])
def test_a_scope_is_named_hetu_lower_case(name):
    with pytest.raises(ValueError, match="hetu_"):
        ht.scope(name)


def test_no_scope_name_lies_inside_another_or_inside_a_kernels():
    with ht.scope("hetu_attn"):
        pass
    for clash in ("hetu_attn_out", "hetu_att"):
        with pytest.raises(ValueError, match="inside the other"):
            ht.scope(clash)
        assert clash not in ht.scopes()
    for block in BLOCKS:
        ht.scope(block)                 # every name the program gives
        assert not any(block in k or k in block for k in KERNELS), block
    given = ht.scopes()
    assert len(set(given)) == len(given) and set(BLOCKS) <= set(given)


def test_scoped_op_keeps_its_scope_and_gradient_nodes_carry_none():
    x = ht.placeholder_op("x", (2, 4))
    with ht.scope("hetu_norm"):
        y = ScopedOp(lambda v, k=1.0: v * k, "hetu_ssm_proj", x, k=3.0)
        grads = ht.gradients(ht.reduce_sum_op(y, axes=[0, 1]), [x])
    assert y.scope == "hetu_ssm_proj" and y.name.startswith("hetu_ssm_proj_")
    assert isinstance(grads[0], GradientSliceOp) and grads[0].scope is None
    bundle = grads[0].inputs[0]
    assert isinstance(bundle, GradientsBundleOp) and bundle.scope is None
    ex = ht.Executor([y, grads[0]])
    out, g = ex.run(feed_dict={x: np.ones((2, 4), np.float32)})
    np.testing.assert_array_equal(np.asarray(out), 3.0)
    np.testing.assert_array_equal(np.asarray(g), 3.0)


def op_names(ex, subgraph):
    text = ex.subexecutor[subgraph].lower_compiled().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def test_scopes_stand_in_op_name_forward_backward_and_recomputed():
    """One name an instruction from the graph side, the backward pass as
    ``transpose(jvp(<scope>))``, a recomputed group's under ``checkpoint``,
    the optimiser's pass and the top-of-step cast under their own."""
    x = ht.placeholder_op("x", (8, 16))
    w1 = ht.Variable("scopes_w1", shape=(16, 16),
                     initializer=ht.init.normal(0.0, 0.1))
    w2 = ht.Variable("scopes_w2", shape=(16, 16),
                     initializer=ht.init.normal(0.0, 0.1))
    with ht.scope("hetu_attn"):
        h = ht.tanh_op(ht.matmul_op(x, w1))
    with ht.remat():
        with ht.scope("hetu_mlp"):
            h = ht.tanh_op(ht.matmul_op(h, w2))
    with ht.scope("hetu_loss"):
        loss = ht.reduce_mean_op(h * h, axes=[0, 1])
    train = ht.AdamWOptimizer(learning_rate=0.1).minimize(loss)
    assert train.scope == "hetu_optim"
    ex = ht.Executor({"train": [loss, train]}, compute_dtype="bfloat16")
    names = op_names(ex, "train")
    for block in ("hetu_attn", "hetu_mlp", "hetu_loss"):
        assert any(f"jvp({block})" in n and "transpose" not in n
                   for n in names), block
    for block in ("hetu_attn", "hetu_loss"):
        assert any(f"transpose(jvp({block}))" in n for n in names), block
    assert any("checkpoint/hetu_mlp/" in n for n in names)
    assert any("rematted_computation/hetu_mlp/" in n for n in names)
    assert any("hetu_optim" in n for n in names)
    assert any("hetu_param_cast" in n for n in names)
    # no nesting from the graph side: a node's name is the only one
    assert not any(len(re.findall(r"hetu_[a-z_]+", n)) > 1 for n in names)
    losses = [float(ex.run("train", feed_dict={
        x: np.ones((8, 16), np.float32)})[0]) for _ in range(3)]
    assert np.isfinite(losses).all() and losses[1] != losses[0]


# -- (b) metadata only ---------------------------------------------------------

CELLS = {
    "bert": ("bert-base.b64-s512", {}),
    "dp4": ("bert-base.dp4-b256-s512", {}),
    "olmoe": ("olmoe-1b-7b.b2-s4096", {}),
    "qwen3next": ("qwen3-next-80b-a3b.b1-s8192",
                  {"num_hidden_layers": 4, "full_attention_interval": 4}),
    "nemotronh": ("nemotron-3-nano-30b-a3b.b1-s8192",
                  {"num_hidden_layers": 9,
                   "hybrid_override_pattern": "MEMEM*EME"}),
}


def stripped(text, renamed=False):
    """A compiled module's text without what ``named_scope`` and the source
    lines write (``conftest.without_locations``).  ``renamed``: instructions
    numbered in their order too, where XLA names them after their ``op_name``
    (under a mesh ``jvp_jit_take_along_axis`` is ``jit_take_along_axis`` once
    a block's name stands inside the ``jvp``)."""
    from conftest import without_locations
    text = without_locations(text)
    if renamed:
        names = {}
        text = re.sub(r"%[\w.-]+", lambda m: names.setdefault(
            m.group(0), f"%n{len(names)}"), text)
    return text


def compiled_step(cell, over):
    """The builder's train step at toy widths, compiled; the node and
    optimiser counters start where they started before and the names in a
    namespace of their own, so that two builds fold the same ids into their
    dropout keys and name their state alike."""
    from chipbench import run
    _, _, config, mix = run.load_cell(cell)
    config = run.merge(run.merge(config, config["toy"]), over)
    mix = run.merge(mix, mix["toy"])
    builder = importlib.import_module("chipbench.builders."
                                      + config["builder"])
    graph_node._node_counter[0] = 10 ** 6
    optimizer._opt_counter[0] = 10 ** 3
    with ht.name_scope():
        prog = builder.build(config, mix, 2 ** 31 + 7, lambda msg: None)
    try:
        return prog.ex.subexecutor["train"].lower_compiled().as_text()
    finally:
        prog.close()


@pytest.mark.parametrize("family", sorted(CELLS))
def test_the_compiled_step_is_the_step_without_scopes(family, monkeypatch):
    cell, over = CELLS[family]
    counter, opt_counter = (graph_node._node_counter[0],
                            optimizer._opt_counter[0])
    try:
        with_scopes = compiled_step(cell, over)
        monkeypatch.setattr(jax, "named_scope", lambda name: nullcontext())
        without = compiled_step(cell, over)
    finally:
        graph_node._node_counter[0] = max(counter,
                                          graph_node._node_counter[0])
        optimizer._opt_counter[0] = opt_counter
    assert "hetu_optim" in with_scopes and "hetu_norm" in with_scopes
    # (a jitted pass that both builds share keeps the names of its one trace)
    assert not re.search(r"hetu_(optim|norm|attn|head|loss|embed)", without)
    renamed = family == "dp4"
    assert stripped(with_scopes, renamed) == stripped(without, renamed)


MOVED = """
import jax
from jax.experimental import pallas as pl

def double(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0

def program(x):
    return pl.pallas_call(double, out_shape=jax.ShapeDtypeStruct(
        x.shape, x.dtype))(x) + 1.0
"""


@pytest.mark.parametrize("debug_info", [False, True])
def test_a_moved_line_moves_nothing_once_locations_are_stripped(debug_info):
    """What a comparison of two trees' step programs stands on: the same
    program written seven lines lower lowers to another text (a Mosaic
    payload carries its call stacks' line numbers; with ``debug_info`` so
    does every operation) and to the same text without its locations, while
    another program stays another."""
    from conftest import without_locations

    def lowered(source, pad):
        space = {}
        exec(compile("\n" * pad + source, "moved.py", "exec"), space)
        return jax.jit(space["program"]).trace(
            jax.ShapeDtypeStruct((8, 128), "float32")).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=debug_info)
    here, lower = lowered(MOVED, 0), lowered(MOVED, 7)
    assert "tpu_custom_call" in here and here != lower
    assert without_locations(here) == without_locations(lower)
    other = lowered(MOVED.replace("* 2.0", "* 3.0"), 0)
    assert without_locations(other) != without_locations(here)
