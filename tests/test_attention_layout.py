"""Which graph an attention layer builds (``layers/attention.py
MultiHeadAttention.layout``, ``hetu_attn_layout_total{layout, reason}``): the
rule a case a reason; a layer with grouped queries, a gate a head, a partial
rotation and a window, or with a norm a head under the block-diffusion and
the causal mask, on the projections' ``[B, S, H d]`` against ``_attend_bhsd``
on the same weights, values and every weight's gradient; the gate a head in
place against the one on ``[B, H, S, d]``; what the toy train
steps of the cells that bypass the rule lower to (a stored hash: ``rep = 1``
and the layers the rule leaves on ``[B, H, S, D]`` build what they built before
PR 52); and the Laguna, Nemotron-H and SDAR toys with heads of 128, which
take the flat path, through the harness's own check of the kernels chosen; and a latent
layer, which chooses when it is traced: in place on a TPU, by heads under a
mesh."""

import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import find_topo_sort
from hetu_tpu.layers import attention as layers
from hetu_tpu.layers.attention import MultiHeadAttention
from hetu_tpu.ops import rotary as op
from hetu_tpu.ops.pallas import dispatch

from chipbench import run
from conftest import rotary_kernels_asked as asked


def kinds_of(out):
    return [getattr(n, "op_kind", type(n).__name__)
            for n in find_topo_sort([out])]


def layouts_built():
    """``{(layout, reason): count}`` of ``hetu_attn_layout_total`` so far."""
    return {tuple(labels.values()): n for labels, n in
            dispatch.counted("hetu_attn_layout_total")}


def since(before):
    return {k: n - before.get(k, 0) for k, n in layouts_built().items()
            if n > before.get(k, 0)}


# -- the rule -----------------------------------------------------------------

@pytest.mark.parametrize("kw,want", [
    (dict(), ("bshd", "in_place")),                           # BERT: heads of 64
    (dict(head_dim=128, rope_theta=1e4, qk_norm=True),        # OLMoE
     ("bshd", "in_place")),
    (dict(head_dim=128, num_kv_heads=2, rope_theta=1e4,       # Laguna, window
          output_gate="head", window=8, causal_mask=True),
     ("bshd", "in_place")),
    (dict(head_dim=128, num_kv_heads=2, rope_theta=1e4, rotary_dim=64,
          output_gate="head", causal_mask=True),              # Laguna, full
     ("bshd", "in_place")),
    (dict(head_dim=256, num_kv_heads=1), ("bshd", "in_place")),  # Nemotron-H's
    (dict(head_dim=64, num_kv_heads=2),                       # Granite
     ("bhsd", "head_dim_not_128_aligned")),
    (dict(head_dim=64, output_gate="head"),
     ("bhsd", "head_dim_not_128_aligned")),
    (dict(head_dim=96, rope_theta=1e4, rotary_dim=32),
     ("bhsd", "head_dim_not_128_aligned")),
    (dict(head_dim=256, num_kv_heads=2, qk_norm="head", output_gate=True,
          rope_theta=1e4, rotary_dim=64),                     # Qwen3-Next
     ("bhsd", "gate_elementwise")),
    (dict(head_dim=128, num_kv_heads=2, qk_norm="head", rope_theta=1e6,
          block_diffusion=4), ("bshd", "in_place")),          # SDAR
    (dict(head_dim=128, qk_norm="head", rope_theta=1e4, causal_mask=True),
     ("bshd", "in_place")),                                   # Qwen3's
    (dict(head_dim=64, qk_norm="head", rope_theta=1e4),
     ("bhsd", "head_dim_not_128_aligned")),
    # a norm a head that no rotation follows: no cell's layer
    (dict(head_dim=128, qk_norm="head"), ("bhsd", "qk_norm_per_head")),
    (dict(head_dim=128, output_gate=True), ("bhsd", "gate_elementwise")),
    (dict(head_dim=128, num_kv_heads=2, alibi=True), ("bhsd", "alibi")),
    (dict(head_dim=128, num_kv_heads=2, fused_head_projection=True),
     ("bhsd", "fused_head_projection")),
])
def test_the_rule_reads_the_layers_own_arguments(live_registry, kw, want):
    """One rule, decided where the graph is built and counted there; the
    reason is the FIRST of the list that holds."""
    name = f"al_rule{abs(hash(str(sorted(kw.items())))) % 10 ** 6}"
    layer = MultiHeadAttention(256, 4, sequence_length=16, bias=False,
                               name=name, **kw)
    assert layer.layout() == want
    x = ht.placeholder_op(f"{name}_x", (1, 16, 256))
    before = layouts_built()
    out = layer(x, x, x)
    assert since(before) == {want: 1}
    kinds = set(kinds_of(out))
    # the flat graph moves no heads; the other one splits and transposes
    assert ("transpose" in kinds or "head_split_linear" in kinds) == (
        want[0] == "bhsd")
    assert ("repeat_kv" in kinds) == (
        want[0] == "bhsd" and layer.num_kv_heads != layer.num_heads)


# -- the flat path against the graph by heads ---------------------------------

def both_graphs(name, dtype=None, **kw):
    """One layer's variables under both graphs: the executor of each graph's
    loss and of every weight's gradient, and the feed."""
    S, hidden = 32, 64
    kw = {"causal_mask": True, **kw}
    layer = MultiHeadAttention(hidden, 4, sequence_length=S,
                               head_dim=128, num_kv_heads=2, bias=False,
                               name=name, **kw)
    assert layer.layout() == ("bshd", "in_place")
    x = ht.placeholder_op(f"{name}_x", (2, S, hidden))
    with ht.scope("hetu_attn"):
        by_heads = layer._attend_bhsd(x, x, x, None, S, S)
    flat = layer(x, x, x)
    weights = [p.weight for p in (layer.q_proj, layer.k_proj, layer.v_proj,
                                  layer.out_proj, layer.gate_proj)
               if p is not None]
    scales = [n.scale for n in (layer.q_norm, layer.k_norm) if n is not None]
    graphs = {}
    for key, y in (("flat", flat), ("bhsd", by_heads)):
        loss = ht.reduce_sum_op(ht.sin_op(y), axes=[0, 1, 2])
        graphs[key] = [loss, y] + ht.gradients(loss, weights + scales)
    ex = ht.Executor(graphs, seed=3, **(
        {} if dtype is None else {"compute_dtype": dtype}))
    r = np.random.default_rng(7)
    for var in weights + scales:
        ex.params[var.name] = jnp.asarray(
            r.normal(float(var in scales), 0.2, var.shape),
            ex.params[var.name].dtype)
    feed = {x: r.normal(size=(2, S, hidden)).astype(np.float32)}
    return ex, feed, flat, by_heads


@pytest.mark.parametrize("through_the_kernels", [False, True])
@pytest.mark.parametrize("kw", [
    dict(rope_theta=1e4, output_gate="head", window=8),       # Laguna, window
    dict(rope_theta=5e5, rotary_dim=64, output_gate="head",   # Laguna, full
         rope_scaling=op.yarn_scaling(64, 16, 64, 1, 1.4158883083359672)),
    dict(),                                                   # Nemotron-H's
], ids=["window", "full", "plain"])
def test_the_flat_path_is_the_graph_by_heads(monkeypatch, kw,
                                             through_the_kernels):
    """Grouped queries (four heads of 128 on two key heads) with a gate a
    head and a window, or a partial rotation under YaRN, or nothing: loss,
    output and the gradient of every weight, f32, against ``_attend_bhsd`` on
    the same variables; with the rotary kernel pair too."""
    if through_the_kernels:
        if "rope_theta" not in kw:
            pytest.skip("no rotary: no kernel to ask for off a TPU")
        asked(monkeypatch)
    name = f"al_flat{int(through_the_kernels)}{len(kw)}"
    ex, feed, flat, by_heads = both_graphs(name, **kw)
    kinds = lambda y: sorted(k for k in kinds_of(y) if k.startswith(
        ("rotary", "repeat", "gate_heads")))
    gate = ["gate_heads"] if "output_gate" in kw else []
    rot = "rope_theta" in kw
    assert kinds(by_heads) == gate + ["repeat_kv"] * 2 + (
        ["rotary_embedding"] * 2 if rot else [])
    assert kinds(flat) == [g + "_in_place" for g in gate] + (
        ["rotary_pair"] if rot else [])
    got, want = (ex.run(key, feed_dict=feed, convert_to_numpy_ret_vals=True)
                 for key in ("flat", "bhsd"))
    ex.close()
    assert abs(float(got[0] - want[0])) < 1e-5 * abs(float(want[0]))
    for a, b in zip(got[1:], want[1:]):
        assert a.shape == b.shape and np.abs(b).max() > 0
        assert np.abs(a - b).max() < 2e-5 * np.abs(b).max()


@pytest.mark.parametrize("through_the_kernels", [False, True])
@pytest.mark.parametrize("mask", [dict(causal_mask=False, block_diffusion=4),
                                  dict(causal_mask=True)],
                         ids=["block_diffusion", "causal"])
def test_a_norm_a_head_in_place_is_the_graph_by_heads(monkeypatch, mask,
                                                      through_the_kernels):
    """Four heads of 128 on two key heads, a norm a head and a whole rotation,
    under the block-diffusion mask (SDAR: a clean and a noised copy, rotated
    alike) and under the causal one: loss, output and the gradient of every
    weight, the two norms' scales among them, f32, against ``_attend_bhsd`` on
    the same variables; with the kernel pair too."""
    if through_the_kernels:
        asked(monkeypatch)
    name = f"al_norm{int(through_the_kernels)}{len(mask)}"
    ex, feed, flat, by_heads = both_graphs(name, rope_theta=1e6,
                                           qk_norm="head", **mask)
    kinds = lambda y: sorted(k for k in kinds_of(y) if k.startswith(
        ("rotary", "repeat", "rms_norm", "qk_norm")))
    assert kinds(by_heads) == (["repeat_kv"] * 2 + ["rms_norm"] * 2
                               + ["rotary_embedding"] * 2)
    assert kinds(flat) == ["qk_norm_rotary_pair"]
    got, want = (ex.run(key, feed_dict=feed, convert_to_numpy_ret_vals=True)
                 for key in ("flat", "bhsd"))
    ex.close()
    assert len(got) == 2 + 4 + 2
    assert abs(float(got[0] - want[0])) < 1e-5 * abs(float(want[0]))
    for a, b in zip(got[1:], want[1:]):
        assert a.shape == b.shape and np.abs(b).max() > 0
        assert np.abs(a - b).max() < 2e-5 * np.abs(b).max()


def test_the_flat_path_in_bf16_rounds_where_the_graph_by_heads_does(
        monkeypatch):
    """bf16 compute, the rotary kernels in: the context's gate and the
    rotation round once, so the two graphs' outputs are a few bf16 places
    apart and no more."""
    asked(monkeypatch)
    ex, feed, _, _ = both_graphs("al_bf16", dtype=jnp.bfloat16,
                                 rope_theta=1e4, output_gate="head", window=8)
    got, want = (ex.run(key, feed_dict=feed, convert_to_numpy_ret_vals=True)
                 for key in ("flat", "bhsd"))
    ex.close()
    a, b = (np.asarray(t[1], np.float32) for t in (got, want))
    assert np.abs(a - b).max() < 0.02 * np.abs(b).max()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_gate_a_head_in_place_is_the_gate_by_heads(dtype):
    """Bit for bit, values and both cotangents: the sigmoid spread over a
    head's lanes by the 0/1 product is the sigmoid (its three bf16 parts add
    up to it), and nothing but the two operands is kept."""
    r = np.random.default_rng(0)
    B, S, H, d = 2, 32, 6, 128
    ctx = jnp.asarray(r.normal(size=(B, H, S, d)), dtype)
    gate = jnp.asarray(3 * r.normal(size=(B, S, H)), dtype)
    w = jnp.asarray(r.normal(size=(B, S, H * d)), jnp.float32)
    flat = lambda x: x.transpose(0, 2, 1, 3).reshape(B, S, H * d)
    sig = jax.nn.sigmoid(gate.astype(jnp.float32))
    assert (layers._widen(sig, d) == jnp.repeat(sig, d, axis=-1)).all()
    assert (layers._gate_heads_in_place(flat(ctx), gate)
            == layers._gate_heads(ctx, gate)).all()

    def loss(fn):
        return lambda c, g: jnp.sum(fn(c, g).astype(jnp.float32) * w)
    want = jax.grad(loss(layers._gate_heads), (0, 1))(ctx, gate)
    got = jax.grad(loss(layers._gate_heads_in_place), (0, 1))(flat(ctx), gate)
    assert (got[0] == flat(want[0])).all()
    gap = np.abs(np.asarray(got[1], np.float32)
                 - np.asarray(want[1], np.float32)).max()
    assert gap <= (4e-6 if dtype == jnp.float32 else 2 ** -6) * float(
        np.abs(np.asarray(want[1], np.float32)).max())
    kept = jax.make_jaxpr(lambda c, g: jax.vjp(
        layers._gate_heads_in_place, c, g)[1])(flat(ctx), gate)
    assert sorted(v.aval.shape for v in kept.jaxpr.outvars) == [
        (B, S, H), (B, S, H * d)]


# -- the cells that bypass the rule -------------------------------------------

#: cell -> sha256 of ``without_locations`` of its toy train step lowered for a
#: TPU (without the results' paths, which hold names the process numbers:
#: the optimizer's, a fresh variable's), taken on the tree before PR 52
#: (8d0d00d) and equal on this one; OLMoE's and Qwen3-Next's since PR 61, whose
#: routers choose their experts in ``hetu_moe_select`` (the three steps
#: without a router kept theirs); EvaByte's since PR 67, which brought it (its
#: toy's heads of 32 take the ``jax.numpy`` forms); Qwen3-Next's since PR 69,
#: whose scan node forms the gates before it slices ``mixed`` (its toy's heads
#: of 16 take the ``jax.numpy`` prologue and form: the parent's 5,325 lines in
#: another order, line for line but for a private function's number);
#: Granite's since PR 71, whose scan node forms ``dt`` and ``A`` before it
#: slices ``xBC`` (its toy's heads of 16 take the slices, the skip and the
#: ``jax.numpy`` form: the parent's 2,619 lines in another order, line for
#: line but for the values' numbers)
TOY_STEPS = {
    "bert-base.b64-s512":
        "fef11c9a04527e1704fa1b7730bef180fb2aae5027eeee745929dd16f4e31407",
    "olmoe-1b-7b.b2-s4096":
        "b624dcca179f629ffb969b5fdd888686184eaffad14989d9a38bc8941834ff1a",
    "ouro-2.6b.b1-s8192":
        "6c3754eb9f8eb2ff37b5f3c719410662b5bc365c495d35292bea1b7c1555f428",
    "qwen3-next-80b-a3b.b1-s8192":
        "5fb0f01223d80a4f8de7a41ea6bba8507f4cb0baa977d92af2e470180ebd4ac2",
    "granite-4.0-h-micro.b1-s8192":
        "baad6f4c94657bc655c804133dccac1e586a830cd8cf4bbdf36a1671c242d8c6",
    "evabyte-6.5b.b1-s8192":
        "05424cb015755648532ddb85848efe69f8de1a07324069a564549250beafc8ee",
}


def toy(cell, say=lambda msg: None, **widths):
    """The cell's program at its configuration's toy size, ``widths`` over
    it."""
    import importlib
    _, _, config, mix = run.load_cell(cell)
    config = run.merge(run.merge(config, config["toy"]), widths)
    mix = run.merge(mix, mix["toy"])
    builder = importlib.import_module(
        "chipbench.builders." + config["builder"])
    return builder.build(config, mix, 0, say), mix


@pytest.mark.parametrize("cell", sorted(TOY_STEPS))
def test_toy_train_steps_lower_to_what_they_lowered_to(monkeypatch, cell):
    """``rep = 1`` (BERT, OLMoE, Ouro: same tables, same index maps, the
    kernels' text where the toy reaches them) and the layers the rule leaves
    on ``[B, H, S, D]`` (Qwen3-Next, Granite): the lowered step is the
    parent's once nothing is left that a moved line moves.  A PR that means to
    change one of these steps takes the new hash; this one did not."""
    from conftest import lowered_for_tpu, without_locations
    text = re.sub(r'jax\.result_info = "[^"]*"', 'jax.result_info = ""',
                  without_locations(lowered_for_tpu(
                      monkeypatch, lambda: toy(cell)[0])))
    assert hashlib.sha256(text.encode()).hexdigest() == TOY_STEPS[cell]


@pytest.mark.parametrize("cell,widths,layers_", [
    ("laguna-xs.2.b1-s8192", dict(head_dim=128), 5),
    ("nemotron-3-nano-30b-a3b.b1-s8192", dict(head_dim=128), 1),
    ("sdar-30b-a3b.b1-s8192", dict(head_dim=128, num_hidden_layers=6), 6),
])
def test_toys_with_heads_of_128_take_the_flat_path(live_registry, cell,
                                                   widths, layers_):
    """The Laguna toy (grouped queries, a gate a head, a window, YaRN on half
    a head), the Nemotron-H toy (grouped queries, no rotary) and the SDAR toy
    (grouped queries, a norm a head, the block-diffusion mask; six layers, as
    its cell has) at heads of 128: every attention layer is built in place, the program is as near its
    cell's plain reference as the toy's limits ask, a train step runs, and the
    harness's own reading of the kernels chosen finds no ``jax.numpy`` form
    that the platform does not explain."""
    before = layouts_built()
    program, mix = toy(cell, **widths)
    try:
        assert since(before) == {("bshd", "in_place"): layers_}
        feed, = program.make_batches(52, 1)
        want = program.reference_loss(feed, int(mix["reference_chunk"]))
        got = program.eval_loss(feed)
        for term, limit in mix["reference_tolerance"].items():
            assert abs(got[term] - want[term]) < limit, (term, got, want)
        assert np.isfinite(program.step(feed))
        _, fallbacks = program.kernel_choices()
        assert fallbacks == []
    finally:
        program.close()


# -- the latent layer chooses when it is traced (PR 59) --------------------------

@pytest.mark.parametrize("platform,mesh,layout,entry,choice", [
    ("tpu", False, ("bshd", "latent_in_place"), ("bshd_v128", 1),
     ("pallas", "")),
    ("tpu", True, ("bhsd", "latent_under_a_mesh"), ("bhsd_v128", 1),
     ("jnp", "mesh")),
    ("cpu", False, ("bhsd", "latent_no_mosaic"), None, None),
])
def test_a_latent_layer_is_in_place_on_a_tpu_and_by_heads_under_a_mesh(
        live_registry, monkeypatch, platform, mesh, layout, entry, choice):
    """Heads of 128 + 64 over values of 128 (both cells' sizes), traced
    abstractly: on a TPU with no mesh the heads node takes the pack pairs and
    the flash entry is ``bshd_v128``, one head a program; under a mesh it
    counts ``mesh`` and every node is the one it was (``bhsd_v128``, per shard);
    off a TPU nothing is recorded and flash is not reached."""
    from jax.sharding import Mesh
    from hetu_tpu.graph.node import VariableOp
    from hetu_tpu.graph.trace import TraceContext, evaluate
    from hetu_tpu.layers.latent_attention import LatentAttention
    from hetu_tpu.ops.pallas import flash_attention as flash
    monkeypatch.setattr(dispatch, "platform", lambda: platform)
    name = f"mla_traced_{platform}_{int(mesh)}"
    x = ht.placeholder_op(f"{name}_x", (2, 256, 64))
    out = LatentAttention(64, 4, 32, 128, 64, 128, name=name)(x)
    variables = [n for n in find_topo_sort([out])
                 if isinstance(n, VariableOp)]
    ctx = TraceContext(training=True, mesh=Mesh(
        np.array(jax.devices()[:2]), ("dp",)) if mesh else None)
    sds = lambda node: jax.ShapeDtypeStruct(node.shape, jnp.bfloat16)

    def traced(*values):
        return evaluate([out], dict(zip([x] + variables, values)), ctx)[0][0]

    def counts():
        return (layouts_built(), flash.entries(),
                {k[1:]: n for k, n in dispatch.choices().items()
                 if k[0] == "mla_pack"})
    before = counts()
    got = jax.eval_shape(traced, *map(sds, [x] + variables))
    assert got.shape == (2, 256, 64)
    built, entries, chosen = ({k: n - b.get(k, 0) for k, n in a.items()
                               if n > b.get(k, 0)}
                              for a, b in zip(counts(), before))
    assert built == {layout: 1}
    assert entries == ({} if entry is None else {entry: 1})
    assert chosen == ({} if choice is None else {choice: 1})
