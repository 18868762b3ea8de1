"""Rotary embeddings on the projections' ``[B, S, H d]`` as a Pallas kernel
pair (``hetu_tpu/ops/pallas/rotary.py``), in interpret mode on the CPU, against
the ``jax.numpy`` form ``ops/rotary.py _rotary(seq_axis=1)`` on the view by
heads: values and gradients in bf16 and f32, one head and sixteen, batch 1 and
2, one row block and several; the backward kernel undoes the forward one; the
rule by which the node takes the kernels, on and off a mesh and off a TPU; what
a recomputed layer lowers to; the layers that must not reach the node.  (The
kernels compiled for a described v5e at the cells' shapes:
``tests/test_flash_attention.py``, where the other such compiles are.)"""

import contextlib
import re
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.graph.node import find_topo_sort
from hetu_tpu.layers.attention import MultiHeadAttention
from hetu_tpu.ops import rotary as op
from hetu_tpu.ops.pallas import dispatch, rotary as kernels

from conftest import close, ulps, rotary_kernels_asked as asked

D, THETA = 128, 10000.0


def operands(B, S, H, dtype, seed=0, d=D):
    r = np.random.default_rng(seed)
    return [jnp.asarray(r.normal(size=(B, S, H * d)), dtype)
            for _ in range(3)]


def tables(S, d=D):
    return op._pair_tables(seq_len=S, dim=d, theta=THETA)


def by_heads(x, d=D):
    """``_rotary`` on the ``[B, S, H, d]`` view: what the layer ran before
    there were kernels, and what the node runs where they do not."""
    B, S, W = x.shape
    return op._rotary(x.reshape(B, S, W // d, d), theta=THETA,
                      seq_axis=1).reshape(B, S, W)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,tile", [
    (1, 64, 1, kernels.TILE),       # one head, one block, one chunk
    (2, 64, 16, kernels.TILE),      # the cells' width: one block a sequence
    (1, 96, 16, 2 ** 17),           # three blocks of 32 rows (16 in f32)
    (2, 48, 2, 2 ** 13),            # blocks of 16 rows, chunks of 16
])
def test_kernels_are_the_jnp_form(B, S, H, tile, dtype):
    q, k, _ = operands(B, S, H, dtype)
    got = kernels.hetu_rope_fwd(q, k, tables(S), interpret=True, tile=tile)
    for what, g, x in zip("qk", got, (q, k)):
        close(g, by_heads(x), dtype, what)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gradients_are_the_jnp_forms(dtype):
    """The kernel pair's cotangents of q and k against ``_rotary``'s, from
    the same cotangents of the results (``jax.grad`` of a loss that is linear
    in them)."""
    q, k, w = operands(2, 64, 4, dtype)
    f32 = jnp.float32

    def loss(fn):
        def f(q, k):
            a, b = fn(q, k)
            return jnp.sum(a.astype(f32) * w.astype(f32)
                           - b.astype(f32) * jnp.flip(w, 1).astype(f32))
        return f
    got = jax.grad(loss(lambda q, k: kernels.rope(q, k, tables(64))),
                   argnums=(0, 1))(q, k)
    want = jax.grad(loss(lambda q, k: (by_heads(q), by_heads(k))),
                    argnums=(0, 1))(q, k)
    for what, g, t in zip(("dq", "dk"), got, want):
        close(g, t, dtype, what)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("H,KV,d,r,scaling", [
    (64, 8, 128, 128, None),                        # Laguna's window layers
    (48, 8, 128, 64, op.yarn_scaling(64, 4096, 64, 1, 1.4158883083359672)),
    (16, 16, 128, 128, None),                       # Ouro's, OLMoE's
    (4, 2, 256, 64, None),                          # heads of two lane tiles
])
def test_grouped_queries_and_a_partial_rotation(H, KV, d, r, scaling, dtype):
    """q and k of their own widths, the first ``r`` lanes of a head turned
    (YaRN's tables among them), against ``_rotary`` on each view by heads:
    values, and the cotangents of q and k from the same cotangents of the
    results."""
    S = 32
    more = {"rotary_dim": r} if r != d else {}
    if scaling is not None:
        more["scaling"] = scaling
    rng = np.random.default_rng(H + r)
    q, k, wq, wk = (jnp.asarray(rng.normal(size=(2, S, n * d)), dtype)
                    for n in (H, KV, H, KV))
    t = op._pair_tables(seq_len=S, dim=d, theta=THETA, **more)
    assert t.shape == (3 if r != d else 2, S, d)

    def plain(x):
        return op._rotary(x.reshape(2, S, -1, d), theta=THETA, seq_axis=1,
                          **more).reshape(x.shape)

    def loss(fn):
        def f(q, k):
            a, b = fn(q, k)
            return (jnp.sum(a.astype(jnp.float32) * wq.astype(jnp.float32))
                    - jnp.sum(b.astype(jnp.float32) * wk.astype(jnp.float32)))
        return f
    through = lambda q, k: kernels.rope(q, k, t, more.get("rotary_dim"))
    for what, g, x in zip("qk", through(q, k), (q, k)):
        close(g, plain(x), dtype, what)
    got = jax.grad(loss(through), argnums=(0, 1))(q, k)
    want = jax.grad(loss(lambda q, k: (plain(q), plain(k))),
                    argnums=(0, 1))(q, k)
    for what, g, w in zip(("dq", "dk"), got, want):
        close(g, w, dtype, what)


def test_a_partial_rotation_leaves_the_rest_of_a_head():
    """From ``r`` on: ``cos`` 1 and both sines 0, so the lanes pass bit for
    bit; and the two sine tables never hold on one lane."""
    t = op._pair_tables(seq_len=32, dim=D, theta=THETA, rotary_dim=64)
    assert (t[0, :, 64:] == 1).all() and not t[1:, :, 64:].any()
    assert not (t[1] * t[2]).any()
    assert not t[1, :, :32].any() and not t[2, :, 32:].any()
    q, k, _ = operands(1, 32, 2, jnp.bfloat16)
    out, _ = kernels.rope(q, k, t, 64)
    by_head = lambda x: np.asarray(x, np.float32).reshape(1, 32, 2, D)
    assert (by_head(out)[..., 64:] == by_head(q)[..., 64:]).all()
    assert (by_head(out)[..., :64] != by_head(q)[..., :64]).any()


def test_the_backward_kernel_undoes_the_forward_one():
    """A rotation's transpose is its inverse; and the tables, the one
    residual, get no gradient."""
    q, k, _ = operands(2, 64, 16, jnp.float32)
    t = tables(64)
    out, vjp = jax.vjp(kernels.rope, q, k, t)
    dq, dk, dt = vjp(out)
    assert float(jnp.abs(dq - q).max()) < 1e-5
    assert float(jnp.abs(dk - k).max()) < 1e-5
    assert dt.shape == t.shape and not dt.any()
    back = kernels.hetu_rope_bwd(*out, t, interpret=True, tile=2 ** 14)
    assert float(jnp.abs(back[0] - q).max()) < 1e-5


def test_the_tables_fold_the_sign_into_the_sine():
    cos, sin = op._rope_tables(32, D, THETA)
    t = tables(32)
    assert t.shape == (2, 32, D) and t.dtype == jnp.float32
    assert (t[0] == cos).all()
    assert (t[1, :, :D // 2] == -sin[:, :D // 2]).all()
    assert (t[1, :, D // 2:] == sin[:, D // 2:]).all()


# -- the rule -----------------------------------------------------------------

def sds(*shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("q,k,head_dim,why", [
    (sds(1, 64, 256), sds(1, 64, 256), 64, "head_dim_not_128_aligned"),
    (sds(1, 64, 256, dtype=jnp.float16), sds(1, 64, 256, dtype=jnp.float16),
     128, "dtype:float16"),
    (sds(1, 64, 256), sds(1, 64, 256, dtype=jnp.float32), 128, "dtype:mixed"),
    (sds(1, 24, 256), sds(1, 24, 256), 128, "seq_not_16_aligned"),
    # grouped queries: k narrower than q (was ``q_k_widths_differ``)
    (sds(1, 64, 512), sds(1, 64, 256), 128, None),
    (sds(1, 64, 256), sds(1, 64, 256), 128, None),
    (sds(2, 4096, 2048, dtype=jnp.float32),
     sds(2, 4096, 2048, dtype=jnp.float32), 256, None),
])
def test_unsupported_reads_its_operands(q, k, head_dim, why):
    assert kernels.unsupported(q, k, head_dim=head_dim) == why


@pytest.fixture
def choices(live_registry):
    """``{(impl, reason): count}`` recorded under ``rotary`` since the test
    began."""
    before = dispatch.choices()

    def since():
        return {key[1:]: n - before.get(key, 0)
                for key, n in dispatch.choices().items()
                if key[0] == "rotary" and n > before.get(key, 0)}
    return since


def pair_node(q_shape, k_shape, d, name, seq_len=None):
    q = ht.placeholder_op(f"{name}_q", q_shape)
    k = ht.placeholder_op(f"{name}_k", k_shape)
    first, second = op.rotary_pair_op(
        q, k, op.RopeTables()(seq_len or q_shape[-2], d, THETA))
    pair, = first.inputs
    assert second.inputs == [pair] and isinstance(pair, op.RotaryPairOp)
    return pair


def compute(pair, q, k, mesh=None):
    d, S = pair.inputs[2].attrs["dim"], pair.inputs[2].attrs["seq_len"]
    return pair._compute([q, k, tables(S, d)],
                         types.SimpleNamespace(mesh=mesh))


@pytest.mark.parametrize("shape,k_shape,d,dtypes,why", [
    ((1, 32, 256), None, 64, "bb", "head_dim_not_128_aligned"),
    ((1, 32, 256), None, 128, "hh", "dtype:float16"),
    ((1, 32, 256), None, 128, "bf", "dtype:mixed"),
    ((1, 24, 256), None, 128, "bb", "seq_not_16_aligned"),
    # four query heads on two key heads: no refusal any more, the kernels
    ((1, 32, 512), (1, 32, 256), 128, "bb", None),
])
def test_a_refusal_takes_the_jnp_form_and_counts_it(choices, monkeypatch,
                                                    shape, k_shape, d, dtypes,
                                                    why):
    asked(monkeypatch)
    if why is not None:
        monkeypatch.setattr(kernels, "rope", None)          # never reached
    types_ = dict(b=jnp.bfloat16, f=jnp.float32, h=jnp.float16)
    r = np.random.default_rng(1)
    q, k = (jnp.asarray(r.normal(size=s), types_[t])
            for s, t in zip((shape, k_shape or shape), dtypes))
    got = compute(pair_node(shape, k_shape or shape, d,
                            f"rk_{(why or 'grouped')[:9]}"), q, k)
    for g, x in zip(got, (q, k)):
        want = by_heads(x, d)
        assert g.dtype == x.dtype and g.shape == x.shape
        assert (g == want).all() if why else ulps(g, want) <= 1.0
    assert choices() == ({("jnp", why): 1} if why else {("pallas", ""): 1})


def test_under_a_mesh_the_jnp_form_and_the_reason_mesh(choices, monkeypatch):
    asked(monkeypatch)
    monkeypatch.setattr(kernels, "rope", None)
    q, k, _ = operands(1, 32, 2, jnp.bfloat16)
    got = compute(pair_node(q.shape, k.shape, D, "rk_mesh"), q, k,
                  mesh=types.SimpleNamespace(shape={"dp": 2}))
    assert (got[0] == by_heads(q)).all() and (got[1] == by_heads(k)).all()
    assert choices() == {("jnp", "mesh"): 1}


def test_off_a_tpu_and_not_asked_nothing_is_recorded(choices, monkeypatch):
    monkeypatch.setattr(kernels, "rope", None)
    q, k, _ = operands(1, 32, 2, jnp.bfloat16)
    got = compute(pair_node(q.shape, k.shape, D, "rk_cpu"), q, k)
    assert (got[0] == by_heads(q)).all()
    assert choices() == {}
    assert "rotary" in dispatch.NO_CHOICE_OFF_TPU


def test_asked_the_node_runs_the_kernels_and_counts_pallas(choices,
                                                           monkeypatch):
    """A caller's ``[B S, H d]`` too: the node reads the sequence's length
    from the tables."""
    asked(monkeypatch)
    q, k, _ = operands(2, 32, 2, jnp.bfloat16)
    pair = pair_node((64, 2 * D), (64, 2 * D), D, "rk_flat", seq_len=32)
    got = compute(pair, q.reshape(64, -1), k.reshape(64, -1))
    for g, x in zip(got, (q, k)):
        assert g.shape == x.shape and ulps(g, by_heads(x)) <= 1.0
    assert choices() == {("pallas", ""): 1}


# -- the layer ----------------------------------------------------------------

def rotary_nodes(out):
    return [n for n in find_topo_sort([out])
            if getattr(n, "op_kind", "").startswith(("rotary", "rope"))]


def layer_grads(name, through, monkeypatch, remat=False, dtype=None, S=32):
    """An attention layer (two heads of 128) over ``[2, S, 64]``: the
    executor of its loss and of every weight's gradient, and the feed."""
    if through:
        asked(monkeypatch)
    layer = MultiHeadAttention(64, 2, sequence_length=S, causal_mask=True,
                               rope_theta=THETA, head_dim=D, bias=False,
                               name=name)
    x = ht.placeholder_op(f"{name}_x", (2, S, 64))
    with ht.remat() if remat else contextlib.nullcontext():
        y = layer(x, x, x)
    loss = ht.reduce_sum_op(ht.sin_op(y), axes=[0, 1, 2])
    weights = [p.weight for p in (layer.q_proj, layer.k_proj, layer.v_proj,
                                  layer.out_proj)]
    ex = ht.Executor({"grads": [loss] + ht.gradients(loss, weights)}, seed=3,
                     **({} if dtype is None else {"compute_dtype": dtype}))
    r = np.random.default_rng(5)
    for var in weights:
        ex.params[var.name] = jnp.asarray(
            r.normal(0.0, 0.1, var.shape), ex.params[var.name].dtype)
    return ex, {x: r.normal(size=(2, S, 64)).astype(np.float32)}, y


def test_layer_through_the_kernels_is_the_layer(choices, monkeypatch):
    """Loss and the gradient of every weight, f32: one tables node, one pair
    node, two items, and one choice a traced call."""
    outs = []
    for through in (False, True):
        ex, feed, y = layer_grads(f"rk_layer{int(through)}", through,
                                  monkeypatch)
        kinds = sorted(n.op_kind for n in rotary_nodes(y))
        assert kinds == ["rope_tables", "rotary_pair"]
        outs.append(ex.run("grads", feed_dict=feed,
                           convert_to_numpy_ret_vals=True))
        ex.close()
    assert choices() == {("pallas", ""): 1}
    (l1, *g1), (l2, *g2) = outs
    assert abs(float(l2 - l1)) < 1e-5 * abs(float(l1))
    for a, b in zip(g2, g1):
        assert a.shape == b.shape and np.abs(b).max() > 0
        assert np.abs(a - b).max() < 1e-4 * np.abs(b).max()


def test_a_models_layers_share_one_tables_node_outside_every_remat_group():
    """Two layers of one model, each applied twice in a recomputed group of
    its own (the looped stack's shape): one tables node, under the block's
    name and in no group; a layer built alone has its own."""
    from hetu_tpu.models import OuroConfig, OuroModel
    model = OuroModel(OuroConfig(vocab_size=64, hidden_size=2 * D,
                                 num_layers=2, num_heads=2,
                                 intermediate_size=64, seq_len=32,
                                 total_ut_steps=2, remat=True),
                      name="rk_share")
    ids = ht.placeholder_op("rk_share_ids", (1, 32), dtype=np.int32)
    nodes = [n for state in model(ids) for n in rotary_nodes(state)]
    pairs = {n for n in nodes if n.op_kind == "rotary_pair"}
    tables = {n for n in nodes if n.op_kind == "rope_tables"}
    assert len(pairs) == 4 and len(tables) == 1
    assert len({n.remat_scope for n in pairs}) == 4 and None not in {
        n.remat_scope for n in pairs}
    shared, = tables
    assert shared.remat_scope is None and shared.scope == "hetu_attn"
    assert shared is model.rope_tables(32, D, model.config.rope_theta)
    assert shared is not model.rope_tables(48, D, model.config.rope_theta)
    alone = MultiHeadAttention(64, 2, rope_theta=THETA, head_dim=D,
                               name="rk_alone")
    assert alone.rope_tables is not model.rope_tables


def test_a_recomputed_layer_lowers_to_two_forward_kernels_and_one_backward(
        monkeypatch):
    """Lowered for a TPU (nothing compiled or run): under ``ht.remat()`` the
    rotary forward kernel stands in the forward pass and in its
    recomputation, the backward kernel once; the flash kernel pair stands
    ONCE each (the group keeps the forward kernel's context and log-sum-exp,
    PR 55), and no f32 array nor a view by heads of q or k stands between the
    projections and the flash kernels."""
    from conftest import kernel_calls
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    jax.clear_caches()
    try:
        ex, _, _ = layer_grads("rk_remat", False, monkeypatch, remat=True,
                               dtype=jnp.bfloat16, S=256)
        sub = ex.subexecutor["grads"]
        if sub._jitted is None:
            sub._build()
        text = sub._jitted.trace(*sub._abstract_args(None)).lower(
            lowering_platforms=("tpu",)).as_text()
        ex.close()
    finally:
        jax.clear_caches()
    calls = re.findall(r"call @(hetu_rope_\w+?)(?:_\d+)?\(", text)
    assert sorted(calls) == ["hetu_rope_bwd"] + ["hetu_rope_fwd"] * 2, calls
    assert 'kernel_name = "hetu_rope_fwd"' in text
    assert kernel_calls(text, "hetu_flash_fwd") == 1
    assert kernel_calls(text, "hetu_flash_bwd") == 1
    # the kernels read and write the projections' type; no view by heads
    assert len(re.findall(
        r"call @hetu_rope_\w+\(.*\) : \(tensor<2x256x256xbf16>, "
        r"tensor<2x256x256xbf16>, tensor<2x256x128xf32>\) -> "
        r"\(tensor<2x256x256xbf16>, tensor<2x256x256xbf16>\)", text)) == 3
    assert not re.findall(r"tensor<2x256x2x128x\w+>", text)


# -- the layers that keep ``_rotary`` ------------------------------------------

def partial_rotary_layer():
    layer = MultiHeadAttention(64, 2, sequence_length=32, causal_mask=True,
                               rope_theta=THETA, head_dim=D, rotary_dim=32,
                               qk_norm="head", output_gate=True, bias=False,
                               name="rk_bhsd")
    x = ht.placeholder_op("rk_bhsd_x", (1, 32, 64))
    return x, layer(x, x, x)


def latent_layer():
    from hetu_tpu.layers.latent_attention import LatentAttention
    layer = LatentAttention(64, 2, 32, D, 64, D, name="rk_mla")
    x = ht.placeholder_op("rk_mla_x", (1, 32, 64))
    return x, layer(x)


def grouped_query_layer(head_dim=D, name="rk_gqa", **more):
    layer = MultiHeadAttention(64, 2, sequence_length=32, causal_mask=True,
                               rope_theta=THETA, head_dim=head_dim,
                               num_kv_heads=1, name=name, **more)
    x = ht.placeholder_op(f"{name}_x", (1, 32, 64))
    return x, layer(x, x, x)


def grouped_query_layer_of_64():
    return grouped_query_layer(64, "rk_gqa64")


def test_a_grouped_query_layer_of_whole_tiles_builds_the_pair_node(
        choices, monkeypatch):
    """Heads of 128 on fewer key heads, and with a partial rotation: the layer
    is on the flat path, q ``[B, S, 2 x 128]`` and k ``[B, S, 128]`` go
    through ONE pair node, and asked for, the kernels run and count."""
    asked(monkeypatch)
    for n, more in enumerate(({}, {"rotary_dim": 64})):
        x, y = grouped_query_layer(name=f"rk_gqa{n}", **more)
        pair, = [n for n in rotary_nodes(y) if n.op_kind == "rotary_pair"]
        tables, = [n for n in rotary_nodes(y) if n.op_kind == "rope_tables"]
        assert pair.inputs[2] is tables
        assert tables.attrs.get("rotary_dim") == more.get("rotary_dim")
        assert pair.attrs.get("rotary_dim") == more.get("rotary_dim")
        ex = ht.Executor({"f": [y]})
        out, = ex.run("f", feed_dict={x: np.ones(x.shape, np.float32)})
        ex.close()
        assert out.shape == x.shape and np.isfinite(np.asarray(out)).all()
    assert choices() == {("pallas", ""): 2}


@pytest.mark.parametrize("build", [partial_rotary_layer, latent_layer,
                                   grouped_query_layer_of_64])
def test_other_layers_build_no_pair_node_and_record_no_choice(
        choices, monkeypatch, build):
    """Partial rotary beside a norm a head and the elementwise gate
    (Qwen3-Next), latent attention's rotary part (Ling-3.0) and grouped
    queries on heads of 64 (Granite) keep ``_rotary`` on ``[B, H, S, d]``: on
    the chip a ``jnp`` record under ``rotary`` would fail their cells' kernel
    check."""
    asked_for, real = [], dispatch.take
    monkeypatch.setattr(dispatch, "take", lambda kernel, *a, **kw:
                        asked_for.append(kernel) or real(kernel, *a, **kw))
    x, y = build()
    assert not [n for n in rotary_nodes(y)
                if n.op_kind in ("rotary_pair", "rope_tables")]
    ex = ht.Executor({"f": [y]})
    out, = ex.run("f", feed_dict={x: np.ones(x.shape, np.float32)})
    ex.close()
    assert out.shape == x.shape
    assert "rotary" not in asked_for and choices() == {}
