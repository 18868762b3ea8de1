"""Differential attention (``layers/attention.py DifferentialAttention``): the
halves of a query pair as heads of a pair's width on one value, through the
flash and the window kernels in interpret mode, against two plain softmaxes
written out (``chipbench/reference/phi4flash.py differential``), with and
without the window and at the window's edge keys; the layer's graph on the CPU
against the same; what the pair view refuses; what is counted."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import telemetry
from hetu_tpu.layers import attention as layer
from hetu_tpu.layers.attention import DifferentialAttention
from hetu_tpu.ops.pallas import flash_attention as fa

from chipbench.reference import phi4flash as ref
from chipbench.reference.ling3 import _mm

S, H, KV, D = 256, 8, 4, 64              # 4 query pairs on 2 key pairs
C = {"num_attention_heads": H, "num_key_value_heads": KV,
     "hidden_size": H * D, "layer_norm_eps": 1e-5}
INDEX = 15


def gap(a, b):
    a, b = (np.asarray(t, np.float64) for t in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.fixture(scope="module")
def operands():
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    q = jax.random.normal(ks[0], (1, S, H * D))
    k, v = (jax.random.normal(kk, (1, S, KV * D)) for kk in ks[1:3])
    w = {n: 0.3 * jax.random.normal(kk, (D,)) for n, kk in zip(
        ("lq1", "lk1", "lq2", "lk2"), ks[3:7])}
    w["subln"] = 1.0 + 0.2 * jax.random.normal(ks[7], (2 * D,))
    return q, k, v, w


def through_the_kernels(q, k, v, w, window):
    heads = layer._pair_heads(q, half=D)
    assert heads.shape == (1, S, 2 * H * D)
    ctx_ = fa.flash_attention(heads, k, v, causal=True, scale=D ** -0.5,
                              num_heads=H, window=window)
    assert ctx_ is not None, "the kernels refused the pair view"
    return layer._differ(ctx_, w["lq1"], w["lk1"], w["lq2"], w["lk2"],
                         w["subln"], width=2 * D,
                         lam_init=layer.lambda_init(INDEX), eps=1e-5)


@pytest.mark.parametrize("window", [None, 96])
def test_the_kernels_on_pair_heads_are_two_plain_softmaxes(operands, window):
    q, k, v, w = operands
    got = through_the_kernels(q, k, v, w, window)
    with jax.default_matmul_precision("highest"):
        want, *edges = (ref.differential(q, k, v, w, C, INDEX, win, _mm)
                        for win in ((window,) if window is None
                                    else (window, window - 1, window + 1)))
    assert got.shape == want.shape == (1, S, H * D)
    assert gap(got, want) < 2e-5
    # the position's own key is among the window's: one key fewer or one more
    # is another result, at every row past the window's width
    for edge in edges:
        assert gap(got[:, window:], edge[:, window:]) > 1e-3
        assert gap(got[:, :window - 1], edge[:, :window - 1]) < 2e-5


@pytest.mark.parametrize("without", ["subtract", "sub_norm"])
def test_no_path_leaves_out_the_second_softmax_or_the_sub_norm(operands,
                                                               without):
    """The reference with the piece left out is far from what the kernels'
    path computes (which is the whole reference's, above)."""
    q, k, v, w = operands
    whole = through_the_kernels(q, k, v, w, None)
    with jax.default_matmul_precision("highest"):
        want = ref.differential(q, k, v, w, C, INDEX, None, _mm,
                                without=(without,))
    assert gap(whole, want) > 0.1
    # and the (1 - lambda_init) factor is the published index's
    assert layer.lambda_init(INDEX) == pytest.approx(0.79333, abs=1e-4)
    assert layer.lambda_init(1) == pytest.approx(0.35551, abs=1e-4)


def layer_output(name, window=None, cross=False):
    """The layer's graph on the CPU (the ``jax.numpy`` products on the free
    view by heads) and the reference's output on its weights."""
    hidden, heads, kv, s = 64, 4, 2, 64
    x = ht.placeholder_op(f"{name}_x", (1, s, hidden))
    source = DifferentialAttention(hidden, heads, kv, 17, s,
                                   name=f"{name}_src")
    attn = DifferentialAttention(hidden, heads, kv, INDEX, s, window=window,
                                 cross=cross, name=name)
    handed = source(x)
    out = attn(x, source.keys, source.values) if cross else attn(x)
    ex = ht.Executor({"f": [out, handed]}, seed=5)
    for key, value in list(ex.params.items()):
        if key.endswith(("_bias", "subln_scale")):
            ex.params[key] = value + 0.2 * jax.random.normal(
                jax.random.PRNGKey(len(key)), value.shape)
    xs = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (1, s, hidden)))
    got = ex.run("f", feed_dict={x: xs}, convert_to_numpy_ret_vals=True)[0]
    c = {"num_attention_heads": heads, "num_key_value_heads": kv,
         "hidden_size": hidden, "layer_norm_eps": 1e-5}

    def weights(m):
        named = dict(zip(("lq1", "lk1", "lq2", "lk2"), m.lambdas),
                     qkv=m.qkv_proj.weight, qkv_bias=m.qkv_proj.bias,
                     o=m.out_proj.weight, o_bias=m.out_proj.bias,
                     subln=m.sub_norm)
        return {k_: ex.params[v_.name] for k_, v_ in named.items()}
    shared = {}
    with jax.default_matmul_precision("highest"):
        ref.attention(jnp.asarray(xs), weights(source), c, 17, 0, "full",
                      _mm, shared)
        want = ref.attention(jnp.asarray(xs), weights(attn), c, INDEX, 0,
                             "cross" if cross else "window", _mm, shared,
                             window=window)
    ex.close()
    return got, want, attn


@pytest.mark.parametrize("window, cross", [(None, False), (16, False),
                                           (None, True)])
def test_the_layer_is_the_reference(window, cross):
    name = f"da_{window}_{cross}"
    got, want, attn = layer_output(name, window, cross)
    assert gap(got, want) < 1e-5
    assert attn.cross is cross and attn.window == window
    assert (attn.qkv_proj.weight.shape[1]
            == (64 if cross else 64 + 2 * 32))


@pytest.mark.parametrize("heads, kv, head_dim, keep, mask, reason", [
    (40, 20, 64, 1.0, None, None),
    (40, 20, 128, 1.0, None, None),
    (39, 20, 64, 1.0, None, "pair_heads_odd"),
    (40, 5, 64, 1.0, None, "pair_heads_odd"),
    (40, 20, 32, 1.0, None, "pair_head_dim_not_64_aligned"),
    (40, 20, 96, 1.0, None, "pair_head_dim_not_64_aligned"),
    (40, 20, 64, 0.9, None, "pair_with_dropout"),
    (40, 20, 64, 1.0, "a key mask", "pair_with_key_mask")])
def test_what_the_pair_view_asks(heads, kv, head_dim, keep, mask, reason):
    assert fa.pair_view_unsupported(heads, kv, head_dim, keep, mask) == reason


def test_grouped_heads_of_64_are_refused_where_a_pair_is_not():
    """40 query heads on 20 key heads of 64 as they are: the reason the pair
    view exists; as 40 halves of 128 on 10 key pairs of 128 the same operands
    are taken."""
    def views(heads, kv, d):
        return (jax.ShapeDtypeStruct((1, heads, 256, d), jnp.bfloat16),
                *(jax.ShapeDtypeStruct((1, kv, 256, d), jnp.bfloat16),) * 2)
    assert fa.unsupported(*views(40, 20, 64)) == (
        "grouped_head_dim_not_128_aligned")
    assert fa.unsupported(*views(40, 10, 128)) is None
    assert fa.unsupported(*views(40, 10, 128), window=512) is None


def test_what_is_counted():
    telemetry.enable()
    try:
        def series(name, label):
            metric = telemetry.get_registry().snapshot().get(
                name, {"samples": []})
            return {s["labels"][label]: s["value"]
                    for s in metric["samples"]}
        before = series("hetu_attn_layers_total", "kind")
        x = ht.placeholder_op("da_count_x", (1, 64, 64))
        full = DifferentialAttention(64, 4, 2, 17, 64, name="da_count_full")
        win = DifferentialAttention(64, 4, 2, 15, 64, window=16,
                                    name="da_count_win")
        cross = DifferentialAttention(64, 4, 2, 19, 64, cross=True,
                                      name="da_count_cross")
        outs = [full(x), win(x), cross(x, full.keys, full.values)]
        after = series("hetu_attn_layers_total", "kind")
        for kind in ("differential_full", "differential_window",
                     "differential_cross"):
            assert after[kind] - before.get(kind, 0) == 1, kind
        assert after.get("full", 0) == before.get("full", 0)
        assert [o.scope for o in outs] == ["hetu_attn", "hetu_window_attn",
                                           "hetu_attn"]
        # the toy's pair is 32 lanes: the kernels would not read it in place
        assert full.pair_view == "pair_head_dim_not_64_aligned"
        wide = DifferentialAttention(256, 4, 2, 17, 64, name="da_count_wide")
        assert wide.pair_view is None and wide.head_dim == 64
        assert wide.lambda_init == layer.lambda_init(17)
    finally:
        telemetry.shutdown()
