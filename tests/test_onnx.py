"""ONNX bridge round-trip tests (reference: tests/onnx/ — per-model
hetu->onnx->hetu equivalence checks; here through the neutral IR since the
`onnx` package is absent in the build image)."""

import os

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import onnx as hx
from hetu_tpu.layers import Linear, Conv2d, BatchNorm, Sequence, Relu


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _roundtrip(eval_nodes, ex, feeds, rng, tmp_path=None, proto=True):
    """Export -> real protobuf bytes (and optionally zip save/load) ->
    import -> compare outputs."""
    model = hx.hetu2onnx(eval_nodes, ex.params)
    if proto:
        # through ACTUAL ModelProto wire bytes every time
        model = hx.deserialize_model(hx.serialize_model(model))
    if tmp_path is not None:
        p = str(tmp_path / "model.onnx.zip")
        hx.save_model(model, p)
        model = hx.load_model(p)
    placeholders, outs = hx.onnx2hetu(model)
    ex2 = ht.Executor(outs)
    feed2 = {placeholders[k.name]: v for k, v in feeds.items()}
    want = ex.run(feed_dict=feeds, convert_to_numpy_ret_vals=True)
    got = ex2.run(feed_dict=feed2, convert_to_numpy_ret_vals=True)
    for w, g in zip(want, got):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)
    return model


def test_mlp_roundtrip(rng, tmp_path):
    x = ht.placeholder_op("x", (4, 10))
    mlp = Sequence(Linear(10, 32), Relu(), Linear(32, 3))
    out = ht.softmax_op(mlp(x))
    ex = ht.Executor([out])
    model = _roundtrip([out], ex, {x: rng.standard_normal((4, 10))}, rng,
                       tmp_path)
    counts = model.summary()["op_counts"]
    assert counts.get("Gemm") == 2 and counts.get("Softmax") == 1


def test_cnn_bn_roundtrip(rng):
    x = ht.placeholder_op("img", (2, 3, 8, 8))
    conv = Conv2d(3, 4, 3, padding=1)
    bn = BatchNorm(4)
    y = ht.max_pool2d_op(ht.relu_op(bn(conv(x))), kernel_H=2, kernel_W=2,
                         stride=2)
    out = ht.reduce_mean_op(y, axes=(2, 3))
    ex = ht.Executor([out])   # inference graph: BN uses running stats
    _roundtrip([out], ex, {x: rng.standard_normal((2, 3, 8, 8))}, rng)


def test_embedding_reshape_roundtrip(rng):
    ids = ht.placeholder_op("ids", (4, 6), dtype=np.int32)
    table = ht.Variable("emb_table", shape=(50, 8),
                        initializer=ht.init.normal(0.0, 0.1))
    e = ht.embedding_lookup_op(table, ids)
    out = ht.reduce_sum_op(
        ht.array_reshape_op(e, output_shape=(4, 48)), axes=1)
    ex = ht.Executor([out])
    _roundtrip([out], ex, {ids: rng.integers(0, 50, (4, 6))}, rng)


def test_elementwise_and_consts_roundtrip(rng):
    x = ht.placeholder_op("x2", (3, 5))
    out = ht.tanh_op(x * 2.0 + 1.5)
    out = ht.clamp_op(out, min=-0.9, max=0.9)
    out = ht.pow_op(out, exponent=2.0)
    ex = ht.Executor([out])
    _roundtrip([out], ex, {x: rng.standard_normal((3, 5))}, rng)


def test_transpose_concat_roundtrip(rng):
    a = ht.placeholder_op("a", (2, 3))
    b = ht.placeholder_op("b", (2, 3))
    cat = ht.concatenate_op([a, b], axis=1)
    out = ht.transpose_op(cat, perm=(1, 0))
    ex = ht.Executor([out])
    _roundtrip([out], ex, {a: rng.standard_normal((2, 3)),
                           b: rng.standard_normal((2, 3))}, rng)


def test_unsupported_op_raises():
    x = ht.placeholder_op("x3", (4, 4))
    out = ht.binary_step_op(x)   # no ONNX equivalent registered
    ex = ht.Executor([out])
    with pytest.raises(NotImplementedError, match="binary_step"):
        hx.hetu2onnx([out], ex.params)


def test_proto_gated():
    assert isinstance(hx.HAS_ONNX, bool)
    if not hx.HAS_ONNX:
        with pytest.raises(ImportError, match="onnx"):
            hx.to_onnx_proto(hx.OnnxModel())


def test_onnx_file_roundtrip_bert_block(rng, tmp_path):
    """BERT-style block -> real .onnx protobuf FILE -> import, numerics
    equal (the reference's tests/onnx hetu<->onnx<->tf loops; here the
    protobuf itself is exercised without the onnx package)."""
    from hetu_tpu.layers import TransformerLayer
    B, S, H = 2, 8, 16
    x = ht.placeholder_op("hx_in", (B, S, H))
    layer = TransformerLayer(H, 4, 32, seq_len=S, dropout_rate=0.0,
                             attn_dropout_rate=0.0, name="onnx_blk")
    out = layer(x, seq_len=S)
    ex = ht.Executor({"inference": [out]})
    model = hx.hetu2onnx([out], ex.params)

    p = str(tmp_path / "block.onnx")
    hx.save_onnx(model, p)
    back = hx.load_onnx(p)

    # serialized protobuf preserved the graph structurally
    assert back.summary()["op_counts"] == model.summary()["op_counts"]
    assert set(back.initializers) == set(model.initializers)
    for k, v in model.initializers.items():
        np.testing.assert_array_equal(np.asarray(v), back.initializers[k])

    placeholders, outs = hx.onnx2hetu(back)
    ex2 = ht.Executor({"inference": outs})
    X = rng.standard_normal((B, S, H)).astype(np.float32)
    want = ex.run("inference", feed_dict={x: X},
                  convert_to_numpy_ret_vals=True)[0]
    got = ex2.run("inference",
                  feed_dict={placeholders["hx_in"]: X},
                  convert_to_numpy_ret_vals=True)[0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_onnx_bytes_roundtrip_causal_gpt(rng):
    """Full GPT (causal attention, position slice, tied trans_B LM head)
    through ModelProto bytes."""
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel
    c = GPTConfig(vocab_size=64, hidden_size=16, num_layers=2,
                  num_heads=2, seq_len=8, dropout_prob=0.0)
    ids = ht.placeholder_op("gpt_ox_ids", (2, 8), dtype=np.int32)
    logits = GPTLMHeadModel(c, name="gpt_ox")(ids)
    ex = ht.Executor({"inference": [logits]})
    data = hx.serialize_model(hx.hetu2onnx([logits], ex.params))
    assert isinstance(data, bytes) and len(data) > 1000
    ph, outs = hx.onnx2hetu(hx.deserialize_model(data))
    ex2 = ht.Executor({"inference": outs})
    iv = rng.integers(0, 64, (2, 8))
    want = ex.run("inference", feed_dict={ids: iv},
                  convert_to_numpy_ret_vals=True)[0]
    got = ex2.run("inference", feed_dict={ph["gpt_ox_ids"]: iv},
                  convert_to_numpy_ret_vals=True)[0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_onnx_bytes_roundtrip_seq2seq(rng):
    """Encoder-decoder Transformer through ModelProto bytes: cross-
    attention (different q/kv lengths), pad-mask bias arithmetic, tied
    head — all standard opset ops (reference tests/onnx round-trips its
    transformer examples the same way)."""
    from hetu_tpu.models import Seq2SeqTransformer, TransformerConfig
    c = TransformerConfig(vocab_size=40, d_model=16, num_blocks=1,
                          num_heads=2, d_ff=32, src_len=10, tgt_len=6,
                          dropout_rate=0.0)
    model = Seq2SeqTransformer(c, name="s2sx")
    B = 2
    src = ht.placeholder_op("s2sx_src", (B, c.src_len), dtype=np.int32)
    tin = ht.placeholder_op("s2sx_tin", (B, c.tgt_len), dtype=np.int32)
    skeep = ht.placeholder_op("s2sx_skeep", (B, c.src_len))
    tkeep = ht.placeholder_op("s2sx_tkeep", (B, c.tgt_len))
    logits = model(src, tin, skeep, tkeep)
    ex = ht.Executor({"inference": [logits]})
    model_pb = hx.deserialize_model(
        hx.serialize_model(hx.hetu2onnx([logits], ex.params)))
    ph, outs = hx.onnx2hetu(model_pb)
    ex2 = ht.Executor({"inference": outs})
    sv = rng.integers(1, 40, (B, c.src_len))
    tv = rng.integers(1, 40, (B, c.tgt_len))
    sk = np.ones((B, c.src_len), np.float32)
    sk[:, -2:] = 0.0
    tk = np.ones((B, c.tgt_len), np.float32)
    feed = {src: sv, tin: tv, skeep: sk, tkeep: tk}
    want = ex.run("inference", feed_dict=feed,
                  convert_to_numpy_ret_vals=True)[0]
    got = ex2.run("inference", feed_dict={
        ph["s2sx_src"]: sv, ph["s2sx_tin"]: tv,
        ph["s2sx_skeep"]: sk, ph["s2sx_tkeep"]: tk},
        convert_to_numpy_ret_vals=True)[0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("kv_heads,tiles,hidden", [
    (2, 4, 16),     # grouped-query: the [B, H, S, D] graph, K/V tiled
    (4, None, 16),  # multi-head: attention on [B, S, H*D], RoPE on its view
    (2, 4, 512),    # grouped-query on heads of 128: [B, S, H*D] with K and
                    # V [B, S, KV*D], tiled where the attention is exported
])
def test_onnx_bytes_roundtrip_llama(rng, kv_heads, tiles, hidden):
    """Llama tier through ModelProto bytes: RMSNorm, RoPE (constant
    cos/sin tables + Slice/Neg/Concat rotation), GQA repeat_kv
    (Reshape/Tile/Reshape), SwiGLU — all as standard opset ops, so any
    ONNX consumer can run the modern-LLM tier."""
    from hetu_tpu.models import LlamaConfig, LlamaForCausalLM
    c = LlamaConfig(vocab_size=64, hidden_size=hidden, num_layers=2,
                    num_heads=4, num_kv_heads=kv_heads,
                    intermediate_size=32, seq_len=8)
    ids = ht.placeholder_op("llx_ids", (2, 8), dtype=np.int32)
    logits = LlamaForCausalLM(c, name=f"llx{kv_heads}_{hidden}")(ids)
    ex = ht.Executor({"inference": [logits]})
    model = hx.deserialize_model(
        hx.serialize_model(hx.hetu2onnx([logits], ex.params)))
    counts = model.summary()["op_counts"]
    # RoPE rotations (2/layer on q,k) and GQA tiles survived lowering
    assert counts.get("Neg") == 4 and counts.get("Tile") == tiles
    assert counts.get("Sigmoid") == 2          # SwiGLU silu
    ph, outs = hx.onnx2hetu(model)
    ex2 = ht.Executor({"inference": outs})
    iv = rng.integers(0, 64, (2, 8))
    want = ex.run("inference", feed_dict={ids: iv},
                  convert_to_numpy_ret_vals=True)[0]
    got = ex2.run("inference", feed_dict={ph["llx_ids"]: iv},
                  convert_to_numpy_ret_vals=True)[0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_onnx_bytes_roundtrip_llama_alibi(rng):
    """Baichuan-13B-style ALiBi variant: the bias lowers to a constant
    initializer (static shapes), everything else as in the RoPE test."""
    from hetu_tpu.models import LlamaConfig, LlamaForCausalLM
    c = LlamaConfig(vocab_size=64, hidden_size=16, num_layers=1,
                    num_heads=4, intermediate_size=32, seq_len=8,
                    position_embedding="alibi")
    ids = ht.placeholder_op("lax_ids", (2, 8), dtype=np.int32)
    logits = LlamaForCausalLM(c, name="lax")(ids)
    ex = ht.Executor({"inference": [logits]})
    model = hx.deserialize_model(
        hx.serialize_model(hx.hetu2onnx([logits], ex.params)))
    ph, outs = hx.onnx2hetu(model)
    ex2 = ht.Executor({"inference": outs})
    iv = rng.integers(0, 64, (2, 8))
    want = ex.run("inference", feed_dict={ids: iv},
                  convert_to_numpy_ret_vals=True)[0]
    got = ex2.run("inference", feed_dict={ph["lax_ids"]: iv},
                  convert_to_numpy_ret_vals=True)[0]
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_wire_attribute_kinds_roundtrip():
    """Every attribute kind the encoder supports survives the wire."""
    from hetu_tpu.onnx import wire
    cases = {
        "i": 7, "neg": -3, "f": 1.5, "s": "same_upper",
        "ints": (1, 2, -4), "floats": (0.5, -1.25), "strs": ("a", "bc"),
        "tensor": np.arange(6, dtype=np.float32).reshape(2, 3),
    }
    for k, v in cases.items():
        name, back = wire.dec_attribute(wire.enc_attribute(k, v))
        assert name == k
        if isinstance(v, np.ndarray):
            np.testing.assert_array_equal(back, v)
        elif isinstance(v, tuple) and isinstance(v[0], float):
            np.testing.assert_allclose(back, v)
        else:
            assert back == v, (k, back, v)


def test_wire_decodes_proto3_packed_and_default_fields():
    """External proto3 serializers pack repeated scalars and OMIT zero
    scalars; the decoder must read both forms."""
    from hetu_tpu.onnx import wire
    # packed dims: field 1, LEN, varints 2 and 3
    packed_dims = (wire._enc_key(1, 2) + wire._enc_varint(2)
                   + wire._enc_varint(2) + wire._enc_varint(3))
    tensor = (packed_dims + wire._enc_int(2, 1)
              + wire._enc_bytes(9, np.zeros(6, "<f4").tobytes()))
    name, arr = wire.dec_tensor(tensor)
    assert arr.shape == (2, 3)
    # omitted zero scalar: attr {name: 'axis', type: INT} with no i field
    attr = wire._enc_str(1, "axis") + wire._enc_int(20, 2)
    name, val = wire.dec_attribute(attr)
    assert name == "axis" and val == 0
    attr_f = wire._enc_str(1, "eps") + wire._enc_int(20, 1)
    assert wire.dec_attribute(attr_f) == ("eps", 0.0)
    # non-default opset domains must not clobber the ai.onnx opset
    opset_ms = wire._enc_bytes(8, wire._enc_str(1, "com.microsoft")
                               + wire._enc_int(2, 1))
    opset_onnx = wire._enc_bytes(8, wire._enc_str(1, "")
                                 + wire._enc_int(2, 17))
    from hetu_tpu.onnx.ir import OnnxModel
    body = wire._enc_bytes(7, wire.enc_graph(OnnxModel()))
    _, opset = wire.dec_model(body + opset_onnx + opset_ms)
    assert opset == 17


def test_wire_dynamic_dims_roundtrip():
    """dim_param (symbolic batch) dims decode as None, not 0."""
    from hetu_tpu.onnx import wire
    vi = wire.enc_value_info("x", 1, (None, 16))
    name, elem, shape = wire.dec_value_info(vi)
    assert name == "x" and shape == (None, 16)


def test_wire_tensor_dtypes_roundtrip(rng):
    from hetu_tpu.onnx import wire
    for dtype in ("float32", "float64", "int32", "int64", "uint8",
                  "bool", "float16"):
        arr = (rng.random((3, 4)) * 10).astype(dtype)
        name, back = wire.dec_tensor(wire.enc_tensor("t", arr))
        assert name == "t" and back.dtype == arr.dtype
        np.testing.assert_array_equal(back, arr)


def test_onnx_export_keeps_shapes_for_remat_graphs():
    # regression: shape inference must bypass remat grouping (interior
    # group nodes aren't bound in the grouped env)
    import hetu_tpu as ht
    from hetu_tpu.onnx import hetu2onnx

    x = ht.placeholder_op("oxr", (2, 4))
    w = ht.Variable("owr", value=np.ones((4, 4), np.float32))
    with ht.remat():
        h = ht.relu_op(ht.matmul_op(x, w))
        h2 = ht.relu_op(ht.matmul_op(h, w))
    ex = ht.Executor([h2])
    from hetu_tpu.onnx.export import _infer_shapes
    shapes = _infer_shapes([h2], ex.params)
    assert shapes.get(h) == (2, 4) and shapes.get(h2) == (2, 4), shapes
    # and the full export still round-trips
    model = hetu2onnx([h2], ex.params)
    assert model.summary()["num_nodes"] > 0


# -- external validation: the REAL protobuf runtime ------------------------
# The reference proves interop by round-tripping through another
# implementation (tests/onnx/ goes hetu->onnx->tensorflow).  The `onnx`
# package is absent here, so the external implementation is protoc +
# google.protobuf: wire.py's bytes must parse under the real ONNX schema,
# and bytes the real runtime serializes (proto3 packed encoding, different
# field order) must decode with wire.py.  A symmetric codec bug (wrong
# field number, wrong wire type) fails these immediately.

@pytest.fixture(scope="module")
def onnx_pb(tmp_path_factory):
    import shutil
    import subprocess
    import sys
    if shutil.which("protoc") is None:
        pytest.skip("protoc not available")
    pytest.importorskip("google.protobuf")
    import hetu_tpu.onnx as _hx
    proto_dir = os.path.dirname(_hx.__file__)
    out = str(tmp_path_factory.mktemp("onnxpb"))
    subprocess.run(
        ["protoc", f"--python_out={out}", f"--proto_path={proto_dir}",
         "onnx_subset.proto"], check=True)
    sys.path.insert(0, out)
    try:
        import onnx_subset_pb2
        yield onnx_subset_pb2
    finally:
        sys.path.remove(out)


def _export_mlp(rng):
    x = ht.placeholder_op("xpb", (4, 10))
    mlp = Sequence(Linear(10, 32, name="pb_l1"), Relu(),
                   Linear(32, 3, name="pb_l2"))
    out = ht.softmax_op(mlp(x))
    ex = ht.Executor([out])
    feeds = {x: rng.standard_normal((4, 10)).astype(np.float32)}
    return out, ex, feeds


def test_wire_bytes_parse_with_real_protobuf(onnx_pb, rng):
    out, ex, feeds = _export_mlp(rng)
    model = hx.hetu2onnx([out], ex.params)
    data = hx.serialize_model(model)

    m = onnx_pb.ModelProto()
    m.ParseFromString(data)
    assert m.ir_version == 10
    assert m.producer_name == "hetu_tpu"
    assert [op.version for op in m.opset_import] == [model.opset]
    g = m.graph
    assert [n.op_type for n in g.node] == [n.op_type for n in model.nodes]
    for pb_n, ir_n in zip(g.node, model.nodes):
        assert list(pb_n.input) == list(ir_n.inputs)
        assert list(pb_n.output) == list(ir_n.outputs)
    # initializers byte-exact against executor params
    assert {t.name for t in g.initializer} == set(model.initializers)
    for t in g.initializer:
        want = np.asarray(model.initializers[t.name])
        got = np.frombuffer(t.raw_data,
                            dtype=np.dtype("float32").newbyteorder("<"))
        np.testing.assert_array_equal(got.reshape(tuple(t.dims)), want)
    # graph inputs carry tensor types + shapes under the real schema
    (inp,) = [vi for vi in g.input if vi.name == "xpb"]
    assert inp.type.tensor_type.elem_type == 1
    assert [d.dim_value for d in inp.type.tensor_type.shape.dim] == [4, 10]


def test_real_protobuf_bytes_decode_with_wire_and_execute(onnx_pb, rng):
    """Full circle through the EXTERNAL codec: our bytes -> real protobuf
    parse -> real protobuf re-serialize (proto3 packed, canonical order)
    -> wire.py decode -> import -> execute; outputs must match the
    original graph."""
    out, ex, feeds = _export_mlp(rng)
    data = hx.serialize_model(hx.hetu2onnx([out], ex.params))
    m = onnx_pb.ModelProto()
    m.ParseFromString(data)
    external_bytes = m.SerializeToString()   # packed/canonical encoding
    assert external_bytes != data            # genuinely different encoding

    model2 = hx.deserialize_model(external_bytes)
    placeholders, outs = hx.onnx2hetu(model2)
    ex2 = ht.Executor(outs)
    want = ex.run(feed_dict=feeds, convert_to_numpy_ret_vals=True)
    got = ex2.run(feed_dict={placeholders[k.name]: v
                             for k, v in feeds.items()},
                  convert_to_numpy_ret_vals=True)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)


def test_real_protobuf_authored_model_imports(onnx_pb):
    """A model AUTHORED with the real protobuf API (packed dims/ints,
    float_data instead of raw_data, attribute defaults omitted) — the
    shapes an external exporter would produce — must import and run."""
    pb = onnx_pb
    m = pb.ModelProto()
    m.ir_version = 10
    m.opset_import.add(version=17)
    g = m.graph
    g.name = "ext"
    w = g.initializer.add()
    w.name = "W"
    w.dims.extend([3, 2])
    w.data_type = 1
    w.float_data.extend([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])  # no raw_data
    n1 = g.node.add(op_type="MatMul", input=["x", "W"], output=["h"])
    n2 = g.node.add(op_type="Relu", input=["h"], output=["y"])
    assert n1.op_type and n2.op_type
    vi = g.input.add(name="x")
    vi.type.tensor_type.elem_type = 1
    vi.type.tensor_type.shape.dim.add().dim_value = 4
    vi.type.tensor_type.shape.dim.add().dim_value = 3
    g.output.add(name="y")

    model = hx.deserialize_model(m.SerializeToString())
    placeholders, outs = hx.onnx2hetu(model)
    ex = ht.Executor(outs)
    X = np.arange(12, dtype=np.float32).reshape(4, 3)
    (got,) = ex.run(feed_dict={list(placeholders.values())[0]: X},
                    convert_to_numpy_ret_vals=True)
    np.testing.assert_allclose(
        got, np.maximum(X @ np.arange(1.0, 7.0,
                                      dtype=np.float32).reshape(3, 2), 0))
