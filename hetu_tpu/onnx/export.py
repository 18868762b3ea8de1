"""hetu_tpu graph -> OnnxModel (reference: python/hetu/onnx/hetu2onnx.py).

Each graph Op kind has a converter emitting ONNX-shaped NodeIR(s).  Variable
values come from an Executor's params (or any {name: array} dict), so the
exported file carries trained weights like the reference's bridge.
"""

from __future__ import annotations

import numpy as np

from ..graph.node import Op, PlaceholderOp, VariableOp, find_topo_sort
from ..ops.base import SimpleOp
from ..ops.nn import BatchNormOp, DropoutOp
from ..ops.attention import ScaledDotProductAttentionOp
from .ir import OnnxModel, NodeIR, TensorInfo

_EXPORTERS = {}


def exporter(*kinds):
    def deco(fn):
        for k in kinds:
            _EXPORTERS[k] = fn
        return fn
    return deco


class _Ctx:
    def __init__(self, model, shapes=None):
        self.model = model
        self.shapes = shapes or {}     # Op -> inferred shape tuple
        self._n = 0

    def aux(self, hint):
        self._n += 1
        return f"{hint}_{self._n}"

    def const(self, hint, value):
        name = self.aux(hint)
        self.model.add_initializer(name, value)
        return name


def _in(node, i):
    return node.inputs[i].name


def _simple(onnx_type, **fixed):
    def fn(node, ctx):
        return [NodeIR(onnx_type, [i.name for i in node.inputs],
                       [node.name], dict(fixed), name=node.name)]
    return fn


for kind, typ in [
        ("add", "Add"), ("minus", "Sub"), ("multiply", "Mul"),
        ("divide", "Div"),
        ("relu", "Relu"), ("sigmoid", "Sigmoid"), ("tanh", "Tanh"),
        ("exp", "Exp"), ("log", "Log"), ("sqrt", "Sqrt"),
        ("abs", "Abs"), ("sign", "Sign"), ("floor", "Floor"),
        ("ceil", "Ceil"), ("softplus", "Softplus"),
        ("opposite", "Neg"), ("reciprocal", "Reciprocal"),
        ("maximum", "Max"), ("minimum", "Min"), ("where", "Where"),
        ("embedding_lookup", "Gather"), ("flatten", "Flatten"),
        ("bool_eq", "Equal"), ("bool_gt", "Greater"), ("bool_lt", "Less"),
        ("stop_gradient", "Identity"), ("zeros_like", "Identity")]:
    _EXPORTERS[kind] = _simple(typ)


@exporter("matmul", "batch_matmul")
def _matmul(node, ctx):
    """MatMul honoring trans_A/trans_B attrs (the tied LM head uses
    h @ table^T): emit explicit Transpose nodes on the transposed side."""
    names = [node.inputs[0].name, node.inputs[1].name]
    out = []
    for slot, key in ((0, "trans_A"), (1, "trans_B")):
        if node.attrs.get(key):
            shp = ctx.shapes.get(node.inputs[slot])
            if shp is None:
                raise NotImplementedError(
                    f"matmul export for {node.name} with {key} needs "
                    "inferable shapes (declare placeholder shapes)")
            ndim = len(shp)
            perm = tuple(range(ndim - 2)) + (ndim - 1, ndim - 2)
            t = ctx.aux(f"{node.name}_t{slot}")
            out.append(NodeIR("Transpose", [names[slot]], [t],
                              {"perm": perm}))
            names[slot] = t
    out.append(NodeIR("MatMul", names, [node.name], name=node.name))
    return out


@exporter("gelu")
def _gelu(node, ctx):
    # Gelu is a standard op from opset 20 (model.opset is 20)
    approx = "tanh" if node.attrs.get("approximate", True) else "none"
    return [NodeIR("Gelu", [_in(node, 0)], [node.name],
                   {"approximate": approx}, name=node.name)]


@exporter("silu")
def _silu(node, ctx):
    # silu(x) = x * sigmoid(x); no standard SiLU op -> decompose
    sig = ctx.aux(f"{node.name}_sig")
    return [NodeIR("Sigmoid", [_in(node, 0)], [sig],
                   name=f"{node.name}_sigmoid"),
            NodeIR("Mul", [_in(node, 0), sig], [node.name],
                   name=node.name)]


@exporter("add_byconst", "mul_byconst")
def _byconst(node, ctx):
    typ = "Add" if node.op_kind == "add_byconst" else "Mul"
    c = ctx.const(f"{node.name}_const",
                  np.asarray(node.attrs["const"], np.float32))
    return [NodeIR(typ, [_in(node, 0), c], [node.name], name=node.name)]


@exporter("pow")
def _pow(node, ctx):
    c = ctx.const(f"{node.name}_exp",
                  np.asarray(node.attrs["exponent"], np.float32))
    return [NodeIR("Pow", [_in(node, 0), c], [node.name], name=node.name)]


@exporter("linear")
def _linear(node, ctx):
    # Gemm(A, B, C): alpha*A@B + beta*C with transA/transB
    return [NodeIR("Gemm", [i.name for i in node.inputs], [node.name],
                   {"alpha": 1.0, "beta": 1.0,
                    "transA": int(bool(node.attrs.get("trans_A", False))),
                    "transB": int(bool(node.attrs.get("trans_B", False)))},
                   name=node.name)]


@exporter("softmax")
def _softmax(node, ctx):
    return [NodeIR("Softmax", [_in(node, 0)], [node.name],
                   {"axis": node.attrs.get("dim", -1)}, name=node.name)]


@exporter("log_softmax")
def _log_softmax(node, ctx):
    return [NodeIR("LogSoftmax", [_in(node, 0)], [node.name],
                   {"axis": node.attrs.get("dim", -1)}, name=node.name)]


@exporter("array_reshape")
def _reshape(node, ctx):
    shape = ctx.const(f"{node.name}_shape",
                      np.asarray(node.attrs["output_shape"], np.int64))
    return [NodeIR("Reshape", [_in(node, 0), shape], [node.name],
                   name=node.name)]


@exporter("transpose")
def _transpose(node, ctx):
    return [NodeIR("Transpose", [_in(node, 0)], [node.name],
                   {"perm": list(node.attrs.get("perm"))}, name=node.name)]


@exporter("concat", "concatenate")
def _concat(node, ctx):
    return [NodeIR("Concat", [i.name for i in node.inputs], [node.name],
                   {"axis": node.attrs.get("axis", 0)}, name=node.name)]


@exporter("expand_dims")
def _unsqueeze(node, ctx):
    ax = node.attrs.get("axis", 0)
    axes = ctx.const(f"{node.name}_axes",
                     np.asarray([ax] if np.isscalar(ax) else list(ax),
                                np.int64))
    return [NodeIR("Unsqueeze", [_in(node, 0), axes], [node.name],
                   name=node.name)]


@exporter("squeeze")
def _squeeze(node, ctx):
    ax = node.attrs.get("axis")
    ins = [_in(node, 0)]
    if ax is not None:
        ins.append(ctx.const(
            f"{node.name}_axes",
            np.asarray([ax] if np.isscalar(ax) else list(ax), np.int64)))
    return [NodeIR("Squeeze", ins, [node.name], name=node.name)]


def _pair(v):
    return (v, v) if np.isscalar(v) else tuple(v)


@exporter("conv2d", "conv2d_add_bias")
def _conv(node, ctx):
    p = _pair(node.attrs.get("padding", 0))
    s = _pair(node.attrs.get("stride", 1))
    return [NodeIR("Conv", [i.name for i in node.inputs], [node.name],
                   {"pads": [p[0], p[1], p[0], p[1]],
                    "strides": list(s),
                    "group": node.attrs.get("groups", 1)},
                   name=node.name)]


@exporter("head_split_linear")
def _head_split_linear(node, ctx):
    # decomposes to MatMul (+Add) + Reshape + Transpose — all standard
    # ONNX ops the importer round-trips
    nh = node.attrs["n_heads"]
    hd = node.attrs["head_dim"]
    seq = node.attrs["seq_len"]
    mm = f"{node.name}_mm"
    nodes = [NodeIR("MatMul", [node.inputs[0].name, node.inputs[1].name],
                    [mm], name=mm)]
    cur = mm
    if len(node.inputs) > 2:
        ad = f"{node.name}_bias"
        nodes.append(NodeIR("Add", [cur, node.inputs[2].name], [ad],
                            name=ad))
        cur = ad
    shp = ctx.const(f"{node.name}_shape",
                    np.asarray([-1, seq, nh, hd], np.int64))
    rs = f"{node.name}_rs"
    nodes.append(NodeIR("Reshape", [cur, shp], [rs], name=rs))
    nodes.append(NodeIR("Transpose", [rs], [node.name],
                        {"perm": [0, 2, 1, 3]}, name=node.name))
    return nodes


@exporter("conv2d_hwio", "conv2d_hwio_add_bias")
def _conv_hwio(node, ctx):
    # layer weights are stored HWIO (TPU-native); ONNX Conv wants OIHW —
    # emit an explicit Transpose on the weight input
    p = _pair(node.attrs.get("padding", 0))
    s = _pair(node.attrs.get("stride", 1))
    wname = node.inputs[1].name
    tname = f"{node.name}_w_oihw"
    tr = NodeIR("Transpose", [wname], [tname], {"perm": [3, 2, 0, 1]},
                name=tname)
    ins = [node.inputs[0].name, tname] + [i.name for i in node.inputs[2:]]
    return [tr, NodeIR("Conv", ins, [node.name],
                       {"pads": [p[0], p[1], p[0], p[1]],
                        "strides": list(s),
                        "group": node.attrs.get("groups", 1)},
                       name=node.name)]


@exporter("max_pool2d", "avg_pool2d")
def _pool(node, ctx):
    typ = "MaxPool" if node.op_kind == "max_pool2d" else "AveragePool"
    p = _pair(node.attrs.get("padding", 0))
    s = _pair(node.attrs.get("stride", 1))
    k = (node.attrs["kernel_H"], node.attrs["kernel_W"])
    return [NodeIR(typ, [_in(node, 0)], [node.name],
                   {"kernel_shape": list(k), "pads": [p[0], p[1], p[0], p[1]],
                    "strides": list(s)}, name=node.name)]


@exporter("global_avg_pool2d")
def _gap(node, ctx):
    if node.attrs.get("channels_last"):
        raise NotImplementedError(
            "ONNX export supports NCHW global_avg_pool2d only; rebuild "
            "the model with channels_last=False for export")
    return [NodeIR("GlobalAveragePool", [_in(node, 0)], [node.name],
                   name=node.name)]


@exporter("layer_normalization")
def _ln(node, ctx):
    return [NodeIR("LayerNormalization", [i.name for i in node.inputs],
                   [node.name], {"epsilon": node.attrs.get("eps", 1e-5),
                                 "axis": -1}, name=node.name)]


@exporter("reduce_mean", "reduce_sum", "reduce_max", "reduce_min")
def _reduce(node, ctx):
    typ = {"reduce_mean": "ReduceMean", "reduce_sum": "ReduceSum",
           "reduce_max": "ReduceMax", "reduce_min": "ReduceMin"}[node.op_kind]
    axes = node.attrs.get("axes")
    attrs = {"keepdims": int(bool(node.attrs.get("keepdims", False)))}
    ins = [_in(node, 0)]
    if axes is not None:
        # opset >= 18: axes are a tensor input for all Reduce* ops
        ins.append(ctx.const(
            f"{node.name}_axes",
            np.asarray([axes] if np.isscalar(axes) else list(axes),
                       np.int64)))
    return [NodeIR(typ, ins, [node.name], attrs, name=node.name)]


@exporter("cast")
def _cast(node, ctx):
    return [NodeIR("Cast", [_in(node, 0)], [node.name],
                   {"to": str(np.dtype(node.attrs.get("dtype", "float32")))},
                   name=node.name)]


@exporter("clamp")
def _clip(node, ctx):
    ins = [_in(node, 0)]
    for key in ("min", "max"):
        v = node.attrs.get(key)
        ins.append(ctx.const(f"{node.name}_{key}",
                             np.asarray(v, np.float32))
                   if v is not None else "")
    return [NodeIR("Clip", ins, [node.name], name=node.name)]


@exporter("one_hot")
def _one_hot(node, ctx):
    depth = ctx.const(f"{node.name}_depth",
                      np.asarray(node.attrs["num_classes"], np.int64))
    values = ctx.const(f"{node.name}_values",
                       np.asarray([0.0, 1.0], np.float32))
    return [NodeIR("OneHot", [_in(node, 0), depth, values], [node.name],
                   {"axis": -1}, name=node.name)]


@exporter("tile")
def _tile(node, ctx):
    reps = ctx.const(f"{node.name}_reps",
                     np.asarray(node.attrs["reps"], np.int64))
    return [NodeIR("Tile", [_in(node, 0), reps], [node.name],
                   name=node.name)]


@exporter("rms_norm")
def _rms_norm_exp(node, ctx):
    """x / sqrt(mean(x^2) + eps) * scale as standard ONNX ops (no RMSNorm
    in mainline opsets), so any consumer — and our importer — runs the
    Llama tier's normalization without custom ops."""
    x, g = _in(node, 0), _in(node, 1)
    eps = float(node.attrs.get("eps", 1e-6))
    sq, mn, ve, sd, nm = (ctx.aux(f"{node.name}_{h}")
                          for h in ("sq", "mean", "vareps", "std", "norm"))
    axes = ctx.const(f"{node.name}_axes", np.asarray([-1], np.int64))
    epsc = ctx.const(f"{node.name}_eps", np.asarray(eps, np.float32))
    return [
        NodeIR("Mul", [x, x], [sq]),
        NodeIR("ReduceMean", [sq, axes], [mn], {"keepdims": 1}),
        NodeIR("Add", [mn, epsc], [ve]),
        NodeIR("Sqrt", [ve], [sd]),
        NodeIR("Div", [x, sd], [nm]),
        NodeIR("Mul", [nm, g], [node.name], name=node.name),
    ]


def _rotary_nodes(ctx, hint, x, out, shape, seq_axis, theta, off=0):
    """``out = rotary(x)`` for ``x`` of ``shape`` (HF rotate_half
    convention): the cos/sin tables are precomputed constants (shapes are
    static), the rotation is Slice/Neg/Concat/Mul/Add — plain opset ops
    (ops/rotary.py ``_rotary``)."""
    s, d = int(shape[seq_axis]), int(shape[-1])
    pos = np.arange(off, off + s, dtype=np.float32)
    inv = 1.0 / (theta ** (np.arange(0, d, 2, dtype=np.float32) / d))
    freqs = np.outer(pos, inv)
    along = [1] * len(shape)
    along[seq_axis], along[-1] = s, d
    emb = np.concatenate([freqs, freqs], axis=-1).reshape(along)
    cosc = ctx.const(f"{hint}_cos", np.cos(emb).astype(np.float32))
    sinc = ctx.const(f"{hint}_sin", np.sin(emb).astype(np.float32))
    ax = ctx.const(f"{hint}_ax", np.asarray([-1], np.int64))
    s0 = ctx.const(f"{hint}_0", np.asarray([0], np.int64))
    sh = ctx.const(f"{hint}_h", np.asarray([d // 2], np.int64))
    sd_ = ctx.const(f"{hint}_d", np.asarray([d], np.int64))
    x1, x2, neg, rot, xc, rs = (ctx.aux(f"{hint}_{h}") for h in
                                ("x1", "x2", "neg", "rot", "xcos", "rsin"))
    return [
        NodeIR("Slice", [x, s0, sh, ax], [x1]),
        NodeIR("Slice", [x, sh, sd_, ax], [x2]),
        NodeIR("Neg", [x2], [neg]),
        NodeIR("Concat", [neg, x1], [rot], {"axis": -1}),
        NodeIR("Mul", [x, cosc], [xc]),
        NodeIR("Mul", [rot, sinc], [rs]),
        NodeIR("Add", [xc, rs], [out], name=out),
    ]


@exporter("rotary_embedding")
def _rotary_exp(node, ctx):
    """RoPE on [B, H, S, D], or on [B, S, H, D] with ``seq_axis=1``."""
    shape = ctx.shapes.get(node.inputs[0])
    if shape is None:
        raise NotImplementedError(
            "rotary_embedding export needs inferred shapes "
            "(placeholders must declare shapes)")
    return _rotary_nodes(ctx, node.name, _in(node, 0), node.name, shape,
                         int(node.attrs.get("seq_axis", -2)),
                         float(node.attrs.get("theta", 10000.0)),
                         int(node.attrs.get("pos_offset", 0)))


@exporter("rope_tables")
def _rope_tables_exp(node, ctx):
    """The kernels' tables: ``rotary_pair`` exports constants of its own."""
    return []


@exporter("rotary_pair")
def _rotary_pair_exp(node, ctx):
    """q and k ``[B, S, H D]`` rotated on their ``[B, S, H, D]`` views, as
    the outputs ``<name>_0`` and ``<name>_1`` that ``pair_item`` reads."""
    tables = ctx.shapes.get(node.inputs[2])
    if tables is None:
        raise NotImplementedError("rotary_pair export needs inferred shapes")
    for what in ("rotary_dim", "scaling"):
        if node.attrs.get(what) is not None:
            raise NotImplementedError(
                f"rotary_pair export with {what}={node.attrs[what]!r}: only "
                "the plain rotation of a whole head is exported")
    s, d = (int(v) for v in tables[1:])
    out = []
    for i in range(2):
        hint = f"{node.name}_{i}"
        width = int(ctx.shapes[node.inputs[i]][-1])
        by_head = ctx.const(f"{hint}_by_head",
                            np.asarray([-1, s, width // d, d], np.int64))
        flat = ctx.const(f"{hint}_flat", np.asarray([-1, s, width], np.int64))
        view, turned = ctx.aux(f"{hint}_view"), ctx.aux(f"{hint}_turned")
        out += [NodeIR("Reshape", [_in(node, i), by_head], [view])]
        out += _rotary_nodes(ctx, hint, view, turned, (1, s, width // d, d),
                             1, float(node.attrs["theta"]))
        out += [NodeIR("Reshape", [turned, flat], [hint], name=hint)]
    return out


@exporter("pair_item")
def _pair_item_exp(node, ctx):
    return [NodeIR("Identity", [f"{_in(node, 0)}_{node.attrs['index']}"],
                   [node.name], name=node.name)]


@exporter("repeat_kv")
def _repeat_kv_exp(node, ctx):
    """GQA K/V head repetition: Reshape → Tile → Reshape (the broadcast
    trick of ops/rotary.py:48 has no ONNX spelling; Tile is the portable
    equivalent)."""
    n = int(node.attrs["n_rep"])
    if n == 1:
        return [NodeIR("Identity", [_in(node, 0)], [node.name],
                       name=node.name)]
    shape = ctx.shapes.get(node.inputs[0])
    if shape is None:
        raise NotImplementedError("repeat_kv export needs inferred shapes")
    b, kv, s, d = (int(v) for v in shape)
    sh5 = ctx.const(f"{node.name}_s5",
                    np.asarray([b, kv, 1, s, d], np.int64))
    reps = ctx.const(f"{node.name}_reps",
                     np.asarray([1, 1, n, 1, 1], np.int64))
    sh4 = ctx.const(f"{node.name}_s4",
                    np.asarray([b, kv * n, s, d], np.int64))
    r5, tl = ctx.aux(f"{node.name}_r5"), ctx.aux(f"{node.name}_tile")
    return [
        NodeIR("Reshape", [_in(node, 0), sh5], [r5]),
        NodeIR("Tile", [r5, reps], [tl]),
        NodeIR("Reshape", [tl, sh4], [node.name], name=node.name),
    ]


@exporter("alibi_bias")
def _alibi_exp(node, ctx):
    """ALiBi additive bias depends only on (num_heads, seq_len), both
    static — exported as a constant initializer (ops/rotary.py:78)."""
    shape = ctx.shapes.get(node.inputs[0])
    if shape is None:
        raise NotImplementedError("alibi_bias export needs inferred shapes")
    s = int(shape[-2])
    nh = int(node.attrs["num_heads"])
    from ..ops.rotary import alibi_slopes
    slopes = np.asarray(alibi_slopes(nh), np.float32)
    rel = (np.arange(s, dtype=np.float32)[None, :]
           - np.arange(s, dtype=np.float32)[:, None])
    bias = (slopes[:, None, None] * rel[None, :, :])[None]   # [1,H,S,S]
    c = ctx.const(f"{node.name}_bias", bias.astype(np.float32))
    return [NodeIR("Identity", [c], [node.name], name=node.name)]


def _export_batchnorm(node, ctx):
    if getattr(node, "channel_axis", 1) not in (1,):
        # ONNX BatchNormalization is channel-axis-1 only; silently
        # exporting a channels-last graph would normalize over H
        raise NotImplementedError(
            "ONNX export supports NCHW BatchNorm only; rebuild the model "
            "with channels_last=False for export")
    return [NodeIR("BatchNormalization", [i.name for i in node.inputs],
                   [node.name],
                   {"epsilon": node.eps, "momentum": 1.0 - node.momentum},
                   name=node.name)]


def _export_dropout(node, ctx):
    ratio = ctx.const(f"{node.name}_ratio",
                      np.asarray(1.0 - node.keep_prob, np.float32))
    return [NodeIR("Dropout", [_in(node, 0), ratio], [node.name],
                   name=node.name)]


def _export_sdpa(node, ctx):
    """ScaledDotProductAttentionOp -> Transpose/MatMul/Mul/Add/Softmax/
    MatMul decomposition (inference export: attention dropout off), the
    same lowering the reference's bridge applies to its attention layers."""
    q, k, v = node.inputs[:3]
    qshape = ctx.shapes.get(q)
    if qshape is None:
        raise NotImplementedError(
            f"attention export for {node.name} needs inferable shapes "
            "(declare placeholder shapes)")
    out = []
    heads = node.num_heads
    if heads is None:
        d, s_q = qshape[-1], qshape[-2]
        s_k = ctx.shapes.get(k, qshape)[-2]
        q, k, v = q.name, k.name, v.name
    else:
        # [B, S, H*D] operands: Reshape + Transpose to [B, H, S, D] here,
        # and the context back below
        d, s_q = qshape[-1] // heads, qshape[-2]
        s_k = ctx.shapes.get(k, qshape)[-2]

        # k and v may hold fewer heads (grouped queries): each key head is
        # tiled under its query heads, as ``repeat_kv`` exports
        rep = qshape[-1] // ctx.shapes.get(k, qshape)[-1]

        def split(x, tag, seq, rep=1):
            shp = ctx.const(f"{node.name}_{tag}_shape", np.asarray(
                [-1, seq, heads // rep] + [1] * (rep > 1) + [d], np.int64))
            rs, tr = (ctx.aux(f"{node.name}_{tag}_{part}")
                      for part in ("rs", "heads"))
            out.append(NodeIR("Reshape", [x.name, shp], [rs]))
            if rep > 1:
                tiled, flat = (ctx.aux(f"{node.name}_{tag}_{part}")
                               for part in ("tile", "rep"))
                out.append(NodeIR("Tile", [rs, ctx.const(
                    f"{node.name}_{tag}_reps",
                    np.asarray([1, 1, 1, rep, 1], np.int64))], [tiled]))
                out.append(NodeIR("Reshape", [tiled, ctx.const(
                    f"{node.name}_{tag}_all",
                    np.asarray([-1, seq, heads, d], np.int64))], [flat]))
                rs = flat
            out.append(NodeIR("Transpose", [rs], [tr],
                              {"perm": (0, 2, 1, 3)}))
            return tr
        q, k, v = (split(q, "q", s_q), split(k, "k", s_k, rep),
                   split(v, "v", s_k, rep))
    scale = node.scale if node.scale is not None else 1.0 / float(np.sqrt(d))
    kt = ctx.aux(f"{node.name}_kT")
    out.append(NodeIR("Transpose", [k], [kt], {"perm": (0, 1, 3, 2)}))
    scores = ctx.aux(f"{node.name}_scores")
    out.append(NodeIR("MatMul", [q, kt], [scores]))
    cur = ctx.aux(f"{node.name}_scaled")
    out.append(NodeIR("Mul", [scores,
                              ctx.const(f"{node.name}_scale",
                                        np.asarray(scale, np.float32))],
                      [cur]))
    if node.causal:
        causal = np.where(
            np.arange(s_q)[:, None] >= np.arange(s_k)[None, :] - (s_k - s_q),
            0.0, -1e9).astype(np.float32)[None, None]
        nxt = ctx.aux(f"{node.name}_causal")
        out.append(NodeIR("Add", [cur, ctx.const(f"{node.name}_cmask",
                                                 causal)], [nxt]))
        cur = nxt
    if node.has_mask:
        nxt = ctx.aux(f"{node.name}_masked")
        out.append(NodeIR("Add", [cur, node.inputs[3].name], [nxt]))
        cur = nxt
    probs = ctx.aux(f"{node.name}_probs")
    out.append(NodeIR("Softmax", [cur], [probs], {"axis": -1}))
    if heads is None:
        out.append(NodeIR("MatMul", [probs, v], [node.name], name=node.name))
        return out
    ctx_heads, ctx_rows = (ctx.aux(f"{node.name}_ctx_{part}")
                           for part in ("heads", "rows"))
    out.append(NodeIR("MatMul", [probs, v], [ctx_heads]))
    out.append(NodeIR("Transpose", [ctx_heads], [ctx_rows],
                      {"perm": (0, 2, 1, 3)}))
    out.append(NodeIR("Reshape", [ctx_rows, ctx.const(
        f"{node.name}_ctx_shape", np.asarray([-1, s_q, heads * d],
                                             np.int64))],
        [node.name], name=node.name))
    return out


def _export_position_ids(node, ctx):
    """models.bert.PositionIdsOp: table[None, :S, :] as Slice+Unsqueeze."""
    starts = ctx.const(f"{node.name}_s0", np.asarray([0], np.int64))
    ends = ctx.const(f"{node.name}_s1",
                     np.asarray([node.seq_len], np.int64))
    axes0 = ctx.const(f"{node.name}_ax", np.asarray([0], np.int64))
    sliced = ctx.aux(f"{node.name}_rows")
    return [
        NodeIR("Slice", [_in(node, 0), starts, ends, axes0], [sliced]),
        NodeIR("Unsqueeze", [sliced, axes0], [node.name], name=node.name),
    ]


def _infer_shapes(eval_nodes, params):
    """Abstractly evaluate the graph to get every node's shape (the role
    of the reference's per-op infer_shape pass, Node.py:130).  Returns {}
    when placeholders lack declared shapes."""
    import jax
    import jax.numpy as jnp
    from ..graph.trace import TraceContext, evaluate

    topo = find_topo_sort(list(eval_nodes))
    phs = [n for n in topo if isinstance(n, PlaceholderOp)]
    vars_ = [n for n in topo if isinstance(n, VariableOp)]
    if any(p.shape is None for p in phs):
        return {}
    interior = [n for n in topo
                if not isinstance(n, (PlaceholderOp, VariableOp))]

    def f(feed_vals):
        ctx = TraceContext(key=jax.random.key(0), training=False)
        bindings = dict(zip(phs, feed_vals))
        for vr in vars_:
            bindings[vr] = jnp.zeros(np.shape(params[vr.name]),
                                     np.asarray(params[vr.name]).dtype)
        # _remat=False: shape inference has no backward pass, and remat
        # grouping binds only group OUTPUTS in env — interior nodes would
        # KeyError here
        _, env = evaluate(eval_nodes, bindings, ctx, _remat=False)
        return [env[n] for n in interior]

    feed_structs = [jax.ShapeDtypeStruct(tuple(p.shape), p.dtype)
                    for p in phs]
    try:
        outs = jax.eval_shape(f, feed_structs)
    except Exception:
        return {}
    shapes = {n: tuple(o.shape) for n, o in zip(interior, outs)
              if hasattr(o, "shape")}      # a pair's value is a tuple
    shapes.update({p: tuple(p.shape) for p in phs})
    shapes.update({vr: tuple(np.shape(params[vr.name])) for vr in vars_})
    return shapes


_NP2ONNX_DTYPE = {"float32": "float32", "float64": "float64",
                  "int32": "int32", "int64": "int64"}


def hetu2onnx(eval_nodes, params, name="hetu_tpu_graph"):
    """Export the graph reaching ``eval_nodes`` to an OnnxModel.

    ``params``: {variable_name: array} (e.g. `Executor.params`) supplying
    initializer values.  Placeholders become graph inputs; ``eval_nodes``
    become graph outputs.
    """
    from ..graph.executor import Executor  # noqa: F401 (doc only)
    model = OnnxModel(name=name)
    ctx = _Ctx(model, shapes=_infer_shapes(eval_nodes, params))
    topo = find_topo_sort(list(eval_nodes))
    for node in topo:
        if isinstance(node, PlaceholderOp):
            model.inputs.append(TensorInfo(
                node.name, tuple(node.shape or ()),
                _NP2ONNX_DTYPE.get(str(node.dtype), "float32")))
        elif isinstance(node, VariableOp):
            if node.name not in params:
                raise KeyError(f"no value for variable {node.name}; pass "
                               f"Executor.params")
            model.add_initializer(node.name, np.asarray(params[node.name]))
        elif isinstance(node, BatchNormOp):
            model.nodes.extend(_export_batchnorm(node, ctx))
        elif isinstance(node, DropoutOp):
            model.nodes.extend(_export_dropout(node, ctx))
        elif isinstance(node, ScaledDotProductAttentionOp):
            model.nodes.extend(_export_sdpa(node, ctx))
        elif type(node).__name__ == "PositionIdsOp":
            model.nodes.extend(_export_position_ids(node, ctx))
        elif isinstance(node, SimpleOp):
            fn = _EXPORTERS.get(node.op_kind)
            if fn is None:
                raise NotImplementedError(
                    f"no ONNX exporter for op kind {node.op_kind!r} "
                    f"(node {node.name})")
            model.nodes.extend(fn(node, ctx))
        else:
            raise NotImplementedError(
                f"no ONNX exporter for {type(node).__name__} ({node.name})")
    for node in eval_nodes:
        model.outputs.append(TensorInfo(node.name, ()))
    return model
