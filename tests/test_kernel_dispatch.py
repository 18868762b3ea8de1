"""One rule for "kernel or ``jax.numpy``" (``ops/pallas/dispatch.py take``),
pinned three ways.

1. Static (the ``test_no_wallclock_timing.py`` pattern): under ``hetu_tpu/ops``
   and ``hetu_tpu/layers`` the reason literal ``"mesh"`` stands in
   ``dispatch.py`` alone, ``dispatch.record`` is called there and by the three
   ops that have a per-shard plan alone, and no module of ``ops/pallas/``,
   ``layers/`` or ``models/`` imports an underscore name from a sibling.
2. The function itself: for each of the kernel labels, on ``tpu`` and
   ``cpu``, with and without a mesh, the key it records is the one in the
   table below, written out from what the call sites recorded before there
   was one function.
3. The nodes: under a mesh the node of every kernel without a per-shard form
   calls the ``jax.numpy`` form and counts ``mesh`` where there was a kernel
   to take; off a mesh on a TPU the kernel, counted ``pallas``; nothing is
   counted on a platform without Mosaic.  One parametrised test over (node,
   platform, mesh) on one stub of the kernel and of the ``jax.numpy`` form.
4. The kernels that left the rule where a ``dp`` axis divides the batch (PR
   72): the rotary pair runs per shard under ``shard_map``, and the experts'
   three (``moe_gmm``, ``moe_rows``, ``moe_select``) are called inside the
   expert layer's own ``shard_map`` over its axis, where they see no mesh:
   each counts ``pallas`` and none ``mesh``.
"""

import ast
import os
import types

import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.ops.pallas import dispatch

HETU_ROOT = os.path.join(os.path.dirname(__file__), "..", "hetu_tpu")

# -- 1. static ---------------------------------------------------------------------

#: the ops whose kernels run per shard under a mesh: each has a plan that
#: reads the mesh (``dispatch.shard_axes``) and hands ``record`` its reason
PLANNED = {"ops/attention.py", "ops/losses.py", "ops/nn.py"}


def modules(*packages):
    """``(relative path, tree)`` of every module under the packages."""
    for package in packages:
        for dirpath, dirnames, files in os.walk(
                os.path.join(HETU_ROOT, package)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in sorted(f for f in files if f.endswith(".py")):
                path = os.path.join(dirpath, fn)
                with open(path, encoding="utf-8") as f:
                    yield (os.path.relpath(path, HETU_ROOT).replace(
                        os.sep, "/"), ast.parse(f.read(), filename=path))


def test_the_reason_mesh_is_written_once():
    where = {rel for rel, tree in modules("ops", "layers")
             for node in ast.walk(tree)
             if isinstance(node, ast.Constant) and node.value == "mesh"}
    assert where == {"ops/pallas/dispatch.py"}


def test_record_is_called_by_dispatch_and_the_plans_alone():
    def records(tree):
        names = {a.asname or a.name for node in ast.walk(tree)
                 if isinstance(node, ast.ImportFrom)
                 and (node.module or "").endswith("dispatch")
                 for a in node.names if a.name == "record"}
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (isinstance(f, ast.Attribute) and f.attr == "record"
                    and isinstance(f.value, ast.Name)
                    and f.value.id == "dispatch") or (
                    isinstance(f, ast.Name) and f.id in names | {"record"}):
                yield node.lineno
    where = {rel for rel, tree in modules("ops", "layers")
             if list(records(tree))}
    assert where == PLANNED | {"ops/pallas/dispatch.py"}


def test_no_module_imports_a_siblings_private_name():
    found = [f"{rel}:{node.lineno} from .{node.module} import {a.name}"
             for rel, tree in modules("ops/pallas", "layers", "models")
             for node in ast.walk(tree)
             if isinstance(node, ast.ImportFrom) and node.level == 1
             and node.module for a in node.names if a.name.startswith("_")]
    assert not found, found


# -- 2. the function ---------------------------------------------------------------

PALLAS, NOTHING = ("pallas", ""), None
MESH, CPU = ("jnp", "mesh"), ("jnp", "platform:cpu")
#: label -> what is recorded on (tpu, no mesh), (tpu, mesh), (cpu, no mesh),
#: (cpu, mesh), the operands such that the kernel's own rule takes them
TABLE = {
    # per shard under a mesh, through their plans
    "flash_attention": (PALLAS, PALLAS, CPU, CPU),
    "softmax_ce": (PALLAS, PALLAS, PALLAS, PALLAS),    # interpret mode
    "dropout": (PALLAS, PALLAS, CPU, CPU),
    # no per-shard form, through ``take``
    "gated_delta": (PALLAS, MESH, NOTHING, NOTHING),
    "ssd": (PALLAS, MESH, NOTHING, NOTHING),
    "kda": (PALLAS, MESH, NOTHING, NOTHING),
    "causal_conv": (PALLAS, MESH, NOTHING, NOTHING),
    "gated_norm": (PALLAS, MESH, NOTHING, NOTHING),
    "moe_rows": (PALLAS, MESH, NOTHING, NOTHING),
    "rotary": (PALLAS, MESH, NOTHING, NOTHING),
    "hc_mix": (PALLAS, MESH, NOTHING, NOTHING),
    "mla_pack": (PALLAS, MESH, NOTHING, NOTHING),
    "moe_select": (PALLAS, MESH, NOTHING, NOTHING),
    "moe_gmm": (PALLAS, MESH, CPU, MESH),
    # the packed table's row-write kernel, by the entry that reached it
    "packed_lookup": (PALLAS, MESH, CPU, MESH),
    "pack_write": (PALLAS, MESH, CPU, MESH),
}


def reason_of_the_plan(label, mesh):
    from hetu_tpu.ops import attention, losses, nn
    sds = jax.ShapeDtypeStruct
    if label == "flash_attention":
        q = sds((2, 4, 256, 64), jnp.bfloat16)
        return attention._flash_plan(q, q, q, None, 1.0, mesh)[0]
    if label == "softmax_ce":
        return losses._ce_kernel_plan(sds((64, 2048), jnp.float32), -1,
                                      mesh)[0]
    return nn._dropout_mask_plan((64, 128), mesh)[0]


@pytest.fixture
def choices(live_registry):
    """``choices(label)``: ``{(impl, reason): count}`` recorded under the
    label since the test began."""
    before = dispatch.choices()

    def since(label):
        return {k[1:]: n - before.get(k, 0)
                for k, n in dispatch.choices().items()
                if k[0] == label and n > before.get(k, 0)}
    return since


@pytest.mark.parametrize("mesh", [None, "a mesh"])
@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("label", sorted(TABLE))
def test_what_a_label_records(choices, monkeypatch, label, platform, mesh):
    monkeypatch.setattr(dispatch, "platform", lambda: platform)
    if mesh is not None:
        mesh = types.SimpleNamespace(shape={"dp": 2})
    if label in ("flash_attention", "softmax_ce", "dropout"):
        took = dispatch.record(label, reason_of_the_plan(label, mesh))
    else:
        took = dispatch.take(label, mesh, None)
    want = TABLE[label][2 * (platform == "cpu") + (mesh is not None)]
    assert took == (want == PALLAS)
    assert choices(label) == ({} if want is NOTHING else {want: 1})


@pytest.mark.parametrize("platform,asked,want", [
    ("tpu", False, ("jnp", "why")), ("cpu", True, ("jnp", "why")),
    ("cpu", False, NOTHING)])
def test_a_refusal_is_recorded_where_there_was_a_choice(choices, monkeypatch,
                                                        platform, asked, want):
    """The kernel file's own reason, on a TPU and where the caller asked for
    the kernels (interpret mode)."""
    monkeypatch.setattr(dispatch, "platform", lambda: platform)
    assert not dispatch.take("moe_rows", None, "why", asked=asked)
    assert choices("moe_rows") == ({} if want is NOTHING else {want: 1})


# -- 3. the nodes ------------------------------------------------------------------

D, P, N = 128, 64, 128


def layer_nodes():
    """Every node of the three mixers, and the attention layer's rotary pair,
    that stands in front of a kernel without a per-shard form, by scope."""
    import hetu_tpu as ht
    from hetu_tpu.graph.node import find_topo_sort
    from hetu_tpu.layers.attention import MultiHeadAttention
    from hetu_tpu.layers.gated_delta_net import GatedDeltaNet
    from hetu_tpu.layers.kda import KimiDeltaAttention
    from hetu_tpu.layers.mamba2 import Mamba2
    x = ht.placeholder_op("kd_x256", (1, 64, 256))
    gdn = GatedDeltaNet(256, 1, 2, D, D, name="kd_gdn")(x)
    ssm = Mamba2(256, 4, P, 1, N, name="kd_ssm")(x)
    kda = KimiDeltaAttention(256, 2, D, name="kd_kda")(x)
    attn = MultiHeadAttention(256, 2, sequence_length=64, rope_theta=1e4,
                              name="kd_attn")(x, x, x)
    x = ht.placeholder_op("kd_x64", (1, 64, 64))
    small_ssm = Mamba2(64, 8, 16, 1, 64, name="kd_ssm_conv")(x)
    small_gdn = GatedDeltaNet(64, 2, 4, 16, 16, name="kd_gdn_conv")(x)
    nodes = {"hetu_gdn_scan": gdn.inputs[0], "hetu_ssm_scan": ssm.inputs[0],
             "hetu_kda_scan": kda.inputs[0],
             "hetu_ssm_conv": small_ssm.inputs[0].inputs[0],
             "hetu_gdn_conv": small_gdn.inputs[0].inputs[0],
             "hetu_ssm_out": Mamba2(64, 8, 32, 2, 64, name="kd_ssm_out")(x),
             "hetu_gdn_out": GatedDeltaNet(64, 2, 4, D, D,
                                           name="kd_gdn_out")(x),
             "hetu_attn": next(n for n in find_topo_sort([attn])
                               if getattr(n, "op_kind", "") == "rotary_pair")}
    assert all(node.scope == scope for scope, node in nodes.items())
    return nodes


def bf16(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


def f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


#: scope -> (label, the kernel's entry, its ``jax.numpy`` form, operands)
NODES = {
    "hetu_gdn_scan": ("gated_delta",
                      "pallas.gated_delta:gated_delta_rule_in_place",
                      "gated_delta:chunk_gated_delta_rule_jnp",
                      [bf16(1, 64, 4 * D), bf16(1, 64, 4), f32(2), f32(2)]),
    "hetu_ssm_scan": ("ssd", "pallas.ssd:ssd_in_place", "ssd:chunk_ssd_jnp",
                      [bf16(1, 128, 4 * P + 2 * N), bf16(1, 128, 4), f32(4),
                       f32(4), f32(4)]),
    "hetu_kda_scan": ("kda", "pallas.kda:kda_in_place", "kda:chunk_kda_jnp",
                      [bf16(1, 64, 10 * D), bf16(1, 64, 6 * D), bf16(1, 64, 2),
                       bf16(2), bf16(2 * D), bf16(D)]),
    # the Mamba-2 layer's reads its window out of the projection's output
    "hetu_ssm_conv": ("causal_conv", "pallas.causal_conv:conv",
                      "causal_conv:causal_conv_jnp",
                      [bf16(1, 64, 392), bf16(4, 256), bf16(256)]),
    "hetu_gdn_conv": ("causal_conv", "pallas.causal_conv:conv",
                      "causal_conv:causal_conv_jnp",
                      [bf16(1, 64, 128), bf16(4, 128)]),
    # y, [z | xBC | dt], the scale, the output weight: the form is the node's
    "hetu_ssm_out": ("gated_norm", "pallas.gated_norm:gated_norm", None,
                     [bf16(1, 64, 256), bf16(1, 64, 648), bf16(256),
                      bf16(256, 64)]),
    "hetu_gdn_out": ("gated_norm", "pallas.gated_norm:gated_norm", None,
                     [bf16(1, 64, 512), bf16(1, 64, 1536), bf16(128),
                      bf16(512, 64)]),
    # q, k and the tables: the form (``_rotary``) is the node's
    "hetu_attn": ("rotary", "pallas.rotary:rope", None,
                  [bf16(1, 64, 2 * D), bf16(1, 64, 2 * D), f32(2, 64, D)]),
}

ON_TPU = ("tpu", None, {PALLAS: 1})
UNDER_A_MESH = [("tpu", "a mesh", {MESH: 1}), ("cpu", "a mesh", {})]
CASES = [(scope,) + case for scope, cases in [
    ("hetu_gdn_scan", [ON_TPU] + UNDER_A_MESH),
    ("hetu_ssm_scan", [ON_TPU] + UNDER_A_MESH),
    ("hetu_kda_scan", [ON_TPU] + UNDER_A_MESH + [("cpu", None, {})]),
    ("hetu_ssm_conv", [ON_TPU] + UNDER_A_MESH + [("cpu", None, {})]),
    ("hetu_gdn_conv", [ON_TPU] + UNDER_A_MESH + [("cpu", None, {})]),
    ("hetu_ssm_out", [ON_TPU] + UNDER_A_MESH + [("cpu", None, {})]),
    ("hetu_gdn_out", [ON_TPU] + UNDER_A_MESH + [("cpu", None, {})]),
    ("hetu_attn", [ON_TPU] + UNDER_A_MESH + [("cpu", None, {})]),
] for case in cases]


def stub(monkeypatch, where, called, impl, like=None):
    """Put a recording stand-in in the place ``where`` (``module:name`` under
    ``hetu_tpu.ops``) names: it notes ``impl`` and calls what stood there, or
    ``like()`` where the real thing is a kernel."""
    import importlib
    module, name = where.split(":")
    module = importlib.import_module("hetu_tpu.ops." + module)
    real = getattr(module, name)
    monkeypatch.setattr(module, name, lambda *a, **k: called.append(impl) or
                        (like or (lambda: real))()(*a, **k))
    return real


@pytest.mark.parametrize("scope,platform,mesh,want", CASES)
def test_a_node_reads_the_mesh(choices, monkeypatch, scope, platform, mesh,
                               want):
    """The one thing the function behind a node cannot see is the node's."""
    label, kernel, form, operands = NODES[scope]
    monkeypatch.setattr(dispatch, "platform", lambda: platform)
    node = layer_nodes()[scope]
    called = []
    if form is None:            # the form is the layer's, on the node
        held = "fn" if hasattr(node, "fn") else "impl"
        plain = getattr(node, held)
        setattr(node, held, lambda *a, **k: (
            called.append("jnp") or plain(*a, **k)))
        stub(monkeypatch, kernel, called, "pallas",
             like=lambda: (lambda q, k, t, r=None: (q, k)) if label == "rotary"
             else lambda o, *a, **k: o)
    elif "_in_place" in kernel:         # from ``mixed`` to [B, S, H d]
        stub(monkeypatch, form, called, "jnp")      # (256 lanes: 2 D = 4 P)
        stub(monkeypatch, kernel, called, "pallas",
             like=lambda: lambda mixed, *a, **k: mixed[..., :2 * D])
    else:
        real = stub(monkeypatch, form, called, "jnp")
        stub(monkeypatch, kernel, called, "pallas",
             like=lambda: lambda *a: real(*a))
    if mesh is not None:        # one that divides no operand's batch of 1
        mesh = types.SimpleNamespace(shape={"dp": 2})
    ctx = types.SimpleNamespace(mesh=mesh)
    jax.eval_shape(lambda *a: node._compute(list(a), ctx), *operands)
    jnp_calls = 2 if label == "rotary" else 1        # q, then k
    assert called == (["pallas"] if want == {PALLAS: 1}
                      else ["jnp"] * jnp_calls)
    assert choices(label) == want


# -- 4. per shard where a dp axis divides the batch --------------------------------

@pytest.fixture(scope="module")
def dp4():
    from hetu_tpu.parallel.mesh import make_mesh
    return make_mesh({"dp": 4}, devices=jax.devices()[:4])


def traced_under(dp4, label):
    """Trace, on a TPU by name and under a mesh whose ``dp`` divides the
    batch of 4, the node that stands in front of ``label``'s kernel."""
    import hetu_tpu as ht
    from hetu_tpu.graph.node import find_topo_sort
    ctx = types.SimpleNamespace(mesh=dp4, master_params=None, training=False,
                                record_update=lambda *a: None)
    if label == "rotary":
        from hetu_tpu.layers.attention import MultiHeadAttention
        x = ht.placeholder_op("kd4_x", (4, 64, 256))
        attn = MultiHeadAttention(256, 2, sequence_length=64, rope_theta=1e4,
                                  name="kd4_attn")(x, x, x)
        node = next(n for n in find_topo_sort([attn])
                    if getattr(n, "op_kind", "") == "rotary_pair")
        operands = [bf16(4, 64, 2 * D), bf16(4, 64, 2 * D), f32(2, 64, D)]
    else:
        from hetu_tpu.layers.moe import MoELayer
        x = ht.placeholder_op("kd4_tokens", (4, 128, D))
        layer = MoELayer(D, D, num_experts=16, k=4, capacity_factor=None,
                         expert_act="swiglu", ep_axis="dp", name="kd4_moe")
        node = layer(x)
        shapes = {"x": bf16(4, 128, D), "router": bf16(D, 16)}
        operands = [shapes.get(name, bf16(16, D, D)) for name in node.at]
    jax.eval_shape(lambda *a: node._compute(list(a), ctx), *operands)


@pytest.mark.parametrize("label", ["rotary", "moe_gmm", "moe_rows",
                                   "moe_select"])
def test_a_kernel_runs_per_shard_where_dp_divides_the_batch(
        choices, monkeypatch, dp4, label):
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    traced_under(dp4, label)
    got = choices(label)
    assert got and set(got) == {PALLAS}, got
