"""The chunked rules' host side, written once (``ops/pallas/common.py``):
how a ``[B, T, ..]`` array is cut into programs of chunks (``cut``), padded
(``rows``) and a gate laid out a chunk (``by_chunk``), and a chunk's L2 norm
(``unit``, ``unit_bwd``), against the copies ``gated_delta.py``, ``kda.py``
and ``ssd.py`` each held before (written out here); and that the file stays
what every kernel file may import: it imports none of them."""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops.pallas import common, ssd
from test_kernel_dispatch import HETU_ROOT, modules


def cut_as_written(T, chunk, chunks=8):
    """``gated_delta._cut`` / ``kda._cut`` (chunk 64) and ``ssd._cut`` (128)
    as they stood."""
    nc = min(chunks, -(-T // chunk))
    groups = -(-T // (nc * chunk))
    return nc, groups, groups * nc * chunk - T


@pytest.mark.parametrize("chunk", [64, 128])
@pytest.mark.parametrize("T", [1, 63, 64, 512, 513, 8192])
def test_the_cut_the_padding_and_the_layouts_are_the_copies(T, chunk):
    nc, groups, pad = cut = common.cut(T, chunk, 8)
    assert cut == cut_as_written(T, chunk)
    assert (T + pad) == groups * nc * chunk and 0 <= pad < nc * chunk
    assert nc <= 8 and (groups == 1 or nc == 8)
    if chunk == common.C:
        assert common.cut(T) == cut
    r = np.random.default_rng(T + chunk)
    B, H, d = 2, 3, 4
    x = jnp.asarray(r.normal(size=(B, T, H, d)), jnp.bfloat16)
    # rows: [B, T, H, d] -> [B, T', H d], zeros (or the constant) behind T
    for how, fill in (({}, 0.0), (dict(constant_values=-1e9), -1e9)):
        got = common.rows(x, pad, **how)
        want = np.full((B, T + pad, H * d), fill, np.float32)
        want[:, :T] = np.asarray(x, np.float32).reshape(B, T, H * d)
        assert got.shape == want.shape and got.dtype == x.dtype
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(jnp.asarray(want, jnp.bfloat16), np.float32))
    gate = jnp.asarray(r.normal(size=(B, T, H)), jnp.bfloat16)
    padded = np.zeros((B, T + pad, H), np.float32)
    padded[:, :T] = np.asarray(gate, np.float32)
    if chunk == common.C:
        # by_chunk: [B, T, H] -> [B, H, groups, nc, C] f32, 0 at the padding
        got = common.by_chunk(gate, *cut)
        assert got.shape == (B, H, groups, nc, chunk)
        assert got.dtype == jnp.float32
        np.testing.assert_array_equal(
            got, padded.transpose(0, 2, 1).reshape(B, H, groups, nc, chunk))
    else:
        # the state-space scan's own layout over the same padding: a chunk
        # along the lanes, [b, G, blocks, nc, R, L]
        G, R = 1, H
        dt, a = ssd._gates(gate, jnp.arange(1.0, H + 1), G, R, cut)
        want = padded.reshape(B, groups, nc, chunk, G, R).transpose(
            0, 4, 1, 2, 5, 3)
        np.testing.assert_array_equal(dt, want)
        np.testing.assert_array_equal(
            a, want * np.arange(1.0, H + 1, dtype=np.float32)[:, None])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_chunks_norm_and_its_cotangent_are_the_copies(dtype):
    """``unit``: ``gated_delta._unit`` (both values) and ``kda._unit`` (the
    first) as they stood; ``unit_bwd``: JAX's own cotangent of the first."""
    r = np.random.default_rng(3)
    t = jnp.asarray(r.normal(size=(common.C, 128)), jnp.dtype(dtype))
    rows, inverse = common.unit(t)
    tf = t.astype(jnp.float32)
    want_r = jax.lax.rsqrt(jnp.sum(tf * tf, axis=1, keepdims=True) + 1e-6)
    assert rows.dtype == inverse.dtype == jnp.float32
    assert inverse.shape == (common.C, 1)
    np.testing.assert_array_equal(inverse, want_r)
    np.testing.assert_array_equal(rows, tf * want_r)
    dt = jnp.asarray(r.normal(size=rows.shape), jnp.float32)
    _, pull = jax.vjp(lambda x: common.unit(x)[0], tf)
    np.testing.assert_allclose(common.unit_bwd(dt, rows, inverse),
                               pull(dt)[0], rtol=2e-5, atol=2e-6)
    # a row of zeros stays zeros: 1e-6 under the root
    assert not np.asarray(common.unit(jnp.zeros((8, 128)))[0]).any()


def imported(tree):
    """The modules and names a parsed file imports, as written (a relative
    one with its dots)."""
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = "." * node.level + (node.module or "")
            found.update(f"{base}.{a.name}" for a in node.names)
    return found


def test_common_imports_no_kernel_file_and_no_family_imports_ling3():
    """``ops/pallas/common.py`` is what the kernel files import and imports
    none of them; an MoE family takes its shared methods from
    ``models/llama.py``, so no model file but the package's ``__init__``
    imports ``models/ling3.py`` and no other family's file names it (its
    checkpoint loader in ``hf_import.py`` does, by its task)."""
    pallas = dict(modules("ops/pallas"))
    kernel_files = {os.path.basename(rel)[:-3] for rel in pallas} - {
        "common", "__init__"}
    assert {"gated_delta", "kda", "ssd", "selective_scan"} <= kernel_files
    named = {part for m in imported(pallas["ops/pallas/common.py"])
             if m.startswith(".") for part in m.split(".")}
    assert not named & kernel_files, named & kernel_files
    for rel, tree in modules("models"):
        if rel in ("models/ling3.py", "models/__init__.py"):
            continue
        assert not any("ling3" in m for m in imported(tree)), rel
        if rel != "models/hf_import.py":
            with open(os.path.join(HETU_ROOT, rel)) as src:
                assert "ling3" not in src.read().lower(), rel
