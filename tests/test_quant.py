"""The shared quantization codec (hetu_tpu/ops/quant.py) and the
quantized serving plane built on it (ISSUE 16).

Contracts pinned here:
* ROUND-TRIP ERROR IS BOUNDED — quantize_blocks/dequantize_blocks err
  by at most ``roundtrip_bound(dtype, absmax)`` per element, for every
  block size, for int8 and fp8, on both the numpy (wire)
  and jax (in-graph) namespaces;
* zero blocks emit scale 0 and round-trip to EXACT zeros — freshly
  allocated quantized KV pages stay bitwise-zero through gather;
* quantized paged pools: gather dequantizes what scatter quantized
  (within the bound), CoW forks copy codes AND scales so forked pages
  keep independent scales, and the HETU_COW_GUARD write-guard still
  trips on shared quantized pages;
* speculative verify over quantized KV stays within the divergence
  gate (streams agree with the non-speculative quantized twin and the
  page audit balances — NOT bitwise vs f32: the verify window attends
  fresh float rows where the plain path attends round-tripped ones);
* quantization is strictly opt-in: kv_dtype demands paged=True,
  gather_dtype demands mesh=;
* THE AST GATE — every narrow-dtype cast (``astype`` to int8/uint8/
  fp8, ``bitcast_convert_type``) in the package lives in ops/quant.py,
  so inline quantization can never drift away from these bounds.
"""

import ast
import os

import numpy as np
import pytest

import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu.models import LlamaConfig, LlamaForCausalLM
from hetu_tpu.ops import quant
from hetu_tpu.serving import InferenceEngine, PagedKVCache
from hetu_tpu.serving.kv_cache import (QuantizedKVPool, gather_pages,
                                       scatter_rows)

V = 64

FP8 = "fp8"


@pytest.fixture
def rng():
    return np.random.default_rng(0)


# -- codec round-trip bounds -------------------------------------------------

@pytest.mark.parametrize("dtype", ["int8", FP8])
@pytest.mark.parametrize("block", [None, 1, 4, 16])
@pytest.mark.parametrize("xp_name", ["numpy", "jnp"])
def test_roundtrip_within_bound(rng, dtype, block, xp_name):
    x = rng.normal(scale=3.0, size=(6, 32)).astype(np.float32)
    if xp_name == "jnp":
        x = jnp.asarray(x)
    codes, scales = quant.quantize_blocks(x, block=block, dtype=dtype)
    assert codes.dtype == quant.code_dtype(dtype)
    assert np.asarray(scales).dtype == np.float32
    nblocks = 32 // (block or 32)
    assert scales.shape == (6, nblocks)
    y = np.asarray(quant.dequantize_blocks(codes, scales))
    err = np.abs(y - np.asarray(x)).reshape(6, nblocks, -1)
    absmax = np.abs(np.asarray(x)).reshape(6, nblocks, -1).max(
        axis=-1, keepdims=True)
    bound = np.vectorize(
        lambda a: quant.roundtrip_bound(dtype, a))(absmax)
    assert (err <= bound + 1e-7).all()


@pytest.mark.parametrize("dtype", ["int8", FP8])
def test_finer_blocks_never_hurt(rng, dtype):
    """An outlier in one block must not spend the mantissa budget of
    the others: per-block max error with block=4 <= per-tensor's."""
    x = rng.normal(size=(2, 16)).astype(np.float32)
    x[0, 0] = 100.0                      # one outlier row-leading value
    errs = {}
    for block in (4, None):
        c, s = quant.quantize_blocks(x, block=block, dtype=dtype)
        errs[block] = np.abs(
            np.asarray(quant.dequantize_blocks(c, s)) - x)[0, 1:].max()
    assert errs[4] <= errs[None] + 1e-7


@pytest.mark.parametrize("dtype", ["int8", FP8])
@pytest.mark.parametrize("xp_name", ["numpy", "jnp"])
def test_zero_blocks_scale_zero_exact_roundtrip(dtype, xp_name):
    x = np.zeros((3, 8), np.float32)
    x[1, :4] = [1.0, -2.0, 0.5, 0.25]    # row 1 block 0 nonzero
    if xp_name == "jnp":
        x = jnp.asarray(x)
    codes, scales = quant.quantize_blocks(x, block=4, dtype=dtype)
    s = np.asarray(scales)
    assert s[0].max() == 0.0 and s[2].max() == 0.0 and s[1, 1] == 0.0
    assert s[1, 0] > 0.0
    y = np.asarray(quant.dequantize_blocks(codes, scales))
    # zero blocks reproduce EXACT zeros, not small values
    assert (y[0] == 0.0).all() and (y[2] == 0.0).all()
    assert (y[1, 4:] == 0.0).all()


def test_block_must_divide_last_axis():
    with pytest.raises(ValueError, match="divide"):
        quant.quantize_blocks(np.ones((2, 10), np.float32), block=4)
    with pytest.raises(ValueError, match="divide"):
        quant.dequantize_blocks(np.ones((2, 10), np.int8),
                                np.ones((2, 4), np.float32))


def test_unknown_dtype_rejected():
    with pytest.raises((ValueError, KeyError)):
        quant.quantize_blocks(np.ones((2, 4), np.float32), dtype="int4")
    with pytest.raises(ValueError, match="unknown"):
        quant.code_dtype("int4")
    with pytest.raises(ValueError, match="unknown"):
        quant.roundtrip_bound("int4")


def test_code_bytes_per_element():
    assert quant.code_bytes_per_element("int8") == 1
    assert quant.code_bytes_per_element("fp8") == 1


def test_int8_negation_roundtrips(rng):
    """Symmetric [-127, 127]: quantizing -x gives exactly -codes, so
    sign structure survives the codec."""
    x = rng.normal(size=(4, 8)).astype(np.float32)
    c_pos, s_pos = quant.quantize_blocks(x, dtype="int8")
    c_neg, s_neg = quant.quantize_blocks(-x, dtype="int8")
    np.testing.assert_array_equal(c_neg, -c_pos)
    np.testing.assert_array_equal(s_neg, s_pos)


# -- quantized paged pools ---------------------------------------------------

def _qpool(n_slots=2, page_len=4, max_len=16, **kw):
    return PagedKVCache(n_slots, layers=2, kv_heads=2,
                        page_len=page_len, head_dim=4, max_len=max_len,
                        kv_dtype="int8", **kw)


def test_quant_pool_fresh_pages_gather_exact_zeros():
    pool = _qpool()
    assert isinstance(pool.k, QuantizedKVPool)
    g = np.asarray(gather_pages(pool.k, jnp.asarray([[1, 2]])))
    assert g.shape == (1, 2, 2, 8, 4) and (g == 0.0).all()


def test_quant_pool_scatter_gather_roundtrip_within_bound(rng):
    pool = _qpool(n_pages=9)
    rows = rng.normal(size=(8, 2, 2, 4)).astype(np.float32)
    pages = jnp.asarray([1, 1, 1, 1, 2, 2, 2, 2])
    offs = jnp.asarray([0, 1, 2, 3, 0, 1, 2, 3])
    pool.k = scatter_rows(pool.k, pages, offs, jnp.asarray(rows))
    g = np.asarray(gather_pages(pool.k, jnp.asarray([[1, 2]])))[0]
    got = np.transpose(g, (2, 0, 1, 3))        # [T, L, KV, D]
    bound = np.abs(rows).max(-1, keepdims=True) / 127.0 * 0.5
    assert (np.abs(got - rows) <= bound + 1e-7).all()


def test_quant_pool_nbytes_counts_codes_and_scales():
    qp, fp = _qpool(), PagedKVCache(2, layers=2, kv_heads=2,
                                    page_len=4, head_dim=4, max_len=16)
    assert qp.k.nbytes == qp.k.codes.nbytes + qp.k.scales.nbytes
    # codes are 1/4 the f32 bytes; scales add 1/head_dim of f32 bytes
    assert qp.k.nbytes == fp.k.nbytes // 4 + fp.k.nbytes // 4
    assert qp.k.nbytes < fp.k.nbytes


def test_quant_pool_layer_slice_matches_full_gather(rng):
    """pool[:, :n] (the truncated self-draft gather) slices codes and
    scales coherently: dequantized rows equal the full gather's."""
    pool = _qpool(n_pages=9)
    rows = rng.normal(size=(4, 2, 2, 4)).astype(np.float32)
    pool.k = scatter_rows(pool.k, jnp.asarray([1, 1, 1, 1]),
                          jnp.asarray([0, 1, 2, 3]), jnp.asarray(rows))
    full = np.asarray(gather_pages(pool.k, jnp.asarray([[1]])))
    part = np.asarray(gather_pages(pool.k[:, :1], jnp.asarray([[1]])))
    np.testing.assert_array_equal(part, full[:, :1])


def test_quant_cow_fork_copies_codes_and_scales(rng):
    """A CoW fork of a quantized shared page starts bit-identical in
    BOTH leaves, and post-fork writes leave the sibling's codes and
    scales untouched — forked pages keep independent scales."""
    pool = _qpool(n_pages=9)
    src = pool.alloc(owner="src", n_tokens=8)
    rows = rng.normal(size=(8, 2, 2, 4)).astype(np.float32)
    phys = [pool._slot_pages[src][t // 4] for t in range(8)]
    pool.k = scatter_rows(pool.k, jnp.asarray(phys),
                          jnp.asarray(np.arange(8) % 4),
                          jnp.asarray(rows))
    dst = 1 - src
    pool._free_slots.remove(dst)
    pool.share_pages(src, dst, 2)
    shared0 = pool._slot_pages[src][0]
    codes_before = np.asarray(pool.k.codes[shared0]).copy()
    scales_before = np.asarray(pool.k.scales[shared0]).copy()
    forks = pool.ensure_writable(dst, 2, 1)
    assert forks == 1 and pool.cow_fork_count == 1
    new0 = pool._slot_pages[dst][0]
    assert new0 != shared0
    np.testing.assert_array_equal(np.asarray(pool.k.codes[new0]),
                                  codes_before)
    np.testing.assert_array_equal(np.asarray(pool.k.scales[new0]),
                                  scales_before)
    # divergent write into the FORK, at 50x the magnitude: its scale
    # rows move, the sibling's stay bitwise where they were
    big = (50.0 * rows[2:3]).astype(np.float32)
    pool.k = scatter_rows(pool.k, jnp.asarray([new0]),
                          jnp.asarray([2]), jnp.asarray(big))
    np.testing.assert_array_equal(np.asarray(pool.k.codes[shared0]),
                                  codes_before)
    np.testing.assert_array_equal(np.asarray(pool.k.scales[shared0]),
                                  scales_before)
    assert (np.asarray(pool.k.scales[new0])[:, :, 2]
            > scales_before[:, :, 2]).all()
    pool.free(src)
    pool.free(dst)
    a = pool.audit()
    assert a["page_allocs"] == a["page_frees"]


def test_cow_guard_trips_on_quantized_shared_page():
    pool = _qpool(n_pages=9)
    src = pool.alloc(owner="src", n_tokens=8)
    dst = 1 - src
    pool._free_slots.remove(dst)
    pool.share_pages(src, dst, 2)
    with pytest.raises(AssertionError, match="refcount"):
        pool.assert_writable(dst, 2, 1)
    pool.ensure_writable(dst, 2, 1)
    pool.assert_writable(dst, 2, 1)      # fork made it writable


def test_fp8_pool_codes_are_e4m3():
    pool = PagedKVCache(2, layers=2, kv_heads=2, page_len=4,
                        head_dim=4, max_len=16, kv_dtype="fp8")
    assert pool.k.codes.dtype == quant.code_dtype("fp8")


# -- quantized serving: opt-in + divergence gate -----------------------------

def _llama(name, seq_len=16):
    c = LlamaConfig(vocab_size=V, hidden_size=32, num_layers=2,
                    num_heads=4, num_kv_heads=2, intermediate_size=56,
                    seq_len=seq_len)
    model = LlamaForCausalLM(c, name=name)
    ids = ht.placeholder_op(f"{name}_ids", (1, 4), dtype=np.int32)
    ex = ht.Executor([model(ids)])
    return ex, model


def _engine(ex, model, name, **kw):
    base = dict(n_slots=2, max_len=32, max_prompt_len=16, name=name,
                paged=True, page_len=4)
    base.update(kw)
    return InferenceEngine(ex, model, **base)


def _prompts(rng, n, lo=3, hi=9):
    return [rng.integers(1, V, (int(L),))
            for L in rng.integers(lo, hi, n)]


def test_kv_dtype_requires_paged():
    ex, model = _llama("qreq")
    with pytest.raises(ValueError, match="paged"):
        InferenceEngine(ex, model, n_slots=2, max_len=32,
                        max_prompt_len=16, name="qreq",
                        kv_dtype="int8")


def test_gather_dtype_requires_mesh():
    ex, model = _llama("greq")
    with pytest.raises(ValueError, match="mesh"):
        _engine(ex, model, "greq", gather_dtype="int8")


def test_quant_engine_streams_near_f32_twin(rng):
    """The quantized engine is an ERROR-BOUNDED twin of the f32 one:
    streams may diverge, but on this tiny model most requests should
    still decode identically, everything must finish, and the page
    audit must balance (quantization never perturbs bookkeeping)."""
    ex, model = _llama("qtw")
    prompts = _prompts(rng, 6)
    f32 = _engine(ex, model, "qtw", instance="f32")
    q = _engine(ex, model, "qtw", instance="q8", kv_dtype="int8")
    outs_f = f32.generate_many(prompts, 10)
    outs_q = q.generate_many(prompts, 10)
    assert all(len(o) == 10 for o in outs_q)
    agree = sum(list(a) == list(b) for a, b in zip(outs_f, outs_q))
    assert agree >= len(prompts) // 2
    a = q.cache.audit()
    assert a["page_allocs"] == a["page_frees"] and a["in_use"] == 0


def test_spec_verify_over_quantized_kv_within_gate(rng):
    """Speculation over quantized pages: the spec-quant engine's
    streams agree with its non-speculative quantized twin on most
    requests (the verify window attends fresh float rows where plain
    decode attends round-tripped ones, so bitwise is NOT the contract
    here — bounded divergence is), all streams complete, and rollback
    bookkeeping still balances the audit."""
    ex, model = _llama("sqv")
    prompts = _prompts(rng, 6)
    plain = _engine(ex, model, "sqv", instance="plainq",
                    kv_dtype="int8")
    spec = _engine(ex, model, "sqv", instance="specq", kv_dtype="int8",
                   spec_k=3, draft_layers=1)
    outs_p = plain.generate_many(prompts, 10)
    outs_s = spec.generate_many(prompts, 10)
    assert all(len(o) == 10 for o in outs_s)
    agree = sum(list(a) == list(b) for a, b in zip(outs_p, outs_s))
    assert agree >= len(prompts) // 2
    a = spec.cache.audit()
    assert a["page_allocs"] == a["page_frees"] and a["in_use"] == 0


def test_f32_engine_unchanged_by_quant_plumbing(rng):
    """Opt-in guarantee: an engine WITHOUT kv_dtype produces streams
    bitwise equal to the one-shot oracle, and its program keys carry
    no quantization components (compile sharing with pre-quant twins
    is preserved)."""
    from hetu_tpu.models.llama_decode import greedy_generate
    ex, model = _llama("qoff")
    prompts = _prompts(rng, 4)
    eng = _engine(ex, model, "qoff")
    outs = eng.generate_many(prompts, 8)
    for p, o in zip(prompts, outs):
        want = greedy_generate(ex, model, np.asarray(p)[None], 8,
                               name="qoff")[0, len(p):]
        np.testing.assert_array_equal(np.asarray(o), want)
    key = str(eng._program_key())
    assert "kv_dtype" not in key and "gather_dtype" not in key


# -- the AST gate ------------------------------------------------------------

_NARROW = ("int8", "uint8", "float8", "fp8", "e4m3", "e5m2")
_PKG = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "hetu_tpu")
#: the one module allowed to spell a narrow cast
_ALLOWED = {os.path.join("ops", "quant.py")}


def _narrow_cast_sites(tree, rel):
    """(file, line, snippet) for every ``x.astype(<narrow dtype>)`` and
    every ``bitcast_convert_type`` call in ``tree``."""
    sites = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not isinstance(f, ast.Attribute):
            continue
        if f.attr == "bitcast_convert_type":
            sites.append((rel, node.lineno, "bitcast_convert_type"))
        elif f.attr == "astype" and node.args:
            arg = ast.unparse(node.args[0]).lower()
            if any(m in arg for m in _NARROW):
                sites.append((rel, node.lineno, f"astype({arg})"))
    return sites


def test_narrow_casts_only_in_shared_codec():
    """Every narrow-dtype cast in the package goes through
    ops/quant.py — an inline ``astype(int8)`` anywhere else would be
    quantization outside the proved error bounds."""
    bad = []
    for root, _, files in os.walk(_PKG):
        for fn in sorted(files):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            rel = os.path.relpath(path, _PKG)
            if rel in _ALLOWED:
                continue
            with open(path, encoding="utf-8") as fh:
                tree = ast.parse(fh.read(), filename=rel)
            bad += _narrow_cast_sites(tree, rel)
    assert not bad, (
        "narrow-dtype casts outside ops/quant.py (route them through "
        f"the shared codec): {bad}")


def test_narrow_cast_scanner_catches_offenders():
    """Self-test: the scanner flags the casts it exists to catch and
    passes ordinary wide-dtype code."""
    offender = ("import jax, jax.numpy as jnp\n"
                "def f(x):\n"
                "    y = x.astype(jnp.int8)\n"
                "    z = x.astype('float8_e4m3fn')\n"
                "    return jax.lax.bitcast_convert_type(y, jnp.uint8)\n")
    got = _narrow_cast_sites(ast.parse(offender), "bad.py")
    assert len(got) == 3
    assert {s[1] for s in got} == {3, 4, 5}
    clean = ("import numpy as np\n"
             "def f(x):\n"
             "    return x.astype(np.float32).astype('int32')\n")
    assert not _narrow_cast_sites(ast.parse(clean), "ok.py")
