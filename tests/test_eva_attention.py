"""EVA attention (``ops/eva.py``, the flash kernels' third static plan in
``ops/pallas/flash_attention.py``): the kernels in interpret mode against the
dense ``jax.numpy`` form and against the plain reference written from the
equations (``chipbench/reference/evabyte.py``), values and every gradient; the
three identities that tie EVA to the attention the repo has; the plan against
the dense mask tile by tile; what ``unsupported`` refuses; what is counted."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.ops import eva
from hetu_tpu.ops.pallas import dispatch, flash_attention as fa

from chipbench.reference import evabyte as ref


def gap(a, b):
    a, b = (np.asarray(t, np.float64) for t in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def operands(seq, heads, d, seed=0):
    r = np.random.default_rng(seed)
    q, k, v, g = (jnp.asarray(r.standard_normal((1, seq, heads * d)),
                              jnp.float32) for _ in range(4))
    phi, mu = (jnp.asarray(r.standard_normal((heads, d)), jnp.float32)
               for _ in range(2))
    return q, k, v, phi, mu, g


def through_the_kernels(window, chunk, heads):
    def f(q, k, v, phi, mu):
        ks, vs = eva.chunk_summaries(k, v, phi, mu, chunk=chunk)
        out = fa.flash_attention(q, k, v, causal=True, num_heads=heads,
                                 eva=(window, chunk), summaries=(ks, vs))
        assert out is not None, "the kernels refused"
        return out
    return f


def dense(window, chunk, heads):
    def f(q, k, v, phi, mu):
        ks, vs = eva.chunk_summaries(k, v, phi, mu, chunk=chunk)
        return eva.dense_eva_attention(q, k, v, ks, vs, window=window,
                                       chunk=chunk, num_heads=heads)
    return f


def plain(window, chunk, heads, without=()):
    """The plain reference's EVA on the same flat operands."""
    c = {"window_size": window, "chunk_size": chunk, "rope_theta": 1e5}

    def f(q, k, v, phi, mu):
        q, k, v = (x[0].reshape(x.shape[1], heads, -1) for x in (q, k, v))
        return ref.eva(q, k, v, phi, mu, c, without=without)[None]
    return f


#: (positions, window, chunk, heads, head size): windows of 1, 2 and 4 tiles
#: of 128, a ragged last window, a padded sequence, two heads a program
SHAPES = {"one_tile_windows": (512, 128, 8, 2, 64),
          "two_tile_windows": (512, 256, 16, 1, 128),
          "four_tile_windows": (1024, 512, 16, 1, 128),
          "ragged_last_window": (640, 256, 16, 2, 64),
          "padded_sequence": (300, 128, 8, 2, 64)}


@pytest.mark.parametrize("form", ["dense", "plain"])
@pytest.mark.parametrize("shape", SHAPES)
def test_the_kernels_are_the_equations(shape, form):
    """Output and the gradients of q, k, v, phi and mu (the last two reach the
    kernels through the summaries' ``d k^`` and ``d v^`` alone)."""
    seq, window, chunk, heads, d = SHAPES[shape]
    *args, g = operands(seq, heads, d)
    other = {"dense": dense, "plain": plain}[form](window, chunk, heads)
    with jax.default_matmul_precision("highest"):
        got, pull = jax.vjp(through_the_kernels(window, chunk, heads), *args)
        want, pull_want = jax.vjp(other, *args)
        assert gap(got, want) < 2e-6
        for name, a, b in zip("q k v phi mu".split(), pull(g), pull_want(g)):
            assert gap(a, b) < 5e-6, name


IDENTITIES = {
    "one_window_is_causal": dict(window=512, chunk=16, zero=()),
    "chunks_of_one_key_without_mu_are_causal": dict(window=128, chunk=1,
                                                    zero=("mu",)),
}


@pytest.mark.parametrize("form", ["dense", "plain", "kernels"])
@pytest.mark.parametrize("case", IDENTITIES)
def test_eva_is_causal_softmax_attention_where_it_must_be(case, form):
    """``window >= S`` sees every earlier key exactly; ``chunk = 1`` with ``mu
    = 0`` makes every summary its one key and value, at ANY window."""
    how = IDENTITIES[case]
    window, chunk = how["window"], how["chunk"]
    seq, heads, d = 512, 2, 64
    q, k, v, phi, mu, _ = operands(seq, heads, d, seed=3)
    if "mu" in how["zero"]:
        mu = jnp.zeros_like(mu)
    f = {"dense": dense, "plain": plain, "kernels": through_the_kernels}[
        form](window, chunk, heads)
    with jax.default_matmul_precision("highest"):
        got = f(q, k, v, phi, mu)
        qh, kh, vh = (x.reshape(1, seq, heads, d) for x in (q, k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", qh, kh) * d ** -0.5
        seen = jnp.tril(jnp.ones((seq, seq), bool))
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
            jnp.where(seen, scores, -jnp.inf), -1), vh).reshape(1, seq, -1)
    assert gap(got, want) < 2e-6


def test_the_kernels_with_chunks_of_one_key_are_causal_attention():
    seq, window, heads, d = 512, 128, 2, 64
    q, k, v, phi, _, _ = operands(seq, heads, d, seed=4)
    with jax.default_matmul_precision("highest"):
        got = through_the_kernels(window, 1, heads)(
            q, k, v, phi, jnp.zeros((heads, d)))
        want = fa.flash_attention(q, k, v, causal=True, num_heads=heads)
    assert gap(got, want) < 2e-6


def test_phi_zero_makes_a_chunks_value_its_mean():
    q, k, v, phi, mu, _ = operands(64, 2, 32, seed=5)
    ks, vs = eva.chunk_summaries(k, v, jnp.zeros_like(phi), mu, chunk=4)
    mean = lambda x: x.reshape(1, 16, 4, -1).mean(2)
    np.testing.assert_allclose(vs, mean(v), atol=1e-6)
    np.testing.assert_allclose(ks, mean(k) + mu.reshape(1, 1, -1), atol=1e-6)


@pytest.mark.parametrize("seq, window, rows", [(64, 32, 8), (96, 32, 16),
                                               (64, 64, 0), (40, 64, 0)])
def test_a_layer_summarises_the_windows_that_are_read(seq, window, rows):
    """Every window but the last: the same rows as of all chunks, fewer; one
    window has none, and the dense form is then causal attention."""
    q, k, v, phi, mu, _ = operands(seq, 2, 32, seed=9)
    upto = eva.summarised(seq, window)
    ks, vs = eva.chunk_summaries(k, v, phi, mu, chunk=4, upto=upto)
    all_ks, all_vs = eva.chunk_summaries(k, v, phi, mu, chunk=4)
    assert ks.shape == vs.shape == (1, rows, 64) and upto == 4 * rows
    np.testing.assert_array_equal(ks, all_ks[:, :rows])
    np.testing.assert_array_equal(vs, all_vs[:, :rows])
    with jax.default_matmul_precision("highest"):
        few = eva.dense_eva_attention(q, k, v, ks, vs, window=window, chunk=4,
                                      num_heads=2)
        every = eva.dense_eva_attention(q, k, v, all_ks, all_vs,
                                        window=window, chunk=4, num_heads=2)
    assert gap(few, every) < 1e-6


@pytest.mark.parametrize("control", ref.CONTROLS)
def test_a_changed_piece_is_not_the_kernels(control):
    """Each control of the plain reference moves the output by far more than
    the kernels lie from the reference (2e-6; the summaries' sums in bf16
    move the remote term's tenth of the output by bf16's 0.4%)."""
    seq, window, chunk, heads, d = 512, 128, 8, 2, 64
    *args, _ = operands(seq, heads, d, seed=6)
    with jax.default_matmul_precision("highest"):
        got = through_the_kernels(window, chunk, heads)(*args)
        changed = plain(window, chunk, heads, without=(control,))(*args)
    assert gap(got, changed) > (1e-4 if control == "summaries_bf16" else 1e-3)


# -- the plan, tile by tile ----------------------------------------------------

def tile_kinds(seq, window, chunk, tile, rows):
    """``{(query tile, ("k" | "s", key tile)): "whole" | "edge"}`` from the
    dense mask alone: a tile every pair of which is seen, or some."""
    mask = np.asarray(eva.eva_mask(seq, window, chunk))
    out = {}
    for qi in range(seq // tile):
        block = mask[qi * tile:(qi + 1) * tile]
        for kind, cols, width in (("k", block[:, :seq], tile),
                                  ("s", block[:, seq:], rows)):
            for j in range(cols.shape[1] // width):
                part = cols[:, j * width:(j + 1) * width]
                if part.any():
                    out[qi, (kind, j)] = "whole" if part.all() else "edge"
    return out


@pytest.mark.parametrize("seq, window, chunk", [
    (1024, 128, 8), (1024, 256, 16), (2048, 512, 16), (640, 256, 16),
    (8192, 2048, 16)])
def test_the_plan_walks_the_masks_tiles_and_no_other(live_registry, seq,
                                                     window, chunk):
    """Forward and backward: the tiles walked whole are those the dense mask
    shows all seen, the tiles under an edge the diagonal's, none else; the
    pairs counted are the mask's."""
    tile, (span, n, rows), read = fa._eva_plan(seq, seq, window, chunk)
    assert window % tile == 0 and span == window and n == window // chunk
    want = tile_kinds(seq, window, chunk, tile, rows)
    assert read == (seq - 1) // window * n
    for which, (whole, edge) in fa._eva_tiles(seq // tile, window // tile, n,
                                              rows).items():
        got = {**dict.fromkeys(whole, "whole"), **dict.fromkeys(edge, "edge")}
        assert len(got) == len(whole) + len(edge), which
        assert got == want, which
    mask = np.asarray(eva.eva_mask(seq, window, chunk))
    assert fa.eva_pairs(seq, window, chunk) == (mask[:, :seq].sum(),
                                                mask[:, seq:].sum())


def test_the_cells_plan_is_the_issues_count():
    assert sum(fa.eva_pairs(8192, 2048, 16)) == 9965568
    assert 8192 * 8193 // 2 == 33558528


# -- what is refused, what is counted ------------------------------------------

REFUSED = {
    "eva_with_dropout": dict(eva=(128, 16), dropout_keep=0.9),
    "eva_with_mask": dict(eva=(128, 16), mask=np.zeros((1, 1, 1, 512))),
    "eva_window_not_128_aligned": dict(eva=(192, 16)),
    "eva_chunk_not_dividing_128": dict(eva=(384, 24)),
    "eva_window_summaries_not_16_aligned": dict(eva=(128, 16 * 2)),
    "eva_grouped_queries": dict(eva=(128, 8), kv_heads=2),
}


@pytest.mark.parametrize("reason", REFUSED)
def test_unsupported_names_what_the_plan_does_not_take(reason):
    kw = dict(REFUSED[reason])
    q = jax.ShapeDtypeStruct((1, 4, 512, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, kw.pop("kv_heads", 4), 512, 128),
                             jnp.bfloat16)
    assert fa.unsupported(q, k, k, **kw) == reason
    assert fa.unsupported(q, q, q, eva=(128, 8)) is None


@pytest.mark.parametrize("platform, seq, window, choice", [
    ("cpu", 512, 128, ("eva", "jnp", "platform:cpu")),
    ("tpu", 512, 128, ("eva", "pallas", "")),
    ("tpu", 512, 96, ("eva", "jnp", "eva_window_not_128_aligned")),
    ("tpu", 128, 128, ("eva", "jnp", "seq<256")),
])
def test_the_entry_records_its_choice(monkeypatch, live_registry, platform,
                                      seq, window, choice):
    """On a TPU the kernels, elsewhere and where the plan refuses the dense
    form: both are the same numbers, and the choice is counted."""
    monkeypatch.setattr(dispatch, "platform", lambda: platform)
    monkeypatch.setattr(fa, "interpret", lambda: True)
    jax.clear_caches()
    heads, d, chunk = 2, 64, 8
    q, k, v, phi, mu, _ = operands(seq, heads, d, seed=7)
    ks, vs = eva.chunk_summaries(k, v, phi, mu, chunk=chunk)
    before = dispatch.choices().get(choice, 0)
    entries = dict(fa.entries())
    with jax.default_matmul_precision("highest"):
        got = eva.eva_attention(q, k, v, ks, vs, window=window, chunk=chunk,
                                num_heads=heads)
        want = dense(window, chunk, heads)(q, k, v, phi, mu)
    jax.clear_caches()
    assert dispatch.choices()[choice] == before + 1
    assert gap(got, want) < 2e-6
    if choice[1] == "pallas":
        key = (f"bshd_eva{window}c{chunk}", 2)
        assert fa.entries()[key] == entries.get(key, 0) + 1


def test_under_a_mesh_the_dense_form_runs(monkeypatch, live_registry):
    from jax.sharding import Mesh
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    q, k, v, phi, mu, _ = operands(512, 2, 64, seed=8)
    ks, vs = eva.chunk_summaries(k, v, phi, mu, chunk=8)
    before = dispatch.choices().get(("eva", "jnp", "mesh"), 0)
    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    eva.eva_attention(q, k, v, ks, vs, window=128, chunk=8, num_heads=2,
                      mesh=mesh)
    assert dispatch.choices()[("eva", "jnp", "mesh")] == before + 1
