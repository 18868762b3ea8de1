"""The state-space scan's Pallas kernel pair (``hetu_tpu/ops/pallas/ssd.py``),
in interpret mode on the CPU: against the recurrence one position at a time
in f32 and against the ``jax.numpy`` chunked form in bf16 at the published
head and state sizes, outputs, last state and the gradient of all five
inputs; the fastest and the slowest decays the model can draw; a long memory,
where a bf16 state is seen and the kernels are not; the rule by which
``chunk_ssd`` takes them; the ``Mamba2`` layer through them against the same
layer through the ``jax.numpy`` form; and the layer's train step compiled for
a described v5e; and a group of more heads than a program holds (Granite
4.0-H: 64 heads on one ``B`` and ``C``) cut into blocks of heads; and the
pair's second entry, ``ssd_in_place``, which reads ``x | B | C`` where the
convolution wrote them and adds the skip itself, against the slices, the
first entry and the layer's ``jax.numpy`` skip, with the rule that admits
it."""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu.ops import ssd
from hetu_tpu.ops.ssd import chunk_ssd, chunk_ssd_jnp, recurrent_ssd
from hetu_tpu.ops.pallas import dispatch, ssd as kernels

P, N = 64, 128                # the published head and state sizes


def ssd_inputs(T, b=1, H=4, G=1, dtype=jnp.float32, seed=0, decay=None,
               dt_mean=0.7):
    """``x`` standard normal, ``B`` and ``C`` of unit length on average;
    ``dt`` about ``dt_mean`` and ``A`` of 0.003 to 1 (as ``tests/
    test_ssd_scan.py``), or ``A`` such that head ``j`` forgets ``decay``
    (first head, last head; log-spaced) a position at ``dt = 1``."""
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(b, T, H, P)), dtype)
    dt = jnp.asarray(np.logaddexp(0, r.normal(size=(b, T, H)))
                     * dt_mean / 0.8, jnp.float32)
    A = -jnp.asarray(np.geomspace(*(decay or (3e-3, 1.0)), H), jnp.float32)
    Bm, Cm = (jnp.asarray(r.normal(size=(b, T, G, N)) * N ** -0.5, dtype)
              for _ in range(2))
    return x, dt, A, Bm, Cm


def weighted_grad(fn, y_shape, s_shape, seed=2):
    r = np.random.default_rng(seed)
    w_y, w_s = (jnp.asarray(r.normal(size=s), jnp.float32)
                for s in (y_shape, s_shape))

    def f(*a):
        y, last = fn(*a)
        return jnp.sum(y.astype(jnp.float32) * w_y) + jnp.sum(last * w_s)
    return jax.grad(f, argnums=range(5))


def rel(got, want):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


def l2_gap(got, want, axes=None):
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    return np.sqrt(((got - want) ** 2).sum(axes) / (want ** 2).sum(axes))


NAMES = ("x", "dt", "A", "B", "C")


@pytest.mark.parametrize("T,b,H,G", [(128, 1, 4, 1), (200, 2, 8, 1),
                                     (300, 1, 8, 2), (70, 2, 16, 2),
                                     (1100, 1, 4, 1), (200, 1, 16, 1),
                                     (130, 2, 64, 1), (140, 1, 24, 2)])
def test_kernels_are_the_recurrence(T, b, H, G):
    """f32 operands, at the tolerances ``tests/test_ssd_scan.py`` holds the
    ``jax.numpy`` form to: outputs, last state and the gradient of x, dt, A,
    B and C; 4 and 8 heads a group, one and two groups and sequences, lengths
    that are and are not a multiple of the chunk, one and several chunks a
    program and more than one program a sequence; and groups of 16, of 64
    and of 12 heads, which run as two and eight blocks of eight heads and
    two of six: ``dB`` and ``dC`` are then sums over a group's blocks."""
    args = ssd_inputs(T, b, H, G)
    y1, s1 = jax.jit(recurrent_ssd)(*args)
    y2, s2 = kernels.ssd(*args)
    assert y2.shape == y1.shape and y2.dtype == y1.dtype
    assert s2.shape == (b, H, P, N) and s2.dtype == jnp.float32
    assert rel(y2, y1) < 2e-5 and rel(s2, s1) < 2e-5
    want = jax.jit(weighted_grad(recurrent_ssd, y1.shape, s1.shape))(*args)
    got = weighted_grad(kernels.ssd, y1.shape, s1.shape)(*args)
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert np.abs(np.asarray(w)).max() > 0, name
        assert rel(g, w) < 1e-4, name


@pytest.mark.parametrize("T,b,H,G", [(128, 2, 4, 1), (300, 1, 8, 2),
                                     (1000, 1, 4, 1), (300, 1, 16, 1),
                                     (200, 1, 64, 1)])
def test_kernels_are_the_chunked_form_in_bf16(T, b, H, G):
    """bf16 x, B and C against the ``jax.numpy`` chunked form on the same
    operands (the last case is the benchmark's probe cut short: 4 heads of
    64 in one group, state 128).  Each rounds one operand a product to bf16,
    not the same one (the kernels ``C B^T L dt`` and ``B dt to_end``, the
    ``jax.numpy`` form ``C B^T L``, ``dt x`` and ``dt x to_end``), so they
    differ by bf16's rounding of single terms: a step of the largest entry,
    and in L2, where the roundings average out, far less; and the kernels
    are about as far from the f32 recurrence on the same operands as the
    ``jax.numpy`` form is (a gradient of the decays taken from ``dy . y``,
    which cancels, was forty times as far in ``dA`` and eight in ``ddt``)."""
    args = ssd_inputs(T, b, H, G, jnp.bfloat16, seed=1)
    f32 = tuple(t.astype(jnp.float32) for t in args)
    y0, s0 = jax.jit(recurrent_ssd)(*f32)
    y1, s1 = jax.jit(chunk_ssd_jnp)(*args)
    y2, s2 = kernels.ssd(*args)
    assert y2.dtype == jnp.bfloat16 and s2.dtype == jnp.float32
    assert rel(y2, y1) < 8e-3 and l2_gap(y2, y1) < 4e-3
    assert l2_gap(s2, s1) < 4e-3
    assert l2_gap(y2, y0) < 1.2 * l2_gap(y1, y0)
    assert l2_gap(s2, s0) < 1.2 * l2_gap(s1, s0)
    grad = lambda fn: weighted_grad(fn, y1.shape, s1.shape)
    truth = jax.jit(grad(recurrent_ssd))(*f32)
    want = jax.jit(grad(chunk_ssd_jnp))(*args)
    got = grad(kernels.ssd)(*args)
    for name, g, w, t in zip(NAMES, got, want, truth):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert l2_gap(g, w) < 1e-2 and l2_gap(g, t) < 1e-2, name
        # dA is four numbers, each a sum over every position of errors that
        # a slow head's state carries for hundreds of positions: either
        # form is the nearer one on some seed
        assert name == "A" or l2_gap(g, t) < 1.2 * l2_gap(w, t) + 1e-4, name


@pytest.mark.parametrize("H,G,P_,want", [
    (64, 8, 64, (8, 8)),          # Nemotron-H: a group a program, as before
    (8, 1, 128, (8, 8)), (4, 1, 64, (4, 4)),
    (64, 1, 64, (64, 8)),         # Granite 4.0-H: eight blocks of eight
    (16, 1, 64, (16, 8)), (24, 2, 64, (12, 6)), (10, 1, 128, (10, 5))])
def test_a_wide_group_is_cut_into_blocks_of_heads(live_registry, H, G, P_,
                                                  want):
    """The most heads, up to eight, that divide a group and fill 128-lane
    tiles; counted where the call is traced."""
    before = kernels.entries()
    assert kernels.heads_a_program(H // G, P_) == want[1]
    sds = jax.ShapeDtypeStruct
    bc = sds((1, 256, G, N), jnp.bfloat16)
    y, last = jax.eval_shape(
        kernels.ssd, sds((1, 256, H, P_), jnp.bfloat16),
        sds((1, 256, H), jnp.float32), sds((H,), jnp.float32), bc, bc)
    assert y.shape == (1, 256, H, P_) and last.shape == (1, H, P_, N)
    assert {k: n - before.get(k, 0) for k, n in kernels.entries().items()
            if n > before.get(k, 0)} == {want: 1}


def test_eight_heads_a_group_read_their_rows_as_before():
    """At eight heads a group nothing of the cut is in the call: one block a
    group, ``B`` and ``C`` blocks indexed by the group, ``dB`` and ``dC``
    written whole (no sum over blocks in the traced function)."""
    grad = weighted_grad(kernels.ssd, (1, 256, 16, P), (1, 16, P, N))

    def sums_after_the_backward_kernel(G):
        text = str(jax.make_jaxpr(grad)(*ssd_inputs(256, 1, 16, G,
                                                    jnp.bfloat16)))
        assert (text.count("hetu_ssd_fwd"), text.count("hetu_ssd_bwd")) == (
            1, 1)
        return text.split("hetu_ssd_bwd")[1].count("reduce_sum")
    # two groups of eight: the sums that give dA from da and nothing else;
    # one group of sixteen: dB's and dC's over the group's two blocks too
    assert sums_after_the_backward_kernel(1) == (
        sums_after_the_backward_kernel(2) + 2)


@pytest.mark.parametrize("rate,dt_mean", [(3.0, 1.0), (1e-4, 1.0),
                                          (16.0, 0.1)])
def test_decays_at_the_ends_of_what_the_model_draws(rate, dt_mean):
    """All heads at one rate: ``a`` about -3 a position (``dt`` 0.1 at ``A``
    16 and beyond: a chunk's running sum reaches -400 and its differences
    still give the decays), ``dt |A|`` of 1e-4 (a chunk's running sum is
    -0.013 and the decays are within 1.3% of one), and the published
    initial extreme, ``A`` 16 at ``dt`` 0.1.  bf16 operands: the kernels'
    outputs within bf16's rounding of the ``jax.numpy`` form's, which builds
    its decays from sums of non-positive terms; in f32 both are the
    recurrence, the gradients to the f32 spacing of a running sum of 400 (3e-5
    in a decay) where they are sums of ten such terms."""
    args = ssd_inputs(384, 1, 4, 1, jnp.bfloat16, seed=3,
                      decay=(rate, rate), dt_mean=dt_mean)
    y1, s1 = jax.jit(chunk_ssd_jnp)(*args)
    y2, s2 = kernels.ssd(*args)
    assert rel(y2, y1) < 8e-3 and l2_gap(y2, y1) < 4e-3
    assert l2_gap(s2, s1) < 4e-3
    f32 = tuple(t.astype(jnp.float32) for t in args)
    y0, s0 = jax.jit(recurrent_ssd)(*f32)
    y3, s3 = kernels.ssd(*f32)
    assert rel(y3, y0) < 2e-5 and rel(s3, s0) < 2e-5
    grad = lambda fn: weighted_grad(fn, y0.shape, s0.shape)
    for name, g, w in zip(NAMES, grad(kernels.ssd)(*f32),
                          jax.jit(grad(recurrent_ssd))(*f32)):
        assert rel(g, w) < 5e-4, name


def test_a_long_memory_sees_a_bf16_state_and_not_the_kernels():
    """Decays of 1e-4 to 1e-1 a position over 2,048 positions (the
    benchmark's probe, 4 heads in one group, at a length the CPU can walk):
    the last state of a recurrence that carries its state in bf16 is off by
    more than 1%, the kernels' on bf16 operands by what the ``jax.numpy``
    form's is, and on f32 operands by rounding."""
    def gap(got, want):
        return l2_gap(got, want, (0, 2, 3)).max()
    args = ssd_inputs(2048, 1, 4, 1, jnp.bfloat16, seed=7,
                      decay=(1e-4, 1e-1), dt_mean=1.0)
    f32 = tuple(t.astype(jnp.float32) for t in args)
    exact = jax.jit(recurrent_ssd)(*f32)[1]
    low = jax.jit(lambda *a: recurrent_ssd(*a, state_dtype=jnp.bfloat16)
                  )(*f32)[1]
    assert gap(low, exact) > 1e-2
    plain = gap(jax.jit(chunk_ssd_jnp)(*args)[1], exact)
    mine = gap(kernels.ssd(*args)[1], exact)
    assert mine < 4e-3 and mine < 1.2 * plain
    assert gap(kernels.ssd(*f32)[1], exact) < 5e-6


# -- the rule of dispatch -----------------------------------------------------

def rule_inputs(p=P, n=N, dtype=jnp.bfloat16, H=4, G=1, T=128, b=1,
                bc_dtype=None):
    sds = jax.ShapeDtypeStruct
    bc = sds((b, T, G, n), bc_dtype or dtype)
    return (sds((b, T, H, p), dtype), sds((b, T, H), jnp.float32),
            sds((H,), jnp.float32), bc, bc)


@pytest.fixture
def ssd_choices(live_registry):
    """``{(impl, reason): count}`` of the rule's choices since the test
    began (the registry is the process's: ``conftest.live_registry``)."""
    before = dispatch.choices()

    def since():
        return {k[1:]: n - before.get(k, 0)
                for k, n in dispatch.choices().items()
                if k[0] == "ssd" and n > before.get(k, 0)}
    return since


def test_nothing_is_recorded_on_the_cpu(ssd_choices, monkeypatch):
    """No Mosaic, no choice: the ``jax.numpy`` form runs, bit for bit, and the
    counter stays empty (the benchmark's rehearsal counts every ``jnp`` sample
    it does not know as unexplained)."""
    monkeypatch.setattr(kernels, "ssd", None)                 # never reached
    args = ssd_inputs(200, 1, 8, 2, seed=2)
    for a, b in zip(chunk_ssd(*args), chunk_ssd_jnp(*args)):
        np.testing.assert_array_equal(a, b)
    assert ssd_choices() == {}


@pytest.mark.parametrize("why,kw,chunk", [
    (None, {}, 128),                           # the benchmark's probe
    (None, dict(H=64, G=8, T=8192), 128),      # the Nemotron-H cell's mixer
    (None, dict(H=64, G=1, T=8192), 128),      # the Granite cell's mixer
    ("blocks_over_vmem", dict(p=512, n=1024, H=8, G=1), 128),
    (None, dict(dtype=jnp.float32, H=6, G=3, T=100, b=3, p=128), 128),
    ("head_dim_not_64_aligned", dict(p=16, n=32, H=8, G=2), 128),
    ("head_dim_not_64_aligned", dict(H=3, G=1), 128),     # 192 lanes a group
    ("state_not_128_aligned", dict(n=64), 128),
    ("chunk!=128", {}, 64),
    ("dtype:float16", dict(dtype=jnp.float16), 128),
    ("dtype:mixed", dict(bc_dtype=jnp.float32), 128),
])
def test_rule_reads_its_operands_as_on_tpu(ssd_choices, monkeypatch, why, kw,
                                           chunk):
    """With the platform patched to ``tpu``: the kernels where the head size
    is a multiple of 64 and a group's heads fill 128-lane tiles, the state
    size a multiple of 128, the chunk 128 and the type bf16 or f32 (any b, T,
    H, G), else the ``jax.numpy`` form with its reason; one sample a call."""
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    taken = []
    monkeypatch.setattr(kernels, "ssd", lambda *a: taken.append(a) or
                        chunk_ssd_jnp(*a))
    jax.eval_shape(lambda *a: chunk_ssd(*a, chunk=chunk), *rule_inputs(**kw))
    if why is None:
        assert len(taken) == 1 and ssd_choices() == {("pallas", ""): 1}
    else:
        assert not taken and ssd_choices() == {("jnp", why): 1}


# -- the second entry: xBC read in place, the skip inside ----------------------

def xbc_inputs(T, b, H, G, dtype, seed, exact):
    """``xBC [b, T, H P + 2 G N]`` as the convolution writes it, ``dt``, ``A``
    as ``ssd_inputs`` draws them, and ``D`` about one or, ``exact``, signed
    powers of two (``D x`` is then exact and a fused multiply-add, which the
    CPU's compiler makes of an interpret-mode body and not of the ``jax.numpy``
    lines, rounds as they do)."""
    x, dt, A, Bm, Cm = ssd_inputs(T, b, H, G, dtype, seed)
    r = np.random.default_rng(seed + 1)
    D = (2.0 ** r.integers(-2, 2, H) * r.choice([-1.0, 1.0], H) if exact
         else r.normal(1.0, 0.3, H))
    xbc = jnp.concatenate([t.reshape(b, T, -1) for t in (x, Bm, Cm)], -1)
    return xbc, dt, A, jnp.asarray(D, jnp.float32)


def from_slices(scan, xbc, dt, A, D, *, H, G):
    """The layer's lines around ``scan`` (``layers/mamba2.py _scan`` before
    the kernels had a second entry): three slices of ``xBC``, the scan, the
    skip in f32 rounded to the compute type."""
    b, T, _ = xbc.shape
    d = H * P
    x = xbc[..., :d].reshape(b, T, H, P)
    Bm, Cm = (xbc[..., lo:lo + G * N].reshape(b, T, G, N)
              for lo in (d, d + G * N))
    y = scan(x, dt, A, Bm, Cm)[0].astype(jnp.float32)
    y = y + D[:, None] * x.astype(jnp.float32)
    return y.astype(xbc.dtype).reshape(b, T, d)


def in_place(xbc, dt, A, D, *, H, G):
    return kernels.ssd_in_place(xbc, dt, A, D, heads=H, head_dim=P,
                                groups=G, state=N)


def value_and_grads(fn, args, **dims):
    def loss(*a):
        y = fn(*a, **dims)
        return jnp.sum(jnp.sin(y.astype(jnp.float32))), y
    (_, y), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                       has_aux=True)(*args)
    return (y,) + grads


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("T,b,H,G", [
    (256, 2, 16, 1),            # Granite's cut: one group, two blocks of heads
    (1024, 1, 16, 2),           # Nemotron-H's: a group a program, two programs
    (128, 1, 4, 1)])            #   a sequence; one chunk, four heads
def test_in_place_is_the_slices_the_kernels_and_the_skip(T, b, H, G, dtype):
    """``ssd_in_place`` on ``xBC`` against today's composition on the same
    kernels: the values bit for bit where ``D x`` is exact (the two round
    ``y`` and ``y + D x`` at the same places) and within a rounding of the
    compute type for any ``D``; the gradients of ``xBC`` (each window), ``dt``,
    ``A`` and ``D``: in f32 to rounding; in bf16 ``dx`` within a step of its
    largest (the skip's ``D dy`` is added in f32 before ``dx``'s one rounding,
    where the composition adds two rounded terms) and the rest, which the skip
    does not enter, as they were where ``y`` is (``dD`` is f32 sums of the
    same products in another order)."""
    dims = dict(H=H, G=G)
    f32 = dtype == "float32"
    # not jitted whole: the kernels' calls then compile as they do under
    # ``in_place``, each a program of its own
    want_fn = lambda *a: value_and_grads(
        lambda *x, **kw: from_slices(kernels.ssd, *x, **kw), a, **dims)
    for exact in (True, False):
        args = xbc_inputs(T, b, H, G, jnp.dtype(dtype), T + exact, exact)
        got = value_and_grads(in_place, args, **dims)
        want = want_fn(*args)
        assert got[0].shape == (b, T, H * P) and got[0].dtype == args[0].dtype
        if exact:
            np.testing.assert_array_equal(got[0], want[0])
        d, gn = H * P, G * N
        for name, a, w in zip(("y", "dxbc", "ddt", "dA", "dD"), got, want):
            assert a.shape == w.shape and a.dtype == w.dtype, name
            a, w = (np.asarray(t, np.float64) for t in (a, w))
            windows = ((slice(0, d), slice(d, d + gn), slice(d + gn, None))
                       if name == "dxbc" else (slice(None),))
            for at in windows:
                top = np.abs(w[..., at]).max()
                assert top > 0, (name, at)
                gap = np.abs(a[..., at] - w[..., at]).max() / top
                # the skip enters y and dx alone; in bf16 a y that rounds
                # the other way (no exact ``D x``) is a step in what it feeds
                rounded = name == "y" or (name == "dxbc" and at.start == 0
                                          ) or not exact
                assert gap <= (5e-6 if f32 else 8e-3 if rounded else 1e-5), (
                    name, at, gap)


@pytest.fixture
def ssd_forms(live_registry):
    """``{form: count}`` of the entries traced since the test began."""
    before = kernels.forms()
    return lambda: {k: n - before.get(k, 0)
                    for k, n in kernels.forms().items()
                    if n > before.get(k, 0)}


@pytest.mark.parametrize("why,form,kw", [
    (None, "in_place", dict(H=64, G=1, T=8192)),     # the Granite cell's mixer
    (None, "in_place", dict(H=64, G=8, T=8192)),     # the Nemotron-H cell's
    (None, "in_place", dict(H=4, G=1, T=384)),       # three chunks, one program
    (None, "in_place", dict(H=6, G=3, T=128, p=128, dtype=jnp.float32)),
    ("positions_not_whole_blocks", "plain", dict(H=8, G=1, T=1100)),
    ("positions_not_whole_blocks", "plain", dict(H=8, G=1, T=1536)),
    ("bc_window_not_block_aligned", "plain", dict(H=2, G=1, T=256, n=256)),
    ("bc_window_not_block_aligned", "plain", dict(H=6, G=3, T=256, n=256)),
    ("head_dim_not_64_aligned", None, dict(H=8, G=2, T=256, p=16, n=32)),
    ("dtype:float16", None, dict(H=8, G=1, T=256, dtype=jnp.float16)),
])
def test_scan_reads_xbc_in_place_where_the_windows_are_whole_blocks(
        as_on_tpu, ssd_choices, ssd_forms, why, form, kw):
    """``layers/mamba2.py _scan`` with the platform read as ``tpu``: the
    in-place entry where the kernels take the heads, ``B``'s window starts at
    a whole block of ``N`` lanes and no position is padded; where its own rule
    refuses, the slices, the first entry and the skip, as before.  ONE choice
    a call either way, ``pallas``, and the entry's form counted beside it; where
    the kernels do not take the heads, the ``jax.numpy`` form with
    ``unsupported``'s reason and no entry at all."""
    from hetu_tpu.layers.mamba2 import _scan
    H, G, T = kw["H"], kw["G"], kw["T"]
    p, n, dtype = kw.get("p", P), kw.get("n", N), kw.get("dtype", jnp.bfloat16)
    if form is not None:
        assert kernels.in_place_unsupported(T, H * p, n) == why
    sds = jax.ShapeDtypeStruct
    y = jax.eval_shape(
        lambda *a: _scan(*a, heads=H, head_dim=p, groups=G, state=n,
                         chunk=128),
        sds((1, T, H * p + 2 * G * n), dtype), sds((1, T, H), dtype),
        *(sds((H,), jnp.float32),) * 3)
    assert y.shape == (1, T, H * p) and y.dtype == dtype
    if form is None:
        assert ssd_forms() == {} and ssd_choices() == {("jnp", why): 1}
    else:
        assert ssd_forms() == {form: 1}
        assert ssd_choices() == {("pallas", ""): 1}


def test_scan_through_the_in_place_kernels_is_the_scan(monkeypatch, ssd_forms):
    """The scan node's function as it runs on a TPU (interpret mode: Mosaic
    read as there, the platform the CPU's) against itself around the
    ``jax.numpy`` form, f32, 8 heads in 2 groups over 256 positions: the
    output and the gradient of ``xBC``, ``dt``, ``dt_bias``, ``A_log`` and
    ``D``."""
    from hetu_tpu.layers.mamba2 import _scan
    dims = dict(heads=8, head_dim=P, groups=2, state=N, chunk=128)
    r = np.random.default_rng(4)
    x = (jnp.asarray(r.normal(size=(1, 256, 8 * P + 4 * N)) * 0.3,
                     jnp.float32),
         jnp.asarray(r.normal(size=(1, 256, 8)), jnp.float32),
         jnp.asarray(r.normal(size=(8,)), jnp.float32),
         jnp.asarray(r.normal(size=(8,)), jnp.float32),
         jnp.asarray(r.normal(1.0, 0.3, size=(8,)), jnp.float32))

    def both(rule):
        def loss(*a):
            y = _scan(*a, rule=rule, **dims)
            return jnp.sum(jnp.sin(y)), y
        (_, y), grads = jax.value_and_grad(loss, argnums=range(5),
                                           has_aux=True)(*x)
        return (y,) + grads
    want = both(chunk_ssd_jnp)
    assert ssd_forms() == {}
    monkeypatch.setattr(dispatch, "mosaic", lambda: True)
    got = both(None)
    assert ssd_forms() == {"in_place": 1}
    for a, b in zip(got, want):
        assert a.shape == b.shape and np.abs(b).max() > 0
        assert np.abs(a - b).max() < 1e-4 * np.abs(b).max()


# -- the layer through the kernels ---------------------------------------------

def layer_loss_and_grads(through_kernels, monkeypatch):
    """Loss and every weight's gradient of one ``Mamba2`` at the published
    head and state sizes (8 heads of 64 in 2 groups, 200 positions), through
    the executor."""
    import hetu_tpu as ht
    from hetu_tpu.graph.node import graph_variables
    from hetu_tpu.layers.mamba2 import Mamba2
    if through_kernels:
        monkeypatch.setattr(ssd, "chunk_ssd",
                            lambda *a, chunk: kernels.ssd(*a))
    name = f"ssk_layer_{int(through_kernels)}"
    layer = Mamba2(128, 8, P, 2, N, name=name)
    x = ht.placeholder_op(f"{name}_x", (2, 200, 128))
    loss = ht.reduce_sum_op(ht.sin_op(layer(x)), axes=[0, 1, 2])
    variables = graph_variables([loss], trainable_only=True)
    assert len(variables) == 8
    ex = ht.Executor({"grads": [loss] + ht.gradients(loss, variables)},
                     seed=3)
    r = np.random.default_rng(5)
    for var in variables:           # the same weights for both, off their
        value = ex.params[var.name]     # initial ones and zeros
        ex.params[var.name] = jnp.asarray(
            r.normal(0.2 if var.shape == (8,) else 0.0, 0.1, var.shape),
            value.dtype)
    feed = {x: r.normal(size=(2, 200, 128)).astype(np.float32)}
    out = ex.run("grads", feed_dict=feed, convert_to_numpy_ret_vals=True)
    return out[0], out[1:]


def test_layer_through_the_kernels_is_the_layer(monkeypatch):
    """Loss and the gradient of all eight weights, f32."""
    l1, g1 = layer_loss_and_grads(False, monkeypatch)
    l2, g2 = layer_loss_and_grads(True, monkeypatch)
    assert abs(float(l2 - l1)) < 1e-5 * abs(float(l1))
    for a, b in zip(g2, g1):
        assert a.shape == b.shape and np.abs(b).max() > 0
        assert np.abs(a - b).max() < 1e-4 * np.abs(b).max()


# -- the layer's step compiled for the chip -------------------------------------
# Interpret mode cannot see what Mosaic refuses.  libtpu is installed, so the
# layer's train step compiles here for a described, not attached, v5e at the
# cell's shapes; nothing runs.  (One process at a time may load libtpu, and
# the driver's workers are given whole files: the fixture stays in this one.)

@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:           # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def as_on_tpu(monkeypatch):
    """The platform read as ``tpu``, and no stage traced for interpret mode
    left in jax's caches (``kernels._dot`` reads the mode while tracing)."""
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("groups", [8, 1])
def test_layer_train_step_compiles_for_v5e(v5e, as_on_tpu, ssd_choices,
                                           ssd_forms, groups):
    """The Nemotron-H cell's mixer (64 heads of 64 in 8 groups, state 128,
    8,192 positions, bf16 over f32 masters) recomputed in the backward pass
    as the cell's are, with AdamW: ``hetu_ssd_fwd`` twice (forward and
    recomputed forward), ``hetu_ssd_bwd`` once and nothing else of the
    scan's: no ``while``, no ``[.., 128, 128]`` array in HBM; the kernels read
    ``x``, ``B`` and ``C`` out of the convolution's ``[1, 8192, 6144]`` (4,352
    lanes at one group) where it lies, add the skip and write ``y [1, 8192,
    4096]``.  And the Granite cell's, all 64
    heads in ONE group: held whole by a program that is 68 MiB of VMEM
    against the limit of 64 (``RESOURCE_EXHAUSTED`` before the group was cut
    into blocks of eight heads), the same three calls."""
    import re
    from jax.sharding import SingleDeviceSharding
    import hetu_tpu as ht
    from hetu_tpu.layers.mamba2 import Mamba2
    layer = Mamba2(256, 64, P, groups, N, name=f"ssk_v5e_g{groups}")
    x = ht.placeholder_op(f"ssk_v5e_g{groups}_x", (1, 8192, 256))
    with ht.remat():
        y = layer(x)
    loss = ht.reduce_sum_op(y * y, axes=[0, 1, 2])
    ex = ht.Executor({"train": [loss, ht.AdamWOptimizer(1e-3).minimize(loss)]},
                     seed=0, compute_dtype=jnp.bfloat16)
    sub = ex.subexecutor["train"]
    sub._build()
    one = SingleDeviceSharding(v5e.devices[0])
    args = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        sub._abstract_args(None))
    hlo = sub._jitted.lower(*args).compile().as_text()
    assert ssd_choices() == {("pallas", ""): 1}
    assert ssd_forms() == {"in_place": 1}
    # a call by its own name, not its operands' (the scan reads the
    # convolution's output where it is: ``custom-call(%hetu_conv_fwd.2, ..``)
    calls = [re.sub(r"custom-call\([^)]*\)", "custom-call()", ln)
             for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert sum("hetu_ssd_fwd" in ln for ln in calls) == 2
    assert sum("hetu_ssd_bwd" in ln for ln in calls) == 1
    # (the layer's other kernels are the convolution's, hetu_conv_*)
    assert all("bf16[1,8192,4096]" in ln for ln in calls if "hetu_ssd" in ln)
    assert sum("hetu_conv_fwd" in ln for ln in calls) == 2
    assert sum("hetu_conv_bwd" in ln for ln in calls) == 1
    # all three read x | B | C where the convolution's kernel wrote them
    scans = [ln for ln in hlo.splitlines() if re.match(r"\s*%hetu_ssd", ln)]
    assert [ln.count("%hetu_conv_fwd") for ln in scans] == [3, 3, 3]
    assert not re.findall(r"\bwhile\(", hlo)
    assert not re.findall(r" = \w+\[[\d,]*128,128\]\S* ", hlo)
