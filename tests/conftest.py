"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's testing approach (SURVEY.md §4): multi-node is
simulated locally — the reference used `mpirun -np N` on one host; we use
XLA's host-platform device partitioning, which exercises the same SPMD
programs/collectives that run over ICI on real TPU pods.
"""

import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
# copy-on-write write-guard: every page write is asserted against the
# refcount table (kv_cache.assert_writable) — debug mode, always on
# under the test suite
os.environ.setdefault("HETU_COW_GUARD", "1")

import signal  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Enforce ``@pytest.mark.timeout(seconds)`` with SIGALRM.

    pytest-timeout is not installed in this environment, so without this
    the mark was a silent no-op (VERDICT r4 item 7) — and the PS
    transport kill/restart tests it guards are exactly the ones that can
    hang on a wedged socket, wedging the whole gate with them.  SIGALRM
    interrupts the blocking call in the main thread and surfaces as a
    plain test failure."""
    marker = item.get_closest_marker("timeout")
    use_alarm = (marker is not None and hasattr(signal, "SIGALRM")
                 and threading.current_thread() is threading.main_thread())
    if not use_alarm:
        return (yield)
    seconds = int(marker.args[0] if marker.args
                  else marker.kwargs["seconds"])

    def _on_alarm(signum, frame):
        raise TimeoutError(
            f"test exceeded its {seconds}s @pytest.mark.timeout watchdog")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    signal.alarm(seconds)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(autouse=True)
def no_leaked_nondaemon_threads(request):
    """Runtime half of the thread-leak gate (the static half is
    tests/test_no_leaked_threads.py): after every serving/fleet test,
    no NEW non-daemon thread may still be alive — a leaked driver or
    exporter thread would wedge interpreter shutdown.  Scoped to the
    thread-spawning suites so the rest of tier-1 pays nothing."""
    mod = request.module.__name__.rsplit(".", 1)[-1]
    if not (mod.startswith("test_serving") or mod.startswith("test_fleet")
            or mod == "test_telemetry"):
        yield
        return
    before = set(threading.enumerate())
    yield
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive() and not t.daemon]
    if leaked:        # give wind-down joins a beat before failing
        import time
        time.sleep(0.2)
        leaked = [t for t in leaked if t.is_alive()]
    assert not leaked, (
        f"non-daemon thread(s) leaked by {request.node.nodeid}: "
        f"{[t.name for t in leaked]}")


@pytest.fixture(autouse=True)
def compile_cache_as_found():
    """A test that points jax's persistent compile cache leaves the process
    as it found it.  The benchmark's ``run.main`` points it at
    ``<checkout>/.jax_cache`` (``platform.enable_compile_cache``) for the
    rest of the process, and a later test of the same worker that compiles
    one step twice and compares the texts (``test_block_scopes.py``) would
    be handed its first build for the second: which worker runs which file
    is xdist's choice, so the failure came and went."""
    import jax
    before = jax.config.jax_compilation_cache_dir
    yield
    if jax.config.jax_compilation_cache_dir != before:
        from jax.experimental.compilation_cache import compilation_cache
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def clone_params_into(ex, prev):
    """Copy a prior executor's params into ``ex`` by sorted-name pairing
    (two same-structure models built sequentially differ only by name
    tags, and sorted order preserves correspondence).  Returns HOST
    copies of the placed params taken NOW — the train step donates the
    device buffers, so reading them later would hit deleted arrays."""
    import jax.numpy as jnp
    if prev is not None:
        ren = dict(zip(sorted(ex.params), sorted(prev)))
        for k in ex.params:
            ex.params[k] = jnp.asarray(prev[ren[k]])
    return {k: np.asarray(v) for k, v in ex.params.items()}


def dense_twin_gate(gate):
    """``gate`` (a ``layers/moe.py`` gate with a choices form) as a
    caller-built gate that exposes ``gating`` and ``aux`` alone: a capacity
    ``MoELayer`` handed it runs the dense one-hot einsums on the same
    routing, the oracle of the scatter-style dispatch."""
    from hetu_tpu.layers.base import BaseLayer

    class Dense(BaseLayer):
        def __init__(self):
            self.wg, self.gating, self.aux = gate.wg, gate.gating, gate.aux
    return Dense()


def lowered_for_tpu(monkeypatch, build, debug_info=False):
    """The text of the train step of the benchmark program ``build()`` makes,
    lowered for a TPU (nothing is compiled or run) with the platform read as
    ``tpu`` while it is built and traced; jax's caches emptied around it,
    since a kernel's jitted entry keeps what it read of the platform when it
    was traced.  ``debug_info``: every operation with its ``loc``, whose
    name holds the block scopes it was traced under."""
    import jax
    from hetu_tpu.ops.pallas import dispatch
    monkeypatch.setattr(dispatch, "platform", lambda: "tpu")
    jax.clear_caches()
    prog = build()
    try:
        sub = prog.ex.subexecutor["train"]
        if sub._jitted is None:
            sub._build()
        return sub._jitted.trace(*sub._abstract_args(None)).lower(
            lowering_platforms=("tpu",)).as_text(debug_info=debug_info)
    finally:
        prog.close()
        jax.clear_caches()


def rotary_kernels_asked(monkeypatch):
    """The ``rotary_pair`` node asks for its kernels as it does on a TPU, and
    gets them in interpret mode (``dispatch.take(asked=True)``)."""
    import types
    from hetu_tpu.ops import rotary
    from hetu_tpu.ops.pallas import dispatch
    monkeypatch.setattr(rotary, "dispatch", types.SimpleNamespace(
        take=lambda kernel, mesh, why:
        dispatch.take(kernel, mesh, why, asked=True)))


def ulps(got, want):
    """The largest gap in units of ``want``'s last place (bf16: 8 bits)."""
    import numpy as np
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    place = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
    return float((np.abs(got - want) / place).max())


def close(got, want, dtype, what):
    """A rotary kernel's result against its ``jax.numpy`` form's: f32 to
    rounding, bf16 within one place."""
    import jax.numpy as jnp
    assert got.shape == want.shape and got.dtype == want.dtype == dtype, what
    if dtype == jnp.float32:
        gap = float(jnp.abs(got - want).max())
        assert gap < 1e-6 * max(1.0, float(jnp.abs(want).max())), (what, gap)
    else:
        assert ulps(got, want) <= 1.0, (what, ulps(got, want))


@pytest.fixture(scope="module")
def one_chip():
    """The sharding of one chip of a described v5e: a test compiles for it
    (Mosaic and all) without one."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - whatever the describing raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def without_locations(text):
    """A program's text with nothing left that a moved source line moves.
    Of a lowered program (``as_text()``, with or without ``debug_info``): the
    ``loc(..)`` of every operation and the ``#loc`` table, and inside every
    Mosaic payload (a ``tpu_custom_call``'s ``body``: base64 of MLIR bytecode
    that carries each operation's Python call stack, file names and line
    numbers) the same, by putting the kernel's assembly printed without debug
    information in the payload's place.  Of a compiled module's text: every
    instruction's ``metadata={..}`` and the file and stack-frame tables it
    points into.  Two trees give equal texts exactly when they give the same
    program."""
    import base64
    import re
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True     # the serialised ``stable_mosaic``

    def kernel(match):
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(2)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return match.group(1) + asm.replace("\n", " ") + match.group(3)
    text = re.sub(r'(\\22body\\22: \\22)([A-Za-z0-9+/=]+)(\\22)', kernel, text)
    text = re.sub(r"^#loc\d*\b.*\n", "", text, flags=re.M)
    text = re.sub(r" loc\((?:[^()]|\((?:[^()]|\([^()]*\))*\))*\)", "", text)
    text = re.sub(r",? ?metadata=\{[^}]*\}", "", text)
    head, _, rest = text.partition("\nFileNames")
    if rest:
        rest = rest[rest.index("\n\n", rest.index("StackFrames")):]
    return head + rest


def conv_calls(text):
    """How often a lowered step calls the causal convolution's two kernel
    entries (``ops/pallas/causal_conv.py``): ``(forward, backward)``."""
    names = ("hetu_conv_fwd", "hetu_conv_bwd")
    assert all(f'kernel_name = "{name}"' in text for name in names)
    return tuple(kernel_calls(text, name) for name in names)


def kernel_calls(text, kernel):
    """How often a lowered program calls the jitted entries that hold the
    Pallas kernel ``kernel`` (a ``tpu_custom_call`` of that ``kernel_name``
    in a private function's body)."""
    import re
    bodies = re.split(r"\n\s*func\.func ", text)
    names = {re.match(r"(?:private )?@([\w.]+)", body).group(1)
             for body in bodies[1:] if f'kernel_name = "{kernel}"' in body}
    return sum(len(re.findall(rf"call @{re.escape(name)}\(", text))
               for name in names)


def gated_norm_calls(text):
    """How often a lowered step calls the gated norm's two kernel entries
    (``ops/pallas/gated_norm.py``): ``(forward, backward)``."""
    return tuple(kernel_calls(text, name) for name in
                 ("hetu_gated_norm_fwd", "hetu_gated_norm_bwd"))


def arrays_under(text, scope, dims):
    """``(operations, views)`` of a step lowered with ``debug_info``: how
    many operations were traced under the block ``scope``, and those of them
    that read or write an f32 array of rank 4 whose last dimensions are
    ``dims`` (a view by groups or by heads)."""
    import re
    names = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    view = re.compile(r"tensor<\d+x\d+x%dx%dxf32>" % dims)
    seen, views = 0, []
    for line in text.splitlines():
        at = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if at and scope in names.get(at.group(1), ""):
            seen += 1
            if view.search(line):
                views.append(line.strip()[:160])
    return seen, views


def jaxpr_primitives(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in its
    equations' parameters (jit, shard_map, custom_vjp, scan), except
    inside a ``pallas_call``: the kernel's own body is not the program's."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from jaxpr_primitives(sub)


@pytest.fixture
def live_registry():
    """The process registry counts only while enabled."""
    from hetu_tpu import telemetry
    reg = telemetry.get_registry()
    was = reg.enabled
    reg.enable()
    yield reg
    reg.enabled = was
