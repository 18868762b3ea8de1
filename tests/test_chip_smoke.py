"""Tier-1 (CPU) checks of the chip bring-up surface: ``chip_smoke.py``
refuses a platform that is not a TPU, a forced serving failure fails it,
the peak table knows what a v5e reports and raises on what it does not
know, the compile cache is placed from outside, and every kernel-vs-jnp
choice is counted.
"""

import ast
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def test_smoke_fails_and_names_the_platform_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=120)
    assert r.returncode != 0
    first = r.stdout.splitlines()[0]
    assert first.startswith("platform cpu, device_kind cpu")
    assert "jax " in first and "libtpu " in first
    assert "needs platform 'tpu' and jax found 'cpu'" in r.stdout
    # no result line: the last line is not the JSON object of a pass
    assert not r.stdout.rstrip().splitlines()[-1].startswith("{")


def test_smoke_has_no_try_around_a_leg():
    """Every leg's failure is the script's failure: the script holds no
    ``try`` at all, so nothing can swallow one."""
    with open(os.path.join(REPO, "chip_smoke.py")) as f:
        tree = ast.parse(f.read())
    assert not [n.lineno for n in ast.walk(tree) if isinstance(n, ast.Try)]


def test_forced_decode_failure_fails_the_server_leg(monkeypatch):
    """With the watchdog at its default the engine survives a raising
    decode program and finishes the requests with "error"; the smoke must
    not take that for success."""
    from hetu_tpu.serving import InferenceEngine

    smoke = chip_smoke.Smoke(rehearsal=True)
    served = chip_smoke.build_served_model(smoke)
    build = InferenceEngine._build_paged

    def build_then_break(self):
        build(self)

        def step_raises(*args, **kw):
            raise RuntimeError("injected: decode program failed")
        self._step_fn = step_raises

    monkeypatch.setattr(InferenceEngine, "_build_paged", build_then_break)
    with pytest.warns(UserWarning, match="decode watchdog"):
        with pytest.raises(chip_smoke.SmokeFailure, match="finish_reason"):
            chip_smoke.server_leg(smoke, served, "forced failure")
    served[0].close()


def test_memory_checks_fail_on_a_device_that_was_not_used():
    """A machine with four chips must not pass by using one."""
    class Dev:
        def __init__(self, i):
            self.id = i
    smoke = chip_smoke.Smoke(rehearsal=True)
    devs = [Dev(0), Dev(1)]
    gib = 2 ** 30
    used = [(1 * gib, 2 * gib), (0, 0)], [(3 * gib, 4 * gib),
                                          (2 * gib, 3 * gib)]
    chip_smoke.check_memory_rose(smoke, devs, *used, "leg")
    chip_smoke.check_peaks_rose(smoke, devs, *used, "legs")
    idle = [(1 * gib, 2 * gib), (0, 0)], [(3 * gib, 4 * gib), (0, 0)]
    with pytest.raises(chip_smoke.SmokeFailure, match="device 1"):
        chip_smoke.check_memory_rose(smoke, devs, *idle, "leg")
    with pytest.raises(chip_smoke.SmokeFailure, match=r"device\(s\) \[1\]"):
        chip_smoke.check_peaks_rose(smoke, devs, *idle, "legs")


def test_chip_peaks_knows_a_v5e_and_raises_on_an_unknown_chip():
    from hetu_tpu.telemetry import perf_model
    v5e = perf_model.chip_peaks("TPU v5 lite")
    assert v5e["peak_flops"] == 197e12
    assert v5e["peak_hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["peak_source"]
    with pytest.raises(ValueError, match="TPU v9"):
        perf_model.chip_peaks("TPU v9")


def test_compile_cache_is_placed_from_outside(monkeypatch):
    import jax
    from hetu_tpu import platform
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert platform.enable_compile_cache() == "/somewhere/else"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        assert platform.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_kernel_choice_counter_records_a_jnp_fallback(live_registry):
    import jax.numpy as jnp
    from hetu_tpu.ops import losses
    from hetu_tpu.ops.pallas import dispatch

    key = ("softmax_ce", "jnp", "vocab<1024")
    n0 = dispatch.choices().get(key, 0)
    # 16 classes: below the kernel's 1024-class floor
    out = losses._softmax_cross_entropy_sparse(
        jnp.zeros((8, 16)), jnp.zeros((8,), jnp.int32))
    assert dispatch.choices()[key] == n0 + 1
    np.testing.assert_allclose(np.asarray(out), np.log(16.0), rtol=1e-6)
    took = ("softmax_ce", "pallas", "")
    k0 = dispatch.choices().get(took, 0)
    losses._softmax_cross_entropy_sparse(
        jnp.zeros((8, 2048)), jnp.zeros((8,), jnp.int32))
    assert dispatch.choices()[took] == k0 + 1


def test_mesh_executor_compiles_its_step_once(live_registry):
    """State initialised on one device is committed to the mesh before the
    first step; left there, step two traced and compiled all over again."""
    import hetu_tpu as ht
    from hetu_tpu.parallel import MegatronLM

    x = ht.placeholder_op("once_x", (8, 16))
    y = ht.placeholder_op("once_y", (8,), dtype=np.int32)
    w1 = ht.Variable("once_q_weight", shape=(16, 8),
                     initializer=ht.init.normal(0.0, 0.1))
    w2 = ht.Variable("once_head", shape=(8, 4),
                     initializer=ht.init.normal(0.0, 0.1))
    loss = ht.reduce_mean_op(ht.softmax_cross_entropy_sparse_op(
        ht.matmul_op(ht.matmul_op(x, w1), w2), y))
    opt = ht.AdamOptimizer(1e-3)
    ex = ht.Executor({"once": [loss, opt.minimize(loss)]},
                     dist_strategy=MegatronLM(dp=2, tp=2))
    feed = {x: np.ones((8, 16), np.float32), y: np.zeros((8,), np.int32)}
    n0 = chip_smoke.counter("hetu_executor_retraces_total", subgraph="once")
    for _ in range(3):
        ex.run("once", feed_dict=feed)
    assert chip_smoke.counter("hetu_executor_retraces_total",
                              subgraph="once") - n0 == 1
    ex.close()
