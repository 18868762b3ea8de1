"""Performance introspection (ISSUE 10): the version-compat XLA
cost/memory helpers, per-layer attribution, MFU/roofline arithmetic,
the process-wide HBM live-buffer ledger (balance across engine/server/
executor lifecycles), and the telemetry report/endpoint/incident
surfaces the profile block rides on."""

import json
import os
import time
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu import telemetry
from hetu_tpu.platform import (compiled_cost_analysis,
                               compiled_memory_analysis)
from hetu_tpu.telemetry import perf_model
from hetu_tpu.telemetry.profiling import (HBM_POOLS, HbmLedger,
                                          ProgramProfiler,
                                          attribute_graph, layer_of)


# ---------------- platform helpers ----------------

class _FakeCompiled:
    """The two analyses as the installed jax hands them out: a cost dict
    and a CompiledMemoryStats-style attribute object."""
    def cost_analysis(self):
        return {"flops": 10.0, "bytes accessed": 4.0}

    def memory_analysis(self):
        class MA:
            generated_code_size_in_bytes = 100
            argument_size_in_bytes = 200
            output_size_in_bytes = 300
            alias_size_in_bytes = 0
            temp_size_in_bytes = 50
            host_temp_size_in_bytes = 0
        return MA()


class _FakeCompiledNoModel:
    def cost_analysis(self):
        return None

    def memory_analysis(self):
        return None


def test_cost_and_memory_analysis_are_plain_dicts():
    assert compiled_cost_analysis(_FakeCompiled()) == {
        "flops": 10.0, "bytes accessed": 4.0}
    assert compiled_memory_analysis(_FakeCompiled()) == {
        "generated_code_size_in_bytes": 100,
        "argument_size_in_bytes": 200,
        "output_size_in_bytes": 300,
        "alias_size_in_bytes": 0,
        "temp_size_in_bytes": 50}
    assert compiled_cost_analysis(_FakeCompiledNoModel()) == {}
    assert compiled_memory_analysis(_FakeCompiledNoModel()) == {}


def test_real_compiled_cost_and_memory():
    """The helpers against this jax version's actual compiled object."""
    compiled = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((8, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 4), jnp.float32)).compile()
    cost = compiled_cost_analysis(compiled)
    assert cost.get("flops", 0) >= 2 * 8 * 16 * 4
    mem = compiled_memory_analysis(compiled)
    assert mem.get("argument_size_in_bytes", 0) > 0


# ---------------- perf model arithmetic ----------------

def test_chip_peaks_table_order_and_env_override(monkeypatch):
    assert perf_model.chip_peaks("TPU v5p")["peak_flops"] == 459e12
    cpu = perf_model.chip_peaks("cpu")
    assert cpu["peak_source"] == "nominal_cpu"
    monkeypatch.setenv("HETU_PEAK_FLOPS", "1e15")
    monkeypatch.setenv("HETU_PEAK_HBM_BW", "2e12")
    pk = perf_model.chip_peaks("TPU v5p")
    assert pk["peak_flops"] == 1e15
    assert pk["peak_hbm_bytes_per_s"] == 2e12
    assert pk["peak_source"] == "env"


def test_mfu_is_exactly_flops_times_rate_over_peak():
    assert perf_model.mfu(2e9, 50.0, 1e12) == 2e9 * 50.0 / 1e12
    assert perf_model.mfu(0, 50.0, 1e12) == 0.0
    assert perf_model.mfu(2e9, 50.0, 0) == 0.0
    assert perf_model.mfu(None, None, None) == 0.0


def test_roofline_bound_switches_at_ridge():
    peaks = {"peak_flops": 100.0, "peak_hbm_bytes_per_s": 10.0}  # ridge 10
    assert perf_model.roofline(200.0, 10.0, peaks)["bound"] == "compute"
    assert perf_model.roofline(50.0, 10.0, peaks)["bound"] == "memory"
    none = perf_model.roofline(0, 0, peaks)
    assert none["bound"] is None and none["ridge_intensity"] == 10.0


def test_derive_flops_steps_consistency():
    """mfu == flops_per_step x steps_per_sec / peak, exactly (modulo the
    documented rounding); achieved rates scale linearly with steps."""
    peaks = {"device_kind": "x", "peak_flops": 1e12,
             "peak_hbm_bytes_per_s": 1e11, "peak_source": "table"}
    cost = {"flops": 5e8, "bytes accessed": 2e7}
    d = perf_model.derive(cost, steps=20, elapsed_s=2.0, peaks=peaks,
                          tokens=400, n_chips=2)
    sps = 20 / 2.0
    assert d["steps_per_sec"] == pytest.approx(sps)
    assert d["mfu"] == round(5e8 * sps / 1e12, 6)
    assert d["achieved_flops_per_sec"] == pytest.approx(5e8 * sps)
    assert d["tokens_per_sec_per_chip"] == pytest.approx(400 / 2.0 / 2)
    static = perf_model.derive(cost, peaks=peaks)
    assert "mfu" not in static and static["flops_per_step"] == 5e8


# ---------------- HBM ledger ----------------

def test_ledger_pool_totals_equal_sum_of_live_buffers():
    led = HbmLedger()
    h1 = led.alloc("params", 1000, owner="a")
    h2 = led.alloc("params", 500, owner="b")
    h3 = led.alloc("kv_cache", 2048, owner="c")
    snap = led.snapshot()
    assert snap["pools"]["params"] == 1500
    assert snap["pools"]["kv_cache"] == 2048
    assert snap["total_bytes"] == sum(
        b["nbytes"] for b in snap["buffers"])
    assert snap["total_bytes"] == sum(snap["pools"].values())
    assert set(snap["pools"]) == set(HBM_POOLS)
    h2.free()
    assert led.live_bytes("params") == 1000
    h1.free(), h3.free()
    assert led.live_bytes() == 0
    assert led.snapshot()["allocs"] == led.snapshot()["frees"] == 3


def test_ledger_free_is_idempotent_and_pools_are_closed_set():
    led = HbmLedger()
    h = led.alloc("workspace", 64)
    h.free()
    h.free()                     # second free must not double-count
    assert led.snapshot()["frees"] == 1
    with pytest.raises(ValueError):
        led.alloc("not_a_pool", 1)


def test_ledger_replace_swaps_in_place():
    led = HbmLedger()
    h = led.alloc("workspace", 100, owner="prog")
    h2 = led.replace(h, "workspace", 250, owner="prog")
    assert led.live_bytes("workspace") == 250
    assert led.snapshot()["live"] == 1
    h2.free()
    assert led.live_bytes() == 0


def test_ledger_mirrors_into_registry_gauge():
    reg = telemetry.MetricsRegistry(enabled=True)
    led = HbmLedger(registry=reg)
    h = led.alloc("kv_cache", 4096)
    snap = reg.snapshot()["hetu_hbm_bytes"]
    vals = {tuple(s["labels"].items()): s["value"]
            for s in snap["samples"]}
    assert vals[(("pool", "kv_cache"),)] == 4096
    h.free()
    vals = {tuple(s["labels"].items()): s["value"]
            for s in reg.snapshot()["hetu_hbm_bytes"]["samples"]}
    assert vals[(("pool", "kv_cache"),)] == 0


def test_ledger_alloc_free_disabled_cost_is_negligible():
    """The ledger always tracks (telemetry off included): one
    alloc+free pair must stay far below even a trivial jitted step —
    same contract as the PR 4 no-op instruments."""
    led = HbmLedger(registry=telemetry.get_registry())
    telemetry.disable()
    reps = 5000
    t0 = time.perf_counter()
    for _ in range(reps):
        led.alloc("workspace", 128, owner="bench").free()
    per_op = (time.perf_counter() - t0) / reps
    assert per_op < 20e-6, f"ledger alloc+free pair cost {per_op:.2e}s"


# ---------------- attribution ----------------

def _wdl_graph(tag):
    import hetu_tpu as ht
    from hetu_tpu.models import WDL
    B, rows = 8, 64
    with ht.name_scope():
        dense = ht.placeholder_op(f"{tag}_dense", (B, 13))
        sparse = ht.placeholder_op(f"{tag}_sparse", (B, 26),
                                   dtype=np.int32)
        labels = ht.placeholder_op(f"{tag}_labels", (B,))
        model = WDL(rows, embedding_dim=8, name=f"{tag}_wdl")
        loss = model.loss(dense, sparse, labels)
    ex = ht.Executor(
        {"train": [loss, ht.AdamOptimizer(0.01).minimize(loss)]})
    rng = np.random.default_rng(0)
    feed = {dense: rng.standard_normal((B, 13)).astype(np.float32),
            sparse: rng.integers(0, rows, (B, 26)).astype(np.int32),
            labels: rng.integers(0, 2, (B,)).astype(np.float32)}
    return ex, feed


def test_layer_of_strips_param_suffixes():
    assert layer_of("wdl_deep0_weight") == "wdl_deep0"
    assert layer_of("wdl_deep0_bias") == "wdl_deep0"
    assert layer_of("serve_blk3_attn_wq_kernel") == "serve_blk3_attn_wq"
    assert layer_of("wdl_emb") == "wdl_emb"


def test_attribution_covers_layers_and_scales_to_xla_totals():
    ex, feed = _wdl_graph("attr")
    try:
        sub = ex.subexecutor["train"]
        cost = sub.cost_analysis()
        rows = attribute_graph(
            sub.eval_nodes, {n.name: v.shape for n, v in feed.items()},
            totals=cost)
        assert rows, "attribution produced no layers"
        layers = {r["layer"] for r in rows}
        # every W&D parameterized layer shows up under its scope name
        assert {"attr_wdl_deep0", "attr_wdl_emb",
                "attr_wdl_wide"} <= layers
        assert sum(r["flops_frac"] for r in rows) == pytest.approx(
            1.0, abs=1e-3)
        # scaled to the XLA total: attributed flops sum to the program's
        assert sum(r["flops"] for r in rows) == pytest.approx(
            cost["flops"], rel=1e-3)
        # the deep tower dominates a W&D step, not the tiny wide path
        assert rows[0]["layer"].startswith("attr_wdl_deep")
    finally:
        ex.close()


def test_attribution_without_totals_uses_estimates():
    ex, feed = _wdl_graph("est")
    try:
        rows = attribute_graph(ex.subexecutor["train"].eval_nodes,
                               {n.name: v.shape for n, v in feed.items()})
        assert rows and all(r["flops"] > 0 for r in rows[:1])
        assert sum(r["flops_frac"] for r in rows) == pytest.approx(
            1.0, abs=1e-3)
    finally:
        ex.close()


# ---------------- executor analysis + ledger lifecycle ----------------

def test_executor_memory_analysis_and_ledger_lifecycle():
    from hetu_tpu.graph.executor import _tree_nbytes
    led = telemetry.get_hbm_ledger()
    p0 = led.live_bytes("params")
    ex, feed = _wdl_graph("mem")
    try:
        assert led.live_bytes("params") - p0 == _tree_nbytes(ex.params)
        ma = ex.subexecutor["train"].memory_analysis()
        assert ma.get("argument_size_in_bytes", 0) > 0
        assert "temp_size_in_bytes" in ma
    finally:
        ex.close()
        ex.close()               # idempotent
    assert led.live_bytes("params") == p0


def test_profiler_capture_observe_and_metrics():
    reg = telemetry.MetricsRegistry(enabled=True)
    led = HbmLedger(registry=reg)
    prof = ProgramProfiler(registry=reg, ledger=led)
    prof._peaks = {"device_kind": "t", "peak_flops": 1e12,
                   "peak_hbm_bytes_per_s": 1e11, "peak_source": "table"}
    p = prof.capture("prog", cost={"flops": 4e9, "bytes accessed": 1e8},
                     memory={"temp_size_in_bytes": 777})
    assert p["derived"]["flops_per_step"] == 4e9
    # the workspace ledger entry tracks the program's temp bytes
    assert led.live_bytes("workspace") == 777
    p = prof.observe("prog", steps=10, elapsed_s=1.0, tokens=100)
    assert p["derived"]["mfu"] == round(4e9 * 10 / 1e12, 6)
    snap = reg.snapshot()
    mfu = snap["hetu_profile_mfu"]["samples"][0]["value"]
    assert mfu == p["derived"]["mfu"]
    assert snap["hetu_profile_flops_per_step"]["samples"][0][
        "value"] == 4e9
    assert snap["hetu_profile_captures_total"]["samples"][0]["value"] == 1
    with pytest.raises(KeyError):
        prof.observe("never_captured", steps=1, elapsed_s=1.0)
    # re-capture replaces the workspace entry, clear() releases it
    prof.capture("prog", cost={"flops": 1.0},
                 memory={"temp_size_in_bytes": 111})
    assert led.live_bytes("workspace") == 111
    prof.clear()
    assert led.live_bytes("workspace") == 0


def _tiny_llama(tag):
    import hetu_tpu as ht
    from hetu_tpu.models import LlamaConfig, LlamaForCausalLM
    c = LlamaConfig(vocab_size=64, hidden_size=16, num_layers=2,
                    num_heads=2, num_kv_heads=2, intermediate_size=32,
                    seq_len=16)
    model = LlamaForCausalLM(c, name=tag)
    ids = ht.placeholder_op(f"{tag}_ids", (1, 4), dtype=np.int32)
    return ht.Executor([model(ids)]), model


def test_engine_ledger_balances_after_close():
    from hetu_tpu.serving import InferenceEngine
    led = telemetry.get_hbm_ledger()
    kv0 = led.live_bytes("kv_cache")
    ex, model = _tiny_llama("ledeng")
    eng = InferenceEngine(ex, model, n_slots=2, max_len=16,
                          max_prompt_len=6)
    expect = int(eng.cache.k.nbytes) + int(eng.cache.v.nbytes)
    assert led.live_bytes("kv_cache") - kv0 == expect
    cp = eng.cost_programs()
    assert compiled_cost_analysis(cp["prefill"]).get("flops", 0) > 0
    assert compiled_cost_analysis(cp["decode"]).get("flops", 0) > 0
    eng.close()
    eng.close()                   # idempotent
    ex.close()
    assert led.live_bytes("kv_cache") == kv0


def test_embedding_server_ledger_balances_after_close():
    import hetu_tpu as ht
    from hetu_tpu.models.ctr import WDL
    from hetu_tpu.serving import EmbeddingServer
    led = telemetry.get_hbm_ledger()
    hot0 = led.live_bytes("hot_cache")
    rows, dim, F, nd = 512, 16, 4, 3
    model = WDL(rows, embedding_dim=dim, num_sparse=F, num_dense=nd,
                hidden=(16,), name="ledsrv")
    dense_ph = ht.placeholder_op("ledsrv_dense", (1, nd))
    ids_ph = ht.placeholder_op("ledsrv_ids", (1, F), dtype=np.int32)
    ex = ht.Executor([model(dense_ph, ids_ph)])
    with EmbeddingServer(ex, model, cache_rows=64, n_slots=4,
                         name="ledsrv") as srv:
        assert led.live_bytes("hot_cache") - hot0 == int(
            srv.hot.rows_dev.nbytes)
        ids = np.arange(F, dtype=np.int64)[None, :].repeat(2, 0)
        srv.score_many(ids)
        cp = srv.cost_programs()
        assert compiled_cost_analysis(cp["score"]).get("flops", 0) > 0
    ex.close()
    assert led.live_bytes("hot_cache") == hot0


# ---------------- telemetry surfaces ----------------

def test_report_carries_profile_block():
    rep = telemetry.report()
    assert "profile" in rep
    blk = rep["profile"]
    assert set(blk) >= {"programs", "layer_table", "hbm"}
    assert set(blk["hbm"]["pools"]) == set(HBM_POOLS)


def test_profile_debug_endpoint_mounted_by_enable():
    prof = telemetry.get_profiler()
    prof.capture("endpoint_prog", cost={"flops": 123.0})
    try:
        srv = telemetry.enable(http_port=0)
        body = urllib.request.urlopen(f"{srv.url}/profile",
                                      timeout=5).read().decode()
        doc = json.loads(body)
        assert "endpoint_prog" in doc["programs"]
        assert doc["hbm"]["pools"].keys() == set(HBM_POOLS)
    finally:
        telemetry.shutdown()
        prof.clear()


def test_flight_incident_dump_carries_hbm_snapshot(tmp_path):
    led = telemetry.get_hbm_ledger()
    fl = telemetry.get_flight()
    h = led.alloc("kv_cache", 12345, owner="incident_test")
    try:
        telemetry.enable()
        fl.configure(incident_dir=str(tmp_path))
        entry = fl.incident("engine_crash", extra={"why": "test"})
        dump = fl.load_dump(entry["path"])
        assert dump["hbm"] is not None
        assert dump["hbm"]["pools"]["kv_cache"] >= 12345
        owners = {b["owner"] for b in dump["hbm"]["buffers"]}
        assert "incident_test" in owners
    finally:
        telemetry.disable()
        h.free()


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
