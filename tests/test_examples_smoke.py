"""Subprocess smoke of the newest example surfaces (the reference's
examples are its de-facto integration suite, SURVEY §4) — each runs the
real script end-to-end on the virtual CPU mesh with tiny steps."""

import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(args, timeout=420):
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"))
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_gpt_hybrid_example_smoke():
    """Searched full-LM Galvatron GPT (tied head) trains for a step."""
    r = _run(["examples/auto_parallel/gpt_hybrid.py", "--preset", "tiny",
              "--steps", "1"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "searched config" in r.stdout and "step 0 loss" in r.stdout


def test_galvatron_search_measured_mode_smoke(tmp_path):
    """--measure profiles real HP layers (time + XLA memory ledger) and
    psum bandwidth, then searches and emits the config JSON."""
    out = str(tmp_path / "cfg.json")
    r = _run(["examples/auto_parallel/galvatron_search.py", "--world", "8",
              "--layers", "2", "--hidden", "64", "--seq-len", "64",
              "--measure", "--out", out])
    assert r.returncode == 0, r.stderr[-2000:]
    import json
    cfg = json.load(open(out))
    assert "sp_flags_enc" in cfg and "pp_division" in cfg


def test_ncf_example_smoke():
    """NCF trainer runs with a compressed table, exercising the per-method
    machinery (codebook_update wiring) through the real script."""
    r = _run(["examples/rec/train_ncf.py", "--head", "neumf", "--method",
              "dpq", "--steps", "5", "--num-users", "300", "--num-items",
              "200", "--batch-size", "64"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "mse" in r.stdout and "mae" in r.stdout


@pytest.mark.slow
def test_ps_scale_bench_smoke():
    """The HET-at-scale sweep runs end-to-end (small tables) and reports
    per-size steps/s + the in-graph feasibility arithmetic."""
    r = _run(["benchmarks/ps_scale_bench.py", "--quick", "--steps", "5"])
    assert r.returncode == 0, r.stderr[-2000:]
    import json
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert len(out["per_table"]) == 2
    assert all(p["steps_per_sec"] > 0 for p in out["per_table"])
    assert out["in_graph_feasible_at_largest"] is True  # quick sizes fit


@pytest.mark.parametrize("model, layers", [
    ("olmoe-1b-7b", "1"),               # softmax router: loads, no bias
    ("nemotron-3-nano-30b-a3b", "2"),   # sigmoid router: loads and biases
    ("sdar-30b-a3b-chat", "2"),         # block diffusion: the noised batch
])
def test_train_llama_example_fetches_an_moe_models_loads(model, layers):
    """The example's fetch list (loss, update, a load a layer and, for the
    families that have ``router_biases()``, a bias a layer) builds and runs
    for a router with a selection bias and for one without."""
    r = _run(["examples/nlp/train_llama.py", "--model", model, "--layers",
              layers, "--hidden", "64", "--intermediate", "32", "--vocab",
              "128", "--seq-len", "64", "--batch-size", "1", "--steps", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "step    1  loss" in r.stdout


def test_train_llama_example_trains_a_cut_across_two_decoders():
    """``--model phi-4-mini-flash-reasoning --layers 6 --first-layer 14``:
    the model's layers 14-19 under their published indices (every kind of
    layer; a Gated Memory Unit and a cross-attention layer reading what two
    recomputed layers before them kept) build and train at a toy size."""
    r = _run(["examples/nlp/train_llama.py", "--model",
              "phi-4-mini-flash-reasoning", "--layers", "6", "--first-layer",
              "14", "--hidden", "64", "--heads", "4:2", "--intermediate",
              "32", "--vocab", "128", "--seq-len", "64", "--batch-size", "1",
              "--steps", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "step    1  loss" in r.stdout
    # a run of layers that leaves out what a reader reads is refused
    r2 = _run(["examples/nlp/train_llama.py", "--model",
               "phi-4-mini-flash-reasoning", "--layers", "2",
               "--first-layer", "18", "--hidden", "64", "--heads", "4:2",
               "--steps", "1"])
    assert r2.returncode != 0 and "reads layer 16" in r2.stderr


def test_train_llama_example_trains_a_byte_level_model_with_eight_heads():
    """``--model evabyte``: EVA attention (two windows of 32, chunks of 4),
    labels ``[B, S, 8]``, whole layers recomputed, builds and trains at a toy
    size."""
    r = _run(["examples/nlp/train_llama.py", "--model", "evabyte", "--layers",
              "2", "--hidden", "64", "--heads", "2:2", "--intermediate", "32",
              "--window", "32:4", "--seq-len", "64", "--batch-size", "1",
              "--steps", "2"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "step    1  loss" in r.stdout


def test_ctr_sparse_opt_example_smoke():
    """train_ctr --sparse-opt (lazy in-graph table updates) runs."""
    r = _run(["examples/ctr/train_ctr.py", "--model", "wdl", "--steps",
              "6", "--sparse-opt", "--num-embeddings", "2000"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "logloss" in r.stdout
    # and the conflicting flags are refused loudly
    r2 = _run(["examples/ctr/train_ctr.py", "--sparse-opt", "--ps",
               "--steps", "1"])
    assert r2.returncode != 0 and "mutually exclusive" in r2.stderr


def test_complex_pipeline_mlp_smoke():
    """Mixed DP x PP graph pipeline example (reference
    examples/runner/parallel/complex_pipeline_mlp.py role) runs with
    per-step loss parity asserted inside."""
    proc = _run(["examples/parallel/complex_pipeline_mlp.py",
                 "--steps", "4", "--width", "16", "--batch", "16",
                 "--num-micro", "2"])
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "loss parity" in proc.stdout, proc.stdout[-1500:]


def test_dist_gcn_example_smoke():
    proc = _run(["examples/gnn/train_dist_gcn.py",
                 "--nodes", "64", "--edges", "256", "--steps", "6",
                 "--hidden", "8", "--features", "8"])
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "loss parity" in proc.stdout, proc.stdout[-1500:]


def test_ctr_real_data_example_smoke():
    """train_ctr --data on the vendored real-format Criteo shard:
    parses, trains, reports held-out AUC (round-5 ingestion path)."""
    proc = _run(["examples/ctr/train_ctr.py", "--model", "wdl",
                 "--data", "examples/ctr/datasets/criteo_sample.txt",
                 "--nrows", "600", "--epochs", "1", "--batch-size", "64"])
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "held-out AUC" in proc.stdout, proc.stdout[-1500:]


def test_ctr_avazu_example_smoke():
    proc = _run(["examples/ctr/train_ctr.py", "--dataset", "avazu",
                 "--data", "examples/ctr/datasets/avazu_sample.csv",
                 "--nrows", "400", "--epochs", "1", "--batch-size", "64"])
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "held-out AUC" in proc.stdout, proc.stdout[-1500:]


def test_dist_gcn_real_data_example_smoke():
    """train_dist_gcn --data on the vendored Cora-format graph across
    the virtual mesh, with loss parity (round-5 ingestion path)."""
    proc = _run(["examples/gnn/train_dist_gcn.py",
                 "--data", "examples/gnn/datasets/cora_sample",
                 "--steps", "5", "--hidden", "8"])
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert "loss parity" in proc.stdout, proc.stdout[-1500:]
