"""The benchmark's side of the Ling-3.0 cell on the CPU: the configuration
file against the published keys, the operations the ``mfu`` reader credits,
the builder at a hybrid toy size against the plain reference with whole layers
recomputed, the cell's rehearsal through the harness, and the probe that
holds the f32 state and the decay a channel."""

import json
import os

import numpy as np
import pytest

import cells
from chipbench import flops_ling3 as fl, run

CELL = "ling-3.0-flash-vl.b1-s8192"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
           "num_experts": 8, "vocab_size": 19648}
#: the family's own mechanism: the delta rule with a decay a key channel
OWN = ("kda_scan_roofline", "kda_block_device_ms_per_step")


def published():
    if not os.path.exists(CATALOG):
        pytest.skip("the catalog is not on this machine")
    rows = [json.loads(ln) for ln in open(CATALOG) if ln.strip()]
    return next(r for r in rows if r["name"] == "Ling-3.0-flash-VL")


def test_configuration_file_holds_the_published_keys():
    row = published()
    _, entry, config, _ = run.load_cell(CELL)
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key, value in row["config"].items():
        assert config[key] == REDUCED.get(key, value), key
    dep = config["deployment"]
    for key in REDUCED:
        assert dep[key] == row["config"][key], key
    assert dep["chips_sharing_a_layer"] * config["num_experts"] == 512
    assert dep["vocabulary_divided"] * config["vocab_size"] == 157184
    assert dep["pipeline_stages"] * dep["layers_a_stage"] == 42
    assert entry["chips"] == 1


def table_part(bench):
    cells.declared(bench, CELL, own=OWN)


def test_benchmark_entries():
    bench, cell, config, mix = run.load_cell(CELL)
    table_part(bench)
    assert cell["config"] == "ling-3.0-flash-vl-pretrain"
    assert sorted(config["reduced"]) == sorted(REDUCED)
    for key in ("kda_gate", "kda_projections", "attention_gate", "qk_norm",
                "kda_heads", "head", "router", "rotary", "remat"):
        assert key in config["assumed"], key
    assert set(config["not_modelled"]) == {
        "vision_tower", "multi_token_prediction", "swiglu_clamp"}
    assert set(mix["reference_tolerance"]) == {
        "ce", "logits_gap", "attention_gap", "dropped", "routing_mismatch",
        "kda_state_gap"}


def test_flops_and_parameters_of_the_cut_configuration():
    """About 1,210 M forward operations a token at this cut (ISSUE 40): the
    six KDA mixers 64%, the MLA layer 12%, the head 8%, the dense MLP 8%, the
    six expert blocks 8%."""
    _, _, c, _ = run.load_cell(CELL)
    assert fl.layer_counts(c) == (6, 1, 1, 6)
    parts = fl.forward_flops_per_token(c, 8192, 8 * 8 / 512)
    total = sum(parts.values())
    assert abs(total - 1210e6) < 15e6

    def share(*names):
        return round(100 * sum(parts[n] for n in names) / total)
    assert share("kda_projections", "kda_rule") == 64
    assert share("attention_projections", "causal_attention") == 12
    assert share("head") == 8 and share("dense_mlp") == 8
    assert share("router", "shared_expert", "held_experts") == 8
    assert parts["kda_rule"] == 6 * 6 * 32 * 128 * 128
    ops, nbytes = fl.kda_step(c, 8192, 64)
    assert ops == 3 * 128 * 32 * fl.kda_chunk(64, 128, 128) and nbytes > 0
    fwd, _ = fl.flash_pass("forward", 32, 8192, 192, 128)
    assert fwd == 2.0 * 32 * 8192 ** 2 * (192 + 128)
    bwd, moved = fl.flash_pass("backward", 32, 8192, 192, 128)
    assert bwd == 2.0 * 32 * 8192 ** 2 * (3 * 192 + 2 * 128)
    assert moved == 4 * 32 * 8192 * 320 * 2


def hybrid_toy(say=lambda msg: None, head_dim=32, **job):
    """The cell's program at toy widths with the published layer pattern
    behind one dense layer (K K K K K A K) and whole layers recomputed (the
    configuration's own ``toy`` is a hybrid of one layer of each kind, see
    its ``why_pattern``)."""
    from chipbench.builders import ling3 as builder
    _, _, config, mix = run.load_cell(CELL)
    config = run.merge(config, config["toy"])
    config = run.merge(config, {"num_hidden_layers": 7, "layer_group_size": 6,
                                "head_dim": head_dim,
                                "job": dict({"remat": "layer"}, **job)})
    mix = run.merge(mix, mix["toy"])
    return builder.build(config, mix, 2 ** 31 + 3, say), mix


@pytest.mark.parametrize("remat", ["layer", "mixer"])
def test_the_cells_builder_at_a_hybrid_toy_size(remat):
    prog, mix = hybrid_toy(remat=remat)
    try:
        feed = prog.make_batches(2 ** 31 + 3, 1)[0]
        want = prog.reference_loss(feed, 1)
        got = prog.eval_loss(feed)
        for term, tol in mix["reference_tolerance"].items():
            assert abs(got[term] - want[term]) < tol, (term, got, want)
        first = prog.step(feed)
        assert abs(first - want["loss"]) < mix["first_loss_tolerance"]
        second = prog.step(feed)
        assert np.isfinite(second) and second != first
        shapes = prog.expected_kernel_shapes()
        assert shapes["attention_passes"] == 1
        assert shapes["attention_layers"] == (2 if remat == "layer" else 1)
        assert shapes["flash_dims"] == (1, 2, 64, 32)
        assert shapes["score_dim"] == 48 and prog.n_layers == 6
    finally:
        prog.close()


def test_the_lowered_train_step_runs_each_layers_kernels_twice(monkeypatch):
    """Whole layers recomputed, heads of the published 128 (the kernels'
    width): the convolution's forward kernel twice a KDA layer (six of them)
    and its backward once, and the delta rule's kernel pair in the step."""
    from conftest import conv_calls, lowered_for_tpu
    text = lowered_for_tpu(monkeypatch,
                           lambda: hybrid_toy(head_dim=128)[0])
    assert conv_calls(text) == (12, 6)
    for name in ("hetu_kda_fwd", "hetu_kda_bwd"):
        assert f'kernel_name = "{name}"' in text


def test_the_lowered_train_step_forms_no_view_by_heads_around_the_rule(
        monkeypatch):
    """The rule's kernels twice forward and once backward a KDA layer (six),
    reading the layer's ``[B, S, H d]`` arrays in place (PR 41): under
    ``hetu_kda_scan`` and ``hetu_kda_out`` no f32 array of rank 4 that ends in
    ``(heads, head size)`` is formed, forward or backward."""
    from conftest import arrays_under, kernel_calls, lowered_for_tpu
    text = lowered_for_tpu(monkeypatch, lambda: hybrid_toy(head_dim=128)[0],
                           debug_info=True)
    assert kernel_calls(text, "hetu_kda_fwd") == 12
    assert kernel_calls(text, "hetu_kda_bwd") == 6
    _, _, config, _ = run.load_cell(CELL)
    heads = run.merge(config, config["toy"])["num_attention_heads"]
    seen, views = zip(*(arrays_under(text, scope, (heads, 128))
                        for scope in ("hetu_kda_scan", "hetu_kda_out")))
    assert sum(seen) > 100 and not any(views), views


def test_cell_rehearses(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 11),
                   "--seconds", "2", "--trace", "0"], rehearsal=True)
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "WRONG" not in out and "routing_mismatch" in out
    assert "kda_state_gap" in out and "logits_gap" in out
    assert "not finite: 0\n" in out


@pytest.mark.parametrize("what", ["bf16_state", "scalar_decay"])
def test_the_probe_refuses(what):
    """A state carried in bf16 and a decay that is one number a head each
    read far over what the chunked rule reads against the f32 recurrence."""
    import jax.numpy as jnp
    from chipbench.builders.ling3 import kda_state_gap
    from hetu_tpu.ops import kda
    _, _, config, _ = run.load_cell(CELL)
    config = run.merge(config, {"num_attention_heads": 2,
                                "job": {"compute_dtype": "float32"}})
    quiet = lambda msg: None
    good = kda_state_gap(config, 512, 11, quiet, kda.chunk_kda)
    assert good < 1e-4
    if what == "bf16_state":
        bad = kda_state_gap(config, 512, 11, quiet, lambda *a:
                            kda.recurrent_kda(*a, state_dtype=jnp.bfloat16))
    else:
        def scalar(q, k, v, g, beta):
            g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
            return kda.chunk_kda(q, k, v, g, beta)
        bad = kda_state_gap(config, 512, 11, quiet, scalar)
    assert bad > 0.004 and bad > 50 * good
