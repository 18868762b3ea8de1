"""Driver benchmark: three north-star metrics vs MEASURED same-chip baselines.

The contract (the reference publishes almost no absolute numbers):
measure the same workload shapes through stock flax/optax — the trusted TPU
idiom MaxText builds on — on the SAME chip, and report `vs_baseline` against
that (VERDICT round-1 item 6).  The three metrics mirror the reference's own
benchmark configs (BASELINE.json):

  1. BERT-base pretraining samples/sec/chip (examples/nlp/bert headline:
     per-device batch 64, seq 512, Adam, dropout on) — headline metric.
  2. GPT-2.7B-shape transformer-layer forward ms (Galvatron computation
     profile: hidden 2560, 32 heads, seq 2048, bsz 2, bf16).  The reference
     repo DOES publish this one: layertype_0 = 2.0645 ms on A100-40GB
     (tools/Hetu-Galvatron/.../computation_profiling_bf16_hidden2560_...json)
     — reported alongside the same-chip flax baseline.
  3. Wide&Deep Criteo-shaped steps/sec, in-graph embedding path
     (examples/ctr wdl_criteo: 26 sparse + 13 dense, 337k rows).

Prints ONE JSON line: the headline metric plus an `extra_metrics` list, every
`vs_baseline` a ratio > 1 iff we beat the measured flax number.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

REFERENCE_A100_GPT_LAYER_MS = 2.0645  # published in the reference repo


def _rerun(fn, lower_is_better=False, n=3, **kw):
    """Run a baseline measurement n times and keep the BEST result (max
    throughput / min latency).  Re-runs reuse the in-process jit cache,
    so the extra cost is timed loops only — and the best-of guards the
    ratio against one-off interference (the r02 ResNet 0.975 was a
    variance artifact: another run of the same build gave 1.01).  n=3
    matches _timeit's best-of-3 groups ours-side, so the treatment is
    symmetric."""
    vals = [fn(**kw) for _ in range(n)]
    return min(vals) if lower_is_better else max(vals)


def _sync(out):
    """Wait for the work that produced ``out``: copying a (small) output
    to the host cannot start before the program that writes it has ended,
    so this closes a timed region as ``block_until_ready`` does."""
    import jax

    np.asarray(jax.tree_util.tree_leaves(out)[0])


def _time_group(fn, reps):
    """One timed group of reps calls (fn already warmed); returns s/call."""
    start = time.perf_counter()
    out = None
    for _ in range(reps):
        out = fn()
    _sync(out)
    return (time.perf_counter() - start) / reps


def _interleaved(ours_fn, base_group, reps, rounds=5):
    """Variance-robust protocol for the small latency-bound stages, whose
    per-call time is mostly host dispatch and moves with whatever else the
    host is doing: ours and the baseline are timed in ALTERNATING groups
    within one process — drift hits both sides — and the reported ratio
    is the MEDIAN of per-round
    adjacent-group ratios (drift is mostly shared within a round, and the
    median drops rounds where a burst hit one side only).  ours_fn: one
    step (already warmed).  base_group: () -> steps/sec for one baseline
    group (compiles once, jit-cached).  Returns
    (ours_best_steps_per_sec, base_best_steps_per_sec, median_ratio)."""
    ours_v, base_v = [], []
    for _ in range(rounds):
        ours_v.append(1.0 / _time_group(ours_fn, reps))
        base_v.append(base_group())
    pairs = [round(o / b, 3) for o, b in zip(ours_v, base_v)]
    ratios = sorted(pairs)
    return (max(ours_v), max(base_v), ratios[len(ratios) // 2], pairs)


def _timeit(fn, reps):
    """Time reps calls of fn; fn must return something SMALL (a scalar or
    loss list)."""
    out = fn()
    _sync(out)
    best = float("inf")
    for _ in range(3):  # best-of-3 groups: robust to one-off interference
        best = min(best, _time_group(fn, reps))
    return best, out


def _interleaved_vs_flash(ours_fn, sps_fn, group_ctor, steps, per_item,
                          base_steps=None, **base_kw):
    """Shared stage tail: measure the flash-equipped baseline on its own
    build (freed after), then interleave ours with the warmed STOCK
    baseline group; the flash number strengthens the bar only when it
    beats stock.  Returns (ours, base, vs_baseline, baseline_dict) in
    caller units (per_item scales a per-call rate to samples/tokens)."""
    import gc

    base_steps = base_steps or steps
    try:
        flash_sps = _rerun(sps_fn, steps=base_steps, flash=True, **base_kw)
    except Exception:
        flash_sps = None
    gc.collect()
    base_group = group_ctor(**base_kw)
    ours_rate, base_rate, ratio, _ = _interleaved(
        ours_fn, lambda: base_group(base_steps) / per_item, steps)
    ours, base = ours_rate * per_item, base_rate * per_item
    bar_extra = (flash_sps / base) if flash_sps and flash_sps > base \
        else 1.0
    return ours, base, round(ratio / bar_extra, 3), {
        "flax_same_chip": round(base, 2),
        "flax_flash_same_chip":
            round(flash_sps, 2) if flash_sps else None}


def bench_bert(quick):
    """Ours: graph-API BERT-base, bf16 compute + f32 masters, Pallas flash
    attention, AdamW — the reference headline config."""
    import jax
    import jax.numpy as jnp
    import hetu_tpu as ht
    from hetu_tpu.models import BertConfig, BertForPreTraining

    if quick:
        B, S, L, steps = 8, 128, 2, 5
    else:
        B, S, L, steps = 64, 512, 12, 20
    c = BertConfig(vocab_size=30522, hidden_size=768, num_hidden_layers=L,
                   seq_len=S, max_position_embeddings=512)
    rng = np.random.default_rng(0)
    input_ids = ht.placeholder_op("input_ids", (B, S), dtype=np.int32)
    token_type = ht.placeholder_op("token_type_ids", (B, S), dtype=np.int32)
    attn_mask = ht.placeholder_op("attention_mask", (B, S))
    mlm_labels = ht.placeholder_op("mlm_labels", (B * S,), dtype=np.int32)
    nsp_labels = ht.placeholder_op("nsp_labels", (B,), dtype=np.int32)

    model = BertForPreTraining(c)
    loss = model.loss(input_ids, token_type, attn_mask, mlm_labels,
                      nsp_labels)
    opt = ht.AdamWOptimizer(learning_rate=1e-4, weight_decay=0.01)
    # rbg: TPU-native RNG for dropout (the flax baseline gets it too)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                     compute_dtype=jnp.bfloat16,
                     rng_impl=None if quick else "rbg")

    ids = rng.integers(0, c.vocab_size, (B, S))
    mlm = np.full((B * S,), -1, np.int64)
    mask_pos = rng.random(B * S) < 0.15
    mlm[mask_pos] = rng.integers(0, c.vocab_size, mask_pos.sum())
    # device-resident feeds: the baseline's data also lives on device, and
    # a real input pipeline prefetches to device, so a per-step upload
    # would time the host copy and not the training step
    feed = {input_ids: jnp.asarray(ids, jnp.int32),
            token_type: jnp.asarray(rng.integers(0, 2, (B, S)), jnp.int32),
            attn_mask: jnp.ones((B, S), jnp.float32),
            mlm_labels: jnp.asarray(mlm, jnp.int32),
            nsp_labels: jnp.asarray(rng.integers(0, 2, (B,)), jnp.int32)}

    out = ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert np.isfinite(out[0]), "non-finite loss"

    from benchmarks.flax_baselines import (bert_samples_per_sec,
                                           bert_train_group)
    ours, base, vs, baselines = _interleaved_vs_flash(
        lambda: ex.run("train", feed_dict=feed),
        bert_samples_per_sec,
        lambda **kw: bert_train_group(kw.pop("batch"), kw.pop("seq_len"),
                                      **kw),
        steps, B, base_steps=max(3, steps // 2),
        batch=B, seq_len=S, layers=L)
    return {"metric": "bert_base_train_samples_per_sec_per_chip",
            "value": round(ours, 2), "unit": "samples/sec",
            "vs_baseline": vs, "protocol": "interleaved_median",
            "baseline": baselines}


def bench_gpt_layer(quick):
    """Ours: pre-norm GPT-2.7B-shape layer (d_head=80) with the Pallas
    flash kernel, 30-layer `lax.scan` in ONE jitted program (one layer is
    a few ms: timed per call it would mostly measure dispatch)."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops.pallas.flash_attention import flash_attention

    if quick:
        B, S, H, heads, n_layers, reps = 1, 256, 128, 2, 2, 2
    else:
        B, S, H, heads, n_layers, reps = 2, 2048, 2560, 32, 30, 5
    d = H // heads
    import gc
    from benchmarks.flax_baselines import gpt_layer_fwd_ms, gpt_layer_group
    kw = dict(batch=B, seq=S, hidden=H, heads=heads,
              n_layers=n_layers) if quick else {}
    # jax's public flash kernel baseline: consistently far behind at
    # d=80 (10.8 vs 6.6 ms stock in every capture) — measured FIRST on
    # its own build (its f32 param stack cannot co-reside with ours +
    # the stock baseline in HBM), then freed
    try:
        flash_ms = _rerun(gpt_layer_fwd_ms, lower_is_better=True,
                          flash=True, reps=reps,
                          param_dtype=jnp.bfloat16, **kw)
    except Exception:
        flash_ms = None
    gc.collect()
    # f32-param stock baseline (the r1-r3 protocol) on its own build:
    # published EVERY round alongside the bf16-param ratio so the trend
    # stays comparable across rounds (VERDICT r4 item 4)
    try:
        f32_ms = _rerun(gpt_layer_fwd_ms, lower_is_better=True,
                        reps=reps, **kw)
    except Exception:
        f32_ms = None
    gc.collect()
    dtype = jnp.bfloat16
    key = jax.random.key(0)
    ks = jax.random.split(key, 6)
    s3 = 0.02
    params = {
        "ln1": jnp.ones((n_layers, H), dtype),
        "ln2": jnp.ones((n_layers, H), dtype),
        # qkv weight shaped [H, 3, heads, d]: the head split+transpose
        # rides the projection einsum's epilogue (the separate
        # reshape->transpose materialized a copy of q/k/v every layer,
        # ~0.25 ms at this shape) — same trick layers/attention.py
        # ships via head_split_linear_op
        "qkv": jax.random.normal(ks[0], (n_layers, H, 3, heads, d),
                                 dtype) * s3,
        # proj shaped [heads, d, H]: the attention output's head-merge
        # transpose rides the projection einsum too (the explicit
        # o.transpose+reshape materialized ~230 us/layer of copies)
        "proj": jax.random.normal(ks[1], (n_layers, heads, d, H),
                                 dtype) * s3,
        "fc1": jax.random.normal(ks[2], (n_layers, H, 4 * H), dtype) * s3,
        "fc2": jax.random.normal(ks[3], (n_layers, 4 * H, H), dtype) * s3,
    }
    x = jax.random.normal(ks[4], (B, S, H), dtype)

    def ln(x, g):
        # one-pass moments (mean + mean-of-squares read x once; jnp.var
        # re-reads it) with the E[x^2]-E[x]^2 form — fine in f32 at LN's
        # post-residual activations scale
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        mu2 = jnp.mean(xf * xf, -1, keepdims=True)
        inv = jax.lax.rsqrt(mu2 - mu * mu + 1e-5)
        return ((xf - mu) * inv).astype(x.dtype) * g

    def layer(x, p):
        h = ln(x, p["ln1"])
        qkv = jnp.einsum("bsE,Ekhd->kbhsd", h, p["qkv"])
        o = flash_attention(qkv[0], qkv[1], qkv[2], causal=True)
        assert o is not None, "flash kernel must cover the GPT shape"
        x = x + jnp.einsum("bhsd,hdE->bsE", o, p["proj"])
        f = ln(x, p["ln2"])
        f = jax.nn.gelu(f @ p["fc1"])
        return (x + f @ p["fc2"], None)

    @jax.jit
    def fwd(params, x):
        out, _ = jax.lax.scan(lambda c, p: layer(c, p), x, params)
        return jnp.sum(out.astype(jnp.float32))

    # interleaved ours/stock rounds (same drift rationale as bench_wdl);
    # the stock baseline stores bf16 params like ours — f32 stacked
    # weights would double its per-layer HBM reads AND overflow HBM
    # next to ours
    base_group = gpt_layer_group(param_dtype=jnp.bfloat16, **kw)
    _sync(fwd(params, x))        # compile+warm ours OUTSIDE the rounds
    ours_v, base_v = [], []
    for _ in range(5):
        dt = _time_group(lambda: fwd(params, x), reps)
        ours_v.append(dt * 1000.0 / n_layers)
        base_v.append(base_group(reps))
    ours_ms = min(ours_v)
    base_ms = min(base_v)
    bars = [min(b, flash_ms) if flash_ms else b for b in base_v]
    ratios = sorted(b / o for o, b in zip(ours_v, bars))
    baselines = {"flax_same_chip_ms": round(base_ms, 4),
                 "flax_flash_same_chip_ms":
                     round(flash_ms, 4) if flash_ms else None,
                 "flax_f32_param_same_chip_ms":
                     round(f32_ms, 4) if f32_ms else None,
                 "reference_a100_ms": REFERENCE_A100_GPT_LAYER_MS}
    return {"metric": "gpt_2.7b_layer_fwd_ms", "value": round(ours_ms, 4),
            "unit": "ms (lower is better)",
            "vs_baseline": round(ratios[len(ratios) // 2], 3),
            "vs_f32_param_stock":
                round(f32_ms / ours_ms, 3) if f32_ms else None,
            "protocol": "interleaved_median",
            "baseline": baselines}


def bench_gpt_e2e(quick):
    """Ours: graph-API GPT-small end-to-end causal-LM pretraining step
    (flagship e2e: flash attention w/ in-kernel dropout, rbg RNG, bf16
    compute + f32 masters, AdamW)."""
    import jax
    import jax.numpy as jnp
    import hetu_tpu as ht
    from hetu_tpu.models import GPTConfig, GPTLMHeadModel

    if quick:
        B, S, L, steps = 2, 128, 2, 3
    else:
        B, S, L, steps = 8, 1024, 12, 10
    c = GPTConfig(vocab_size=50257, hidden_size=768, num_layers=L,
                  num_heads=12, seq_len=S, dropout_prob=0.1)
    rng = np.random.default_rng(0)
    ids = ht.placeholder_op("gpt_ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op("gpt_labels", (B, S), dtype=np.int32)
    loss = GPTLMHeadModel(c).loss(ids, labels)
    opt = ht.AdamWOptimizer(learning_rate=1e-4, weight_decay=0.01)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                     compute_dtype=jnp.bfloat16,
                     rng_impl=None if quick else "rbg")
    ids_v = rng.integers(0, c.vocab_size, (B, S))
    feed = {ids: jnp.asarray(ids_v, jnp.int32),
            labels: jnp.asarray(np.roll(ids_v, -1, 1), jnp.int32)}
    out = ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert np.isfinite(out[0]), "non-finite loss"

    from benchmarks.flax_baselines import (gpt_samples_per_sec,
                                           gpt_train_group)
    ours, base, vs, baselines = _interleaved_vs_flash(
        lambda: ex.run("train", feed_dict=feed),
        gpt_samples_per_sec,
        lambda **kw: gpt_train_group(kw.pop("batch"), kw.pop("seq_len"),
                                     **kw),
        steps, B, batch=B, seq_len=S, layers=L)
    return {"metric": "gpt_small_train_samples_per_sec_per_chip",
            "value": round(ours, 2), "unit": "samples/sec",
            "vs_baseline": vs, "protocol": "interleaved_median",
            "baseline": baselines}


def bench_llama(quick):
    """Ours: Llama-small causal-LM pretraining step (RoPE + GQA + RMSNorm
    + SwiGLU — the reference's Galvatron Llama tier,
    tools/Hetu-Galvatron/galvatron/models/llama) vs a flax twin; the bar
    is the stronger of stock and flash-equipped baselines."""
    import jax
    import jax.numpy as jnp
    import hetu_tpu as ht
    from hetu_tpu.models import LlamaConfig, LlamaForCausalLM

    if quick:
        B, S, L, steps = 2, 128, 2, 3
    else:
        B, S, L, steps = 8, 1024, 12, 10
    c = LlamaConfig(vocab_size=32000, hidden_size=768, num_layers=L,
                    num_heads=12, num_kv_heads=4, intermediate_size=2048,
                    seq_len=S)
    rng = np.random.default_rng(0)
    ids = ht.placeholder_op("lm_ids", (B, S), dtype=np.int32)
    labels = ht.placeholder_op("lm_labels", (B, S), dtype=np.int32)
    loss = LlamaForCausalLM(c).loss(ids, labels)
    opt = ht.AdamWOptimizer(learning_rate=1e-4, weight_decay=0.01)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                     compute_dtype=jnp.bfloat16)
    ids_v = rng.integers(0, c.vocab_size, (B, S))
    feed = {ids: jnp.asarray(ids_v, jnp.int32),
            labels: jnp.asarray(np.roll(ids_v, -1, 1), jnp.int32)}
    out = ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert np.isfinite(out[0]), "non-finite loss"

    # prefetch-driven ours (see bench_wdl): token batches uploaded one
    # step ahead instead of a single device-resident feed
    from hetu_tpu.datasets.prefetch import prefetch_feeds
    pool = []
    for _ in range(4):
        iv = rng.integers(0, c.vocab_size, (B, S))
        pool.append({ids: iv.astype(np.int32),
                     labels: np.roll(iv, -1, 1).astype(np.int32)})
    pf = prefetch_feeds(ex, _batch_pool_stream(pool), "train", depth=2)
    ours_fn = lambda: ex.run("train", feed_dict=next(pf))  # noqa: E731
    ours_fn()

    from benchmarks.flax_baselines import (llama_samples_per_sec,
                                           llama_train_group)
    ours, base, vs, baselines = _interleaved_vs_flash(
        ours_fn,
        llama_samples_per_sec,
        lambda **kw: llama_train_group(kw.pop("batch"), kw.pop("seq_len"),
                                       **kw),
        steps, B, batch=B, seq_len=S, layers=L, kv_heads=4)
    dev_ours = _ours_device_us(ours_fn, 5, "llama")
    pf.close()
    return {"metric": "llama_small_train_samples_per_sec_per_chip",
            "value": round(ours, 2), "unit": "samples/sec",
            "vs_baseline": vs,
            "host_gap": _host_gap(ours / B, dev_ours),
            "prefetch": {"depth": 2, "async": not pf.sync},
            "protocol": "interleaved_median",
            "baseline": baselines}


def bench_resnet(quick):
    """Ours: graph-API ResNet-18 / CIFAR10-shape training step (reference
    benchmark config #1, examples/cnn) — convs on the MXU, BatchNorm
    running stats threaded through the fused vjp."""
    import hetu_tpu as ht
    from hetu_tpu.models import resnet18
    import jax.numpy as jnp

    # large batch: CIFAR steps are tiny, and a small-batch measurement
    # times dispatch, not the chip.  Quick mode (the CPU smoke) must stay
    # under the stage timeout: tiny batch, few rounds.
    B, steps = (32, 3) if quick else (2048, 20)
    rounds = 3 if quick else 7
    rng = np.random.default_rng(0)
    x = ht.placeholder_op("rn_x", (B, 3, 32, 32))
    y = ht.placeholder_op("rn_y", (B,), dtype=np.int32)
    model = resnet18(num_classes=10)
    loss = ht.reduce_mean_op(
        ht.softmax_cross_entropy_sparse_op(model(x), y))
    opt = ht.MomentumOptimizer(learning_rate=0.1, momentum=0.9)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]})
    feed = {x: jnp.asarray(rng.standard_normal((B, 3, 32, 32)),
                           jnp.float32),
            y: jnp.asarray(rng.integers(0, 10, (B,)), jnp.int32)}
    out = ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert np.isfinite(out[0])
    # prefetch-driven ours (see bench_wdl): fresh host batches uploaded
    # one step ahead, executor fast path swapping leaf buffers
    from hetu_tpu.datasets.prefetch import prefetch_feeds
    pool = [{x: rng.standard_normal((B, 3, 32, 32)).astype(np.float32),
             y: rng.integers(0, 10, (B,)).astype(np.int32)}
            for _ in range(4)]
    pf = prefetch_feeds(ex, _batch_pool_stream(pool), "train", depth=2)
    ours_fn = lambda: ex.run("train", feed_dict=next(pf))  # noqa: E731
    ours_fn()
    # interleaved ours/baseline groups (same rationale as bench_wdl: the
    # 0.975-0.991 r2/r3 misses sit inside sequential-measurement drift)
    from benchmarks.flax_baselines import resnet18_train_group
    base_group = resnet18_train_group(batch=B)        # built+warmed ONCE
    ours_sps, base, ratio, round_ratios = _interleaved(
        ours_fn, lambda: base_group(steps) / B,
        steps, rounds=rounds)
    dev_ours = _ours_device_us(ours_fn, 10, "resnet")
    pf.close()
    ours, base = ours_sps * B, base * B
    return {"metric": "resnet18_cifar_train_samples_per_sec_per_chip",
            "value": round(ours, 2), "unit": "samples/sec",
            "vs_baseline": round(ratio, 3),
            "host_gap": _host_gap(ours_sps, dev_ours),
            "prefetch": {"depth": 2, "async": not pf.sync},
            "protocol": "interleaved_median",
            "round_ratios": round_ratios,
            "baseline": {"flax_same_chip": round(base, 2)}}


def bench_moe(quick):
    """Ours: graph-API top-2 MoE FFN block (8 experts, capacity dispatch)
    training step — reference benchmark config #5 (examples/moe); on one
    chip the dispatch/combine einsums and batched expert matmuls are the
    thing measured (EP a2a is exercised on the mesh tests/dryrun)."""
    import hetu_tpu as ht
    from hetu_tpu.layers import MoELayer
    import jax.numpy as jnp

    if quick:
        B, S, H, F, steps = 2, 64, 32, 64, 3
    else:
        B, S, H, F, steps = 8, 1024, 512, 2048, 15
    rng = np.random.default_rng(0)
    x = ht.placeholder_op("moe_x", (B, S, H))
    y = ht.placeholder_op("moe_y", (B, S, H))
    moe = MoELayer(H, F, num_experts=8, k=2, capacity_factor=1.25)
    loss = ht.mse_loss_op(moe(x), y) + moe.aux_loss() * 0.01
    ex = ht.Executor(
        {"train": [loss, ht.AdamOptimizer(1e-3).minimize(loss)]})
    feed = {x: jnp.asarray(rng.standard_normal((B, S, H)), jnp.float32),
            y: jnp.zeros((B, S, H), jnp.float32)}
    out = ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert np.isfinite(out[0])
    from benchmarks.flax_baselines import moe_train_group
    base_group = moe_train_group(batch=B, seq=S, hidden=H, d_ff=F)
    ours_sps, base_sps, ratio, _ = _interleaved(
        lambda: ex.run("train", feed_dict=feed),
        lambda: base_group(steps) / (B * S), steps)
    ours, base = ours_sps * B * S, base_sps * B * S
    return {"metric": "moe_top2_8expert_train_tokens_per_sec",
            "value": round(ours, 2), "unit": "tokens/sec",
            "vs_baseline": round(ratio, 3),
            "protocol": "interleaved_median",
            "baseline": {"flax_same_chip": round(base, 2)}}


def _batch_pool_stream(pool):
    """Endless rotation over a pool of pre-built HOST batches — the
    cheapest stand-in for a real ingestion pipeline that still forces a
    fresh host->device upload every step (what prefetch must hide)."""
    i = 0
    while True:
        yield pool[i % len(pool)]
        i += 1


def _ours_device_us(run_one, steps, tag):
    """Device time of OUR step via a profiler trace — TPU only (CPU
    traces have no device lanes; the aggregator would report host
    events, a misleading stand-in for device time)."""
    import jax

    try:
        if jax.default_backend() != "tpu":
            return None
        return _device_us_per_step(run_one, steps, f"/tmp/bench_{tag}_dev")
    except Exception:
        return None


def _host_gap(wall_steps_per_sec, dev_us):
    """End-to-end vs device ratio for one of OUR steps: wall time per
    step over device time per step.  1.0 == the host is fully off the
    critical path."""
    if not wall_steps_per_sec or not dev_us:
        return None
    return round((1e6 / wall_steps_per_sec) / dev_us, 3)


def _device_us_per_step(run_one, steps, trace_dir):
    """Per-step DEVICE time from a jax.profiler trace: the sum of the
    device ops' durations, which leaves host dispatch out where the wall
    protocols include it."""
    import jax
    from hetu_tpu.timeline import write_aggregates

    with jax.profiler.trace(trace_dir):
        out = None
        for _ in range(steps):
            out = run_one()
        _sync(out)
    aggs = write_aggregates(trace_dir, extra={})
    return sum(v["total_us"] for v in aggs.values()) / steps


def bench_wdl(quick):
    """Ours: graph-API Wide&Deep with the PACKED embedding table
    (ops/pallas/sparse_densify.py — [rows/8, 128] storage, scatter-free
    gradient via the Pallas pack-write kernel, single-pass Adam).

    Reported both ways (VERDICT r4 items 2/5): ``vs_baseline`` is the
    interleaved per-call wall ratio (end to end: at a few hundred us a
    step, host dispatch is a large share of both sides), and
    ``vs_baseline_device`` is the trace-measured device-time ratio —
    packed removes XLA's 194 us scatter (59% of flax's step) and fuses
    the table update into one pass."""
    import hetu_tpu as ht
    from hetu_tpu.models import WDL

    B, rows = (32, 5000) if quick else (128, 337000)
    steps = 10 if quick else 50
    rng = np.random.default_rng(0)
    dense = ht.placeholder_op("dense", (B, 13))
    sparse = ht.placeholder_op("sparse", (B, 26), dtype=np.int32)
    labels = ht.placeholder_op("labels", (B,))
    model = WDL(rows, embedding_dim=16, packed_embedding=True)
    loss = model.loss(dense, sparse, labels)
    ex = ht.Executor(
        {"train": [loss, ht.AdamOptimizer(0.01).minimize(loss)]})
    import jax.numpy as jnp
    feed = {dense: jnp.asarray(rng.standard_normal((B, 13)), jnp.float32),
            sparse: jnp.asarray(rng.integers(0, rows, (B, 26)), jnp.int32),
            labels: jnp.asarray(rng.integers(0, 2, (B,)), jnp.float32)}
    out = ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
    assert np.isfinite(out[0])
    # the test suite runs on forced-CPU (jnp fallback); this stage is
    # the per-round TPU correctness witness for the pack-write KERNEL:
    # same gradient through the kernel and the fallback, same inputs
    import jax
    if jax.default_backend() == "tpu":
        from hetu_tpu.ops.pallas.sparse_densify import packed_lookup
        tbl = ex.params[model.emb.table.name]
        idsv = feed[sparse]
        # distinct per-row cotangents: an all-ones ct would make every
        # same-lane-offset line identical and let a misrouted write-DMA
        # pass the check byte-identically
        ct = jnp.asarray(rng.standard_normal((idsv.size, 16)),
                         jnp.float32)

        def g(t, pallas):
            return jax.grad(lambda t_: jnp.sum(
                packed_lookup(t_, idsv.reshape(-1), 16, pallas) * ct))(t)

        gk = np.asarray(g(tbl, True))
        gf = np.asarray(g(tbl, False))
        err = np.abs(gk - gf).max()
        assert err < 1e-4, f"pack-write kernel diverges from fallback: {err}"
    # the r05 host/device gap fix: drive OUR side through the async
    # device-prefetch pipeline (datasets/prefetch.py) — a pool of host
    # batches is uploaded one step ahead with the committed sharding, and
    # the executor's structure-cached fast path swaps the buffers in, so
    # the per-step host work is one queue pop + one dispatch
    from hetu_tpu.datasets.prefetch import prefetch_feeds
    pool = [{dense: rng.standard_normal((B, 13)).astype(np.float32),
             sparse: rng.integers(0, rows, (B, 26)).astype(np.int32),
             labels: rng.integers(0, 2, (B,)).astype(np.float32)}
            for _ in range(8)]
    pf = prefetch_feeds(ex, _batch_pool_stream(pool), "train", depth=2)
    ours_fn = lambda: ex.run("train", feed_dict=next(pf))  # noqa: E731
    ours_fn()                                    # warm the fast path
    from benchmarks.flax_baselines import wdl_train_group
    base_group = wdl_train_group(batch=B, rows=rows)  # built+warmed ONCE
    base_group(3)
    ours, base, ratio, round_ratios = _interleaved(
        ours_fn, lambda: base_group(steps),
        steps, rounds=7 if quick else 31)
    # device-time ratio from traces — TPU only: on CPU the trace has no
    # device lanes and the aggregator would report host/dispatch events,
    # a misleading stand-in for "device time"
    dev_ratio = None
    dev_ours = _ours_device_us(ours_fn, 30, "wdl_o")
    dev_base = _ours_device_us(lambda: base_group(1), 30, "wdl_b")
    if dev_ours and dev_base:
        dev_ratio = round(dev_base / dev_ours, 3)
    pf.close()
    return {"metric": "wdl_criteo_train_steps_per_sec",
            "value": round(ours, 2), "unit": "steps/sec",
            "vs_baseline": round(ratio, 3),
            "vs_baseline_device": dev_ratio,
            "host_gap": _host_gap(ours, dev_ours),
            "prefetch": {"depth": 2, "async": not pf.sync},
            "device_us_per_step": {
                "ours_packed": round(dev_ours, 1) if dev_ours else None,
                "flax": round(dev_base, 1) if dev_base else None},
            "protocol": "interleaved_median+device_trace",
            "round_ratios": round_ratios,
            "packed_table": True,
            "baseline": {"flax_same_chip": round(base, 2)}}


def bench_wdl_ps(quick):
    """Ours: W&D with the PS host-store embedding path at HET scale —
    tables whose in-graph Adam state cannot fit one chip's 16 GiB HBM,
    trained at a per-step cost FLAT in table size thanks to the client
    cache (LFU, 1% of rows) absorbing zipf traffic (SURVEY §3.4 / HET
    VLDB'22).

    VERDICT r4 items 1c+8: three-point flatness (337k / 2.6M / 8M rows
    by default; the 28.6 GiB 80M tier is opt-in via
    HETU_BENCH_WDL_PS_BIG_ROWS=80000000 — same thesis, a tenth the
    setup cost) with a log-log slope fit, and min/median/max of the
    per-sweep ratios so one noisy group cannot swing the metric.

    `vs_baseline` is the flatness ratio: steps/s at the LARGEST scale
    over steps/s at the smallest (337k) table through the same PS path
    — ~1.0 means table size doesn't tax the step, which is exactly what
    the in-graph path cannot offer past HBM.  `flatness_slope` is the
    fitted d log(steps/s) / d log(rows): ~0 means flat."""
    B, steps = (32, 5) if quick else (128, 30)
    dim = 32
    if quick:
        sizes = [1000, 4000, 10_000]
    else:
        sizes = [337_000, 2_600_000, 8_000_000]
        big = int(os.environ.get("HETU_BENCH_WDL_PS_BIG_ROWS", "0"))
        if big > sizes[-1]:
            sizes.append(big)
    rng = np.random.default_rng(0)
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    from ps_harness import build_wdl_ps, time_steps, zipf_feeds

    def build_at(rows):
        ex, ps_emb, ph = build_wdl_ps(
            rows, dim, B, 26, optimizer="adam", lr=1e-2,
            cache_limit=max(4096, rows // 100), name_prefix=f"wps{rows}")
        feeds = zipf_feeds(rng, rows, B, 26, ph)
        return ex, ps_emb, feeds

    # all stores resident (0.12 + 0.93 + 2.86 GiB host RAM at defaults),
    # timed in ROTATING sweeps: the PS path is host-CPU-bound, so host
    # load drift must hit every size for the flatness ratio to mean
    # anything.  groups=1 per sweep: the median over sweeps IS the
    # robustness; best-of-3 inside each sweep would triple the work and
    # push the groups apart in time.
    built = [build_at(r) for r in sizes]
    rounds = 3 if quick else 7
    sps = {r: [] for r in sizes}
    for _ in range(rounds):
        for r, (ex, _, feeds) in zip(sizes, built):
            sps[r].append(1.0 / time_steps(ex, feeds, steps, groups=1))
    ratios = sorted(sps[sizes[-1]][i] / sps[sizes[0]][i]
                    for i in range(rounds))
    flatness = ratios[len(ratios) // 2]
    med = [sorted(sps[r])[rounds // 2] for r in sizes]
    slope = float(np.polyfit(np.log(np.asarray(sizes, np.float64)),
                             np.log(np.asarray(med, np.float64)), 1)[0])
    hit_big = built[-1][1].stats().get("hit_rate", 0.0)
    rows_big = sizes[-1]
    in_graph_gib = rows_big * dim * 4 * 3 / 1024 ** 3  # params + adam m,v
    return {"metric": "wdl_ps_het_scale_train_steps_per_sec",
            "value": round(max(sps[rows_big]), 2), "unit": "steps/sec",
            "vs_baseline": round(flatness, 3),
            "protocol": f"flatness_{len(sizes)}pt_rotating_median_of_"
                        f"{rounds}",
            "table_rows": rows_big,
            "table_sizes": sizes,
            "steps_per_sec_by_size":
                {str(r): round(m, 2) for r, m in zip(sizes, med)},
            "flatness_slope": round(slope, 4),
            "flatness_min_med_max": [round(ratios[0], 3),
                                     round(flatness, 3),
                                     round(ratios[-1], 3)],
            "host_store_gib": round(in_graph_gib, 2),
            "in_graph_feasible": bool(in_graph_gib < 16.0),
            "cache_hit_rate": round(hit_big, 4),
            "baseline": {"ps_steps_per_sec_at_smallest":
                             round(max(sps[sizes[0]]), 2),
                         "in_graph_adam_gib_at_scale":
                             round(in_graph_gib, 2),
                         "hbm_gib_v5e": 16.0}}


# -- chaos mode (bench.py --chaos) -----------------------------------------
# Resilience evidence to ride alongside the perf rounds: inject faults
# mid-stage through hetu_tpu.resilience.faults and report, per fault
# class, how many were injected vs recovered — plus the steady-state
# cost of the guard itself (guarded vs unguarded steps/sec, and on TPU
# the guarded run's host_gap, which must stay ~1.0: the fused sentinel
# adds no host work to the step path).

CHAOS_DETAIL_PATH = os.environ.get(
    "HETU_CHAOS_JSON",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "CHAOS_FULL.json"))


def _chaos_build(tag, guard=None, B=32, rows=2000, numerics=None):
    """Small W&D train step (the chaos workload: cheap, NaN-prone float
    path through labels/dense) + a deterministic per-step batch maker."""
    import hetu_tpu as ht
    from hetu_tpu.models import WDL

    with ht.name_scope():   # name-stable params: rebuilds restore 1:1
        dense = ht.placeholder_op(f"cz_dense_{tag}", (B, 13))
        sparse = ht.placeholder_op(f"cz_sparse_{tag}", (B, 26),
                                   dtype=np.int32)
        labels = ht.placeholder_op(f"cz_labels_{tag}", (B,))
        model = WDL(rows, embedding_dim=8)
        loss = model.loss(dense, sparse, labels)
    kw = {"step_guard": guard} if guard is not None else {}
    if numerics is not None:
        kw["numerics"] = numerics
    ex = ht.Executor(
        {"train": [loss, ht.AdamOptimizer(0.01).minimize(loss)]}, **kw)

    def batch(i, bad=False):
        r = np.random.default_rng(1000 + i)
        d = r.standard_normal((B, 13)).astype(np.float32)
        if bad:
            d[0, 0] = np.nan
        return {dense: d,
                sparse: r.integers(0, rows, (B, 26)).astype(np.int32),
                labels: r.integers(0, 2, (B,)).astype(np.float32)}

    return ex, batch


def _chaos_nan_skip(steps, injector):
    """NaN batches absorbed by the skip policy: the fused select keeps
    params clean and the run finishes finite.  A NumericsMonitor rides
    along so every trip carries culprit layer attribution — with the
    flight recorder on, the guard_trip incident dump must NAME the
    culprit layer (the ISSUE 12 acceptance gate)."""
    from hetu_tpu import telemetry
    from hetu_tpu.resilience import StepGuard
    from hetu_tpu.telemetry import NumericsMonitor
    guard = StepGuard(policy="skip")
    mon = NumericsMonitor(name="chaos_nan", check_interval=1)
    ex, batch = _chaos_build("skip", guard, numerics=mon)
    fault_at = set(injector.pick_steps(steps, n_faults=2))
    for i in range(steps):
        ex.run("train", feed_dict=batch(i, bad=i in fault_at))
    guard.flush()
    mon.flush()
    final = ex.run("train", feed_dict=batch(steps),
                   convert_to_numpy_ret_vals=True)
    culprit = mon.culprit()
    layers = set(mon.layers or ())
    out = {"faults_injected": len(fault_at),
           "faults_recovered": int(guard.stats["skipped"]),
           "steps": steps,
           "final_loss_finite": bool(np.isfinite(final[0])),
           "culprit_layer": culprit.get("first_nonfinite"),
           "nonfinite_layers": culprit.get("nonfinite_layers")}
    assert out["culprit_layer"] in layers, \
        f"numerics culprit {out['culprit_layer']!r} is not a model layer"
    fl = telemetry.get_flight()
    if fl.enabled and fl.incident_dir:
        trips = [e for e in fl.incidents() if e["kind"] == "guard_trip"]
        assert trips, "no guard_trip incident despite injected NaNs"
        dump = fl.load_dump(trips[-1]["path"])
        named = ((dump.get("extra") or {}).get("culprit")
                 or {}).get("first_nonfinite")
        assert named in layers, \
            f"guard_trip incident dump culprit {named!r} not a layer"
        out["culprit_in_incident"] = named
    mon.close()
    return out


def _chaos_nan_rollback(steps, injector, tmpdir):
    """A NaN that DOES corrupt params (no in-graph select under the
    rollback policy) triggers restore of the last rolling checkpoint."""
    import warnings
    from hetu_tpu.resilience import RollingCheckpointManager, StepGuard
    mgr = RollingCheckpointManager(tmpdir, keep=2)
    guard = StepGuard(policy="rollback", manager=mgr, defer=False)
    ex, batch = _chaos_build("rb", guard)
    (fault_at,) = injector.pick_steps(steps, n_faults=1,
                                      low=max(2, steps // 3))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for i in range(steps):
            if i % 5 == 0:
                mgr.save(ex)
            ex.run("train", feed_dict=batch(i, bad=i == fault_at))
        guard.flush()
    finite = all(
        np.isfinite(np.asarray(v)).all() for v in ex.params.values()
        if np.issubdtype(np.asarray(v).dtype, np.floating))
    return {"faults_injected": 1,
            "faults_recovered": int(guard.stats["rollbacks"]),
            "restored_steps": guard.stats["restored_steps"],
            "params_finite": bool(finite)}


def _chaos_prefetch_kill(steps, injector):
    """Silent producer death mid-stream must surface within one step;
    a fresh prefetcher resumes the run."""
    from hetu_tpu.resilience import StepGuard, faults
    from hetu_tpu.datasets.prefetch import DevicePrefetcher
    ex, batch = _chaos_build("pk", StepGuard(policy="skip"))
    kill_at = injector.pick_steps(steps, n_faults=1,
                                  low=max(2, steps // 3))[0]
    src = (batch(i) for i in range(10 ** 9))
    pf = DevicePrefetcher(faults.killer_stream(src, at=kill_at),
                          depth=2, sync=False)
    n_ok, surfaced = 0, False
    try:
        for _ in range(steps):
            ex.run("train", feed_dict=next(pf))
            n_ok += 1
    except RuntimeError as e:
        surfaced = "producer" in str(e)
    pf.close()
    resumed = 0
    pf2 = DevicePrefetcher((batch(i) for i in range(8)), depth=2,
                           sync=False)
    for _ in range(3):
        ex.run("train", feed_dict=next(pf2))
        resumed += 1
    pf2.close()
    return {"faults_injected": 1, "faults_recovered": int(surfaced),
            "steps_before_kill": n_ok, "kill_at": kill_at,
            "detected_within_one_step": bool(surfaced
                                             and n_ok == kill_at),
            "steps_after_restart": resumed}


def _chaos_torn_ckpt(injector, tmpdir):
    """Tear the NEWEST checkpoint; restore_latest must fall back to the
    previous good one."""
    import warnings
    from hetu_tpu.resilience import RollingCheckpointManager, faults
    mgr = RollingCheckpointManager(tmpdir, keep=3)
    ex, batch = _chaos_build("tc")
    for i in range(6):
        ex.run("train", feed_dict=batch(i))
        mgr.save(ex)
    entries = mgr.entries()
    newest, second = entries[0], entries[1]
    faults.tear_file(os.path.join(tmpdir, newest["file"]), frac=0.5)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        restored = mgr.restore_latest(ex)
    return {"faults_injected": 1,
            "faults_recovered": int(restored == second["step"]),
            "torn_step": newest["step"], "restored_step": restored}


def _chaos_preempt(injector, tmpdir):
    """Simulated SIGTERM preemption: the hook flushes a checkpoint and
    the run resumes bitwise from it."""
    from hetu_tpu.resilience import RollingCheckpointManager, faults
    mgr = RollingCheckpointManager(tmpdir, keep=2)
    ex, batch = _chaos_build("pre")
    mgr.install_preemption_hook(ex, exit_on_save=False)
    try:
        for i in range(5):
            ex.run("train", feed_dict=batch(i))
        faults.simulate_preemption()
        flushed = mgr.preempted
        saved = {k: np.asarray(v).copy() for k, v in ex.params.items()}
        for i in range(5, 8):   # post-preemption work that will be lost
            ex.run("train", feed_dict=batch(i))
        restored = mgr.restore_latest(ex)
        bitwise = all(np.array_equal(saved[k], np.asarray(ex.params[k]))
                      for k in saved)
    finally:
        mgr.uninstall_preemption_hook()
    return {"faults_injected": 1,
            "faults_recovered": int(bool(flushed and bitwise)),
            "checkpoint_flushed": bool(flushed),
            "resumed_step": restored, "bitwise_resume": bool(bitwise)}


def _chaos_elastic(quick, tmpdir):
    """Kill-a-chip elastic recovery vs a cold-restart twin.

    The elastic leg trains on a 2-device dp mesh, loses a chip halfway
    (next dispatch raises DeviceLost), and the ElasticTrainer re-plans
    onto the survivor and resumes from the resharded rolling
    checkpoint.  The twin models the pre-elastic world: the same fault
    cold-restarts training from step 0 on the survivor (no rolling
    checkpoint to adopt).  Both legs report the same goodput measure —
    time spent on steps that COUNTED (last run of each step) over
    wall — so ``elastic_vs_restart_goodput`` is the margin in-place
    recovery buys; ``elastic_recovery_s`` is the recover-protocol wall
    time and the GoodputLedger prices it in the ``reshard`` bucket."""
    import jax
    import hetu_tpu as ht
    from hetu_tpu import telemetry
    from hetu_tpu.resilience import (ElasticTrainer,
                                     RollingCheckpointManager, faults)
    from hetu_tpu.telemetry.goodput import GoodputLedger

    devs = jax.devices()
    if len(devs) < 2:
        return {"faults_injected": 0, "faults_recovered": 0,
                "skipped": "needs >= 2 devices"}
    devs = list(devs[:2])
    n_steps = 8 if quick else 24
    fault_at = n_steps // 2
    B = 16

    def build(strategy):
        with ht.name_scope():
            x = ht.placeholder_op("ez_x", (B, 16))
            y = ht.placeholder_op("ez_y", (B, 1))
            w1 = ht.Variable("ez_in_weight", shape=(16, 8),
                             initializer=ht.init.xavier_normal())
            w2 = ht.Variable("ez_out_weight", shape=(8, 1),
                             initializer=ht.init.xavier_normal())
            loss = ht.mse_loss_op(
                ht.matmul_op(ht.matmul_op(x, w1), w2), y)
            train = ht.AdamOptimizer(0.02).minimize(loss)
        return ht.Executor({"train": [loss, train]},
                           dist_strategy=strategy, seed=11)

    def batch(i):
        r = np.random.default_rng(4000 + i)
        return {"ez_x": r.standard_normal((B, 16)).astype(np.float32),
                "ez_y": r.standard_normal((B, 1)).astype(np.float32)}

    def goodput_frac(step_times, wall):
        # last run of each step is the one that counted; re-runs and
        # recovery time are the lost capacity
        useful = sum(step_times.values())
        return useful / wall if wall > 0 else 0.0

    tel_was_on = telemetry.enabled()
    if not tel_was_on:       # the ledger needs the tracer's spans
        telemetry.enable()
    try:
        # -- elastic leg ---------------------------------------------------
        ledger = GoodputLedger(registry=telemetry.get_registry(),
                               tracer=telemetry.get_tracer(),
                               name="elastic", enabled=True)
        ledger.begin()
        t0 = time.perf_counter()
        mgr = RollingCheckpointManager(os.path.join(tmpdir, "el"),
                                       keep=3, sharded=True)
        tr = ElasticTrainer(build, mgr, devices=devs,
                            checkpoint_every=1, install_hook=False)
        step_times, losses = {}, {}
        lost = []

        def chaotic(i):
            if i == fault_at and not lost:
                lost.append(i)
                faults.lose_device(tr.executor)
            return batch(i)

        while True:
            i = tr.global_step
            if i >= n_steps:
                break
            s0 = time.perf_counter()
            got = tr.train(i + 1, chaotic)
            step_times[i] = time.perf_counter() - s0
            losses.update(got)
        elastic_wall = time.perf_counter() - t0
        acct = ledger.account(wall_s=elastic_wall)
        recovery_s = tr.recovery_s[0] if tr.recovery_s else None
        if recovery_s and fault_at in step_times:
            # the fault step's timing window swallowed the recovery —
            # recovery is lost capacity, not useful step time
            step_times[fault_at] = max(
                0.0, step_times[fault_at] - recovery_s)
        elastic_frac = goodput_frac(step_times, elastic_wall)
        final_loss = losses.get(n_steps - 1)
        recovered = (tr.resharded == 1 and len(losses) == n_steps
                     and all(np.isfinite(v) for v in losses.values()))
        world_after = len(tr.devices)
        tr.executor.close()

        # -- cold-restart twin --------------------------------------------
        t0 = time.perf_counter()
        twin_times = {}
        ex = build(_dp_strategy(devs))
        for i in range(fault_at):           # work the fault throws away
            s0 = time.perf_counter()
            ex.run("train", feed_dict=batch(i))
            twin_times[i] = time.perf_counter() - s0
        faults.lose_device(ex)
        try:                                # the dispatch that finds out
            ex.run("train", feed_dict=batch(fault_at))
        except Exception:
            pass
        ex.close()
        ex = build(_dp_strategy(devs[:1]))  # cold restart: from step 0
        for i in range(n_steps):
            s0 = time.perf_counter()
            ex.run("train", feed_dict=batch(i))
            twin_times[i] = time.perf_counter() - s0
        restart_wall = time.perf_counter() - t0
        restart_frac = goodput_frac(twin_times, restart_wall)
        ex.close()
    finally:
        if not tel_was_on:
            telemetry.disable()

    return {"faults_injected": 1,
            "faults_recovered": int(recovered),
            "world_before": len(devs), "world_after": world_after,
            "resumed_step": fault_at,
            "final_loss": (round(float(final_loss), 6)
                           if final_loss is not None else None),
            "elastic_recovery_s": (round(recovery_s, 6)
                                   if recovery_s is not None else None),
            "elastic_goodput_frac": round(elastic_frac, 4),
            "restart_goodput_frac": round(restart_frac, 4),
            "elastic_vs_restart_goodput": round(
                elastic_frac - restart_frac, 4),
            "fractions": {k: round(v, 6)
                          for k, v in acct["fractions"].items()},
            "steps": n_steps}


def _dp_strategy(devices):
    from hetu_tpu.parallel.mesh import make_mesh
    from hetu_tpu.parallel.strategies import DataParallel
    return DataParallel(mesh=make_mesh({"dp": len(devices)},
                                       devices=devices))


def _chaos_overhead(steps, check_interval=4):
    """Steady-state guard cost: guarded vs unguarded steps/sec on the
    same workload, interleaved groups (shared drift), plus the guarded
    run's host_gap on TPU."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.resilience import StepGuard
    guard = StepGuard(policy="skip", check_interval=check_interval)
    exg, batchg = _chaos_build("ovh_g", guard)
    exu, batchu = _chaos_build("ovh_u")

    def dev_feed(ex, batch):
        return {k: jnp.asarray(v) for k, v in batch(0).items()}

    fg, fu = dev_feed(exg, batchg), dev_feed(exu, batchu)
    run_g = lambda: exg.run("train", feed_dict=fg)    # noqa: E731
    run_u = lambda: exu.run("train", feed_dict=fu)    # noqa: E731
    run_g(), run_u()                                  # compile + warm
    # alternating within-round order + median-of-ratios: the shared-CPU
    # drift this box shows round-to-round (±25%) hits both sides
    ratios, g_best, u_best = [], 0.0, 0.0
    for r in range(8):
        first, second = (run_g, run_u) if r % 2 else (run_u, run_g)
        a = 1.0 / _time_group(first, steps)
        b = 1.0 / _time_group(second, steps)
        g, u = (a, b) if r % 2 else (b, a)
        ratios.append(g / u)
        g_best, u_best = max(g_best, g), max(u_best, u)
    guard.flush()
    ratio = sorted(ratios)[len(ratios) // 2]
    dev_us = _ours_device_us(run_g, min(steps, 20), "chaos_g")
    out = {"guarded_steps_per_sec": round(g_best, 2),
           "unguarded_steps_per_sec": round(u_best, 2),
           "guard_overhead_frac": round(max(0.0, 1.0 - ratio), 4),
           "check_interval": check_interval,
           "host_gap": _host_gap(g_best, dev_us)}
    if jax.default_backend() == "cpu":
        # the skip-select stays a separate pass on the CPU backend; on
        # TPU it fuses into the param-update fusion (one extra operand
        # read), so CPU overstates the guard's device cost
        out["note"] = "cpu_backend_select_unfused"
    return out


def _chaos_numerics_overhead(steps, check_interval=4, sample_every=256):
    """Steady-state numerics-plane cost: monitored vs plain steps/sec
    on the same workload, interleaved groups + median of ratios (the
    chaos-overhead protocol).  Target <= 1% at the production config —
    off-cadence steps run a program with NO stats in it at all (the
    executor switches to the stats-bearing twin host-side every
    ``sample_every``-th step), and host reads are deferred by
    ``check_interval`` so the step path stays sync-free.  Each timing
    group spans exactly ``sample_every`` steps, so every group pays
    exactly one sampled step wherever the cadence phase lands.
    (``sample_every=1`` forensics mode pays ~3 extra memory passes per
    step: near-free on TPU where the reduces fuse into the update
    fusion, visible on CPU.)"""
    import jax.numpy as jnp
    from hetu_tpu.telemetry import NumericsMonitor
    mon = NumericsMonitor(name="ovh_num", check_interval=check_interval,
                          sample_every=sample_every)
    exn, batchn = _chaos_build("ovh_n", numerics=mon)
    exp, batchp = _chaos_build("ovh_p")
    fn = {k: jnp.asarray(v) for k, v in batchn(0).items()}
    fp = {k: jnp.asarray(v) for k, v in batchp(0).items()}
    run_n = lambda: exn.run("train", feed_dict=fn)    # noqa: E731
    run_p = lambda: exp.run("train", feed_dict=fp)    # noqa: E731
    for _ in range(2):                # compile both variants + warm
        run_n(), run_p()
    group = sample_every
    ratios, n_best, p_best = [], 0.0, 0.0
    for r in range(8):
        first, second = (run_n, run_p) if r % 2 else (run_p, run_n)
        a = 1.0 / _time_group(first, group)
        b = 1.0 / _time_group(second, group)
        n, p = (a, b) if r % 2 else (b, a)
        ratios.append(n / p)
        n_best, p_best = max(n_best, n), max(p_best, p)
    mon.flush()
    mon.close()
    ratio = sorted(ratios)[len(ratios) // 2]
    return {"numerics_on_steps_per_sec": round(n_best, 2),
            "numerics_off_steps_per_sec": round(p_best, 2),
            "numerics_overhead_frac": round(max(0.0, 1.0 - ratio), 4),
            "check_interval": check_interval,
            "sample_every": sample_every}


def _telemetry_on():
    """Enable the unified runtime telemetry for this process (bench
    --telemetry): registry + tracer + request trace + flight recorder
    live, plus the /metrics exporter (with the /requests and /incidents
    debug endpoints) when HETU_METRICS_PORT is set.  Incident dumps go
    to HETU_INCIDENT_DIR (default: a shared tempdir — evidence, not
    repo litter; the detail JSON records where)."""
    import tempfile
    from hetu_tpu import telemetry

    port = os.environ.get("HETU_METRICS_PORT")
    inc_dir = os.environ.get(
        "HETU_INCIDENT_DIR",
        os.path.join(tempfile.gettempdir(), "hetu_incidents"))
    telemetry.enable(http_port=int(port) if port else None,
                     incident_dir=inc_dir)
    return telemetry


def _telemetry_report(exclude_rids=()):
    """Registry snapshot + step-phase breakdown + the request-timeline
    audit for a detail JSON.  ``exclude_rids``: rid prefixes of engines
    whose DEATH is a stage's point (unprotected twins) — their
    abandoned streams are incomplete by design, not by bug."""
    from hetu_tpu import telemetry

    rep = telemetry.report()
    rt = telemetry.get_request_trace()
    rids = rt.rids()
    audited = [r for r in rids
               if not any(str(r).startswith(p) for p in exclude_rids)]
    bad = [str(r) for r in audited if not rt.complete(r)]
    rep["rid_audit"] = {"rids": len(rids), "audited": len(audited),
                        "complete": len(audited) - len(bad),
                        "incomplete": bad[:8],
                        "all_complete": not bad}
    fl = telemetry.get_flight()
    rep["incident_dir"] = fl.incident_dir
    rep["incident_index"] = fl.incidents()
    return rep


def _assert_rid_audit(rep):
    """The ISSUE 9 acceptance gate: every accepted (non-excluded) rid
    must show a complete admit->terminal timeline, stitched across
    however many failovers it survived."""
    audit = rep["rid_audit"]
    assert audit["all_complete"], \
        f"incomplete rid timelines: {audit['incomplete']}"


def _staged(stage_fn, *args):
    """Run one chaos stage and attach how many flight-recorder
    incidents it tripped (--telemetry: the per-stage post-mortem count
    next to the recovery evidence)."""
    from hetu_tpu import telemetry

    fl = telemetry.get_flight()
    n0 = fl.incident_count()
    out = stage_fn(*args)
    if fl.enabled:
        out["incidents_during"] = fl.incident_count() - n0
    return out


class _PlaneProbe:
    """ISSUE 19 chaos acceptance: a dedicated time-series plane (own
    ring + the standard slo_rules AlertManager + a scoped GoodputLedger,
    all on one manual clock) wrapped around the canonical fault stages.
    Each probed stage must (a) fire EXACTLY its named alert rule — one
    pending->firing transition, resolving once the movement ages out of
    the window, no flapping — with truly-unrelated fault rules quiet,
    and (b) attribute lost capacity to the MATCHING goodput cause with
    the bucket fractions summing to 1.  Inactive (one flag check per
    wrapped stage) unless --telemetry enabled the instruments the plane
    reads."""

    #: the fault-class -> rule -> cause contract probed by the chaos
    #: modes (nan step, engine crash, transfer fault, overload burst)
    FAULT_RULES = ("guard_trips", "engine_crashes",
                   "migration_failures", "overload_shed")

    def __init__(self, tag):
        from hetu_tpu import telemetry
        from hetu_tpu.telemetry import GoodputLedger

        self.active = telemetry.enabled()
        if not self.active:
            return
        self.t = 0.0                # manual clock: 1.0 per poll
        clock = lambda: self.t      # noqa: E731
        self._clock = clock
        self.ledger = GoodputLedger(
            registry=telemetry.get_registry(),
            tracer=telemetry.get_tracer(), name=str(tag),
            clock=clock, enabled=True)
        self._fresh_plane()

    def _fresh_plane(self):
        """A NEW ring + AlertManager for each probed stage: the first
        frames baseline the registry as it stands NOW, so counter
        movement from unprobed stages run between probes (while the
        manual clock is frozen) cannot masquerade as a fresh burst
        inside this stage's window — and the transition history is
        per-stage by construction."""
        from hetu_tpu import telemetry
        from hetu_tpu.telemetry import (AlertManager, TimeSeriesStore,
                                        slo_rules)
        reg = telemetry.get_registry()
        self.store = TimeSeriesStore(registry=reg, capacity=256,
                                     clock=self._clock, enabled=True)
        # window=8 ticks, for_ticks=2: a fault fires on the second
        # post-fault poll and ages out after eight — short enough that
        # one probe sequence walks the whole state machine
        self.alerts = AlertManager(
            self.store, slo_rules(window=8.0, for_ticks=2),
            registry=reg, flight=telemetry.get_flight(),
            clock=self._clock, enabled=True)

    def _poll(self, n):
        fired = set()
        for _ in range(n):
            self.t += 1.0
            fired.update(self.alerts.poll(self.t))
        return fired

    def stage(self, rule, cause, quiet, stage_fn, *args):
        """Run one fault stage under the probe.  ``rule``: the alert
        that MUST fire; ``cause``: the goodput bucket the lost capacity
        MUST land in; ``quiet``: fault rules that must NOT fire (the
        FAULT_RULES minus legitimate co-trips — e.g. a transfer fault
        stage crashes an engine on purpose, so engine_crashes is not in
        its quiet set)."""
        if not self.active:
            return _staged(stage_fn, *args)
        self._fresh_plane()
        self._poll(3)                       # pre-fault baseline
        self.ledger.begin(now=self.t)
        w0 = time.perf_counter()
        out = _staged(stage_fn, *args)
        wall = time.perf_counter() - w0
        fired = self._poll(4)               # detection window
        acct = self.ledger.account(wall_s=wall, now=self.t)
        self._poll(12)                      # fault ages out: resolve
        assert rule in fired, \
            f"injected fault did not fire alert rule {rule!r} " \
            f"(fired: {sorted(fired)})"
        firings = [t for s, t in self.alerts.transitions(rule)
                   if s == "firing"]
        assert len(firings) == 1, \
            f"alert rule {rule!r} flapped: firing at {firings}"
        end_state = self.alerts.state(rule)
        assert end_state in ("resolved", "inactive"), \
            f"alert rule {rule!r} never resolved (state {end_state!r})"
        for q in quiet:
            q_fired = [t for s, t in self.alerts.transitions(q)
                       if s == "firing"]
            assert not q_fired, \
                f"unrelated fault rule {q!r} fired at {q_fired} " \
                f"during the {rule!r} stage"
        fr = acct["fractions"]
        total = sum(fr.values())
        assert abs(total - 1.0) <= 1e-6, \
            f"goodput fractions sum to {total!r}, not 1"
        assert fr[cause] > 0.0, \
            f"no lost capacity attributed to {cause!r} " \
            f"(lost: {acct['lost']})"
        out["alert"] = {"rule": rule, "fired": sorted(fired),
                        "transitions": self.alerts.transitions(rule),
                        "state": end_state,
                        "quiet_checked": sorted(quiet)}
        out["goodput"] = {"cause": cause,
                          "cause_fraction": fr[cause],
                          "goodput_fraction": acct["goodput_fraction"],
                          "fractions_sum": round(total, 9),
                          "window_s": acct["window_s"],
                          "scaled_to_wall": acct["scaled_to_wall"],
                          "lost": acct["lost"]}
        return out


def run_telemetry_overhead(quick=False, rounds=6):
    """Measured cost of telemetry-on vs -off on the SAME warmed step
    (interleaved groups, median of ratios — the chaos-overhead
    protocol): the proof that the disabled fast path is free and the
    enabled path is cheap.  The ISSUE 19 plane rides the same twin at
    its production cadence: both sides run a store-tick + full
    alert-rule evaluation at most every ``poll_interval_s`` of wall
    time (an operator plane polls on a wall-clock period, not per
    step) — enabled on the ON side, the one-flag-check disabled path
    on the OFF side — so ``overhead_frac`` covers metric history and
    alerting, not just the registry/tracer.  The goodput ledger is a
    report-time instrument (one account per window, never per step),
    so its cost is measured once and reported separately."""
    import jax
    from hetu_tpu import telemetry
    from hetu_tpu.telemetry import (AlertManager, GoodputLedger,
                                    TimeSeriesStore, slo_rules)

    steps = 15 if quick else 40
    poll_interval_s = 0.1
    ex, batch = _chaos_build("tel")
    import jax.numpy as jnp
    feed = {k: jnp.asarray(v) for k, v in batch(0).items()}
    reg = telemetry.get_registry()
    plane = {"t": 0.0, "last": 0.0}
    clock = lambda: plane["t"]                        # noqa: E731
    store = TimeSeriesStore(registry=reg, capacity=256, clock=clock)
    alerts = AlertManager(store, slo_rules(), registry=reg, clock=clock)
    ledger = GoodputLedger(registry=reg, tracer=telemetry.get_tracer(),
                           name="overhead", clock=clock)

    def run():
        out = ex.run("train", feed_dict=feed)
        now = time.perf_counter()
        if now - plane["last"] >= poll_interval_s:
            plane["last"] = now
            plane["t"] += 1.0
            alerts.poll(plane["t"])
        return out

    def set_on(on):
        telemetry.enable() if on else telemetry.disable()
        store.enabled = alerts.enabled = ledger.enabled = bool(on)

    set_on(False)
    run()                                             # compile + warm
    ratios, on_best, off_best = [], 0.0, 0.0
    for r in range(rounds):
        set_on(bool(r % 2))
        a = 1.0 / _time_group(run, steps)
        set_on(not r % 2)
        b = 1.0 / _time_group(run, steps)
        on, off = (a, b) if r % 2 else (b, a)
        ratios.append(on / off)
        on_best, off_best = max(on_best, on), max(off_best, off)
    set_on(True)
    ledger.begin(now=plane["t"])
    run()
    t0 = time.perf_counter()
    ledger.account(now=plane["t"] + 1.0)
    account_cost = time.perf_counter() - t0
    set_on(False)
    ratio = sorted(ratios)[len(ratios) // 2]
    return {"metric": "telemetry_overhead",
            "telemetry_on_steps_per_sec": round(on_best, 2),
            "telemetry_off_steps_per_sec": round(off_best, 2),
            "overhead_frac": round(max(0.0, 1.0 - ratio), 4),
            "plane": {"poll_interval_s": poll_interval_s,
                      "rules": len(alerts.rules()),
                      "ticks": store.tick_count,
                      "evals": alerts.evals,
                      "goodput_account_cost_s": round(account_cost, 6)},
            "platform": jax.default_backend(), "steps": steps}


def run_chaos(quick=False, seed=0, elastic=False):
    import tempfile
    import jax
    from hetu_tpu.resilience import FaultInjector

    steps = 12 if quick else 40
    injector = FaultInjector(seed)
    probe = _PlaneProbe("chaos_train")
    stages = {}
    stages["nan_skip"] = _staged(_chaos_nan_skip, steps, injector)
    with tempfile.TemporaryDirectory() as d:
        # the nan fault class under the plane probe: the injected
        # non-finite step must fire guard_trips (and nothing else in
        # the fault set) and the lost capacity must land in rollback
        stages["nan_rollback"] = probe.stage(
            "guard_trips", "rollback",
            ("engine_crashes", "migration_failures", "overload_shed"),
            _chaos_nan_rollback, steps, injector, d)
    stages["prefetch_kill"] = _staged(_chaos_prefetch_kill, steps,
                                      injector)
    with tempfile.TemporaryDirectory() as d:
        stages["torn_ckpt"] = _staged(_chaos_torn_ckpt, injector, d)
    with tempfile.TemporaryDirectory() as d:
        stages["preempt"] = _staged(_chaos_preempt, injector, d)
    if elastic:
        with tempfile.TemporaryDirectory() as d:
            stages["elastic"] = _staged(_chaos_elastic, quick, d)
    overhead = _chaos_overhead(steps)
    numerics_overhead = _chaos_numerics_overhead(steps)
    out = {"metric": "chaos_resilience",
           "value": sum(s["faults_recovered"] for s in stages.values()),
           "unit": "faults_recovered",
           "seed": seed,
           "platform": jax.default_backend(),
           "stages": stages}
    out.update(overhead)
    out["numerics"] = numerics_overhead
    el = stages.get("elastic", {})
    if el.get("elastic_recovery_s") is not None:
        # the perf_diff contract: a flat signals block like --profile's
        out["signals"] = {
            "elastic_recovery_s": el["elastic_recovery_s"],
            "elastic_vs_restart_goodput":
                el["elastic_vs_restart_goodput"]}
    out["all_stages_recovered"] = all(
        s["faults_recovered"] >= 1 for s in stages.values()
        if "skipped" not in s)
    return out


def _emit_chaos(out, detail_path=None):
    detail_path = CHAOS_DETAIL_PATH if detail_path is None else detail_path
    full = json.dumps(out)
    try:
        with open(detail_path, "w") as f:
            f.write(full + "\n")
    except OSError:
        pass
    print(full, flush=True)
    compact = {"metric": out["metric"], "value": out["value"],
               "unit": out["unit"],
               "all_stages_recovered": out["all_stages_recovered"],
               "guard_overhead_frac": out.get("guard_overhead_frac"),
               "host_gap": out.get("host_gap"),
               "stages": {k: f"{v['faults_recovered']}/"
                             f"{v['faults_injected']}"
                          for k, v in out["stages"].items()},
               "detail": os.path.basename(detail_path)}
    for k in ("zero_accepted_loss", "single_engine_twin_lost_streams",
              "signals"):
        if k in out:
            compact[k] = out[k]
    if "telemetry_overhead" in out:
        compact["telemetry_overhead_frac"] = \
            out["telemetry_overhead"]["overhead_frac"]
    if "numerics" in out:
        compact["numerics_overhead_frac"] = \
            out["numerics"]["numerics_overhead_frac"]
        compact["culprit_layer"] = \
            out["stages"].get("nan_skip", {}).get("culprit_layer")
    _print_compact(compact, drop_order=("host_gap",))


# -- serve mode (bench.py --serve) -----------------------------------------
# Inference-serving evidence: replay one seeded Poisson arrival trace of
# mixed-length requests through the continuous-batching engine
# (hetu_tpu/serving/) and through a static-batch twin that runs the SAME
# jitted programs under gang scheduling (admit only when every slot is
# free — the occupancy collapse iteration-level batching removes).
# Reported: tokens/s, TTFT/TPOT/queue-wait percentiles, mean batch
# occupancy, and the compile-once witness (trace counts must be 1).

SERVE_DETAIL_PATH = os.environ.get(
    "HETU_SERVE_JSON",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "SERVE_FULL.json"))


def _serve_build(quick, kv_heads=None):
    """Llama-tier decode model sized for the platform; random
    name-seeded init (deterministic) — serving perf does not depend on
    trained weights.  ``kv_heads`` overrides the KV-head count so the
    --tp stage can pick a head geometry the mesh divides."""
    import hetu_tpu as ht
    from hetu_tpu.models import LlamaConfig, LlamaForCausalLM

    if quick:
        c = LlamaConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=4, num_kv_heads=kv_heads or 2,
                        intermediate_size=56, seq_len=16)
    else:
        c = LlamaConfig(vocab_size=1024, hidden_size=128, num_layers=4,
                        num_heads=8, num_kv_heads=kv_heads or 4,
                        intermediate_size=384, seq_len=64)
    model = LlamaForCausalLM(c, name="serve")
    ids = ht.placeholder_op("serve_ids", (1, 4), dtype=np.int32)
    ex = ht.Executor([model(ids)])
    return ex, model, c


def _serve_trace(seed, n_requests, vocab, p_lo, p_hi, new_lo, new_hi,
                 mean_gap=0.6):
    """Seeded open-loop arrival trace: Poisson-process arrivals measured
    in scheduler iterations (exponential inter-arrival gaps, mean
    ``mean_gap`` iterations — platform-independent and reproducible),
    prompts and output budgets mixed-length uniform."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(mean_gap, n_requests)
    arrivals = np.floor(np.cumsum(gaps)).astype(np.int64)
    trace = []
    for i in range(n_requests):
        p_len = int(rng.integers(p_lo, p_hi + 1))
        trace.append((int(arrivals[i]),
                      rng.integers(1, vocab, (p_len,)).astype(np.int32),
                      int(rng.integers(new_lo, new_hi + 1))))
    return trace


def _serve_replay(engine, trace):
    """Drive one engine through the trace (arrival clock = iteration
    index) and summarize throughput + latency percentiles.
    ``stream_sha`` hashes every request's token stream in trace order —
    two engines replaying the same trace produced bitwise-identical
    streams iff the hashes match (the paged-vs-slot parity witness)."""
    import hashlib

    from hetu_tpu.metrics import request_latency_summary

    engine.reset_stats()
    t0 = time.perf_counter()
    submitted, it, reqs = 0, 0, []
    while submitted < len(trace) or not engine.scheduler.idle:
        while submitted < len(trace) and trace[submitted][0] <= it:
            _, prompt, max_new = trace[submitted]
            reqs.append(engine.submit(prompt, max_new))
            submitted += 1
        engine.step()
        it += 1
    wall = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in reqs)
    assert all(r.finished for r in reqs), "replay left unfinished requests"
    sha = hashlib.sha256()
    for r in reqs:
        sha.update(np.asarray(r.tokens, np.int32).tobytes())
    lat = request_latency_summary(engine.records)
    stats = engine.stats()
    return {"tokens_per_sec": round(toks / wall, 2),
            "total_tokens": toks,
            "wall_s": round(wall, 3),
            "iterations": it,
            "decode_steps": stats["decode_steps"],
            "mean_occupancy": stats["mean_occupancy"],
            "peak_active": stats["peak_active"],
            "peak_live_tokens": stats["peak_live_tokens"],
            "prefill_chunks": stats["prefill_chunks"],
            "stream_sha": sha.hexdigest()[:16],
            "trace_counts": stats["trace_counts"],
            "latency_s": {k: {q: (round(x, 6)
                                  if isinstance(x, float) else x)
                              for q, x in v.items()}
                          for k, v in lat.items()}}


def run_serve(quick=False, seed=0):
    import jax
    from hetu_tpu.serving import InferenceEngine

    ex, model, c = _serve_build(quick)
    if quick:
        n_slots, max_len, max_prompt = 4, 48, 12
        trace = _serve_trace(seed, 24, c.vocab_size, 3, 12, 4, 16)
    else:
        n_slots, max_len, max_prompt = 8, 160, 48
        trace = _serve_trace(seed, 80, c.vocab_size, 8, 48, 8, 64)
    kw = dict(n_slots=n_slots, max_len=max_len, max_prompt_len=max_prompt,
              prefill_budget=2, name="serve", seed=seed)

    def best_of(engine, tr, n=2):
        # replay variance on shared CPUs swings +-10%; keep the best of
        # n measured replays (every replay still asserts correctness)
        best = None
        for _ in range(n):
            r = _serve_replay(engine, tr)
            if best is None or r["tokens_per_sec"] > best["tokens_per_sec"]:
                best = r
        return best

    results = {}
    engines = {}
    for mode, gang in (("continuous", False), ("static_batch", True)):
        eng = InferenceEngine(ex, model, gang=gang, instance=mode, **kw)
        # warm the jitted programs with one untimed replay; the trace
        # counters keep counting, so a retrace DURING the measured
        # replay still shows up as trace_counts > 1
        eng.generate_many([trace[0][1]], 2)
        _serve_replay(eng, trace)
        results[mode] = best_of(eng, trace)
        engines[mode] = eng

    # paged twin (ISSUE 13): the same model + trace through a paged-KV
    # engine whose pool holds the SAME usable KV HBM as the slot twin's
    # dense pool — n_pages * page_len == n_slots * max_len tokens (+ the
    # never-allocated sentinel page) — but spread over pages, so
    # worst-case reservation per request (< max_len for real mixes)
    # admits MORE concurrent requests at equal bytes.  Chunked prefill
    # (prefill_token_budget) keeps decode interleaving under long
    # prompts.
    if quick:
        paged_slots, page_len, prefill_budget, mix_budget = 8, 8, 24, 6
    else:
        paged_slots, page_len, prefill_budget, mix_budget = 16, 16, 96, 24
    n_pages = (n_slots * max_len) // page_len + 1   # + sentinel
    pkw = dict(kw, n_slots=paged_slots, paged=True, page_len=page_len,
               n_pages=n_pages, prefill_token_budget=prefill_budget)
    peng = InferenceEngine(ex, model, instance="paged", **pkw)
    # warm EVERY pow2 prefill bucket the trace can hit by replaying it
    # once untimed, then pin the retrace counters: a flat counter dict
    # across the measured replays is the compile-once witness
    _serve_replay(peng, trace)
    warm_traces = dict(peng.trace_counts)
    # fair A/B: measure the slot and paged twins ADJACENTLY with
    # alternating replays and keep each engine's best.  In-process
    # warm-state drift between stages (allocator / code-cache state left
    # behind by whichever engine ran last) biases a later stage by
    # 10-25% on shared CPUs, so a sequential slot-then-static-then-paged
    # sweep systematically under-reads the paged twin; interleaving
    # exposes both engines to the same instantaneous machine state.
    best_slot = best_paged = None
    for _ in range(3):
        rs = _serve_replay(engines["continuous"], trace)
        rp = _serve_replay(peng, trace)
        if best_slot is None or (rs["tokens_per_sec"]
                                 > best_slot["tokens_per_sec"]):
            best_slot = rs
        if best_paged is None or (rp["tokens_per_sec"]
                                  > best_paged["tokens_per_sec"]):
            best_paged = rp
    results["paged"] = best_paged
    results["slot_adjacent"] = best_slot
    paged_flat = peng.trace_counts == warm_traces
    # TPOT under a long-prompt + short-decode mix, with the prefill
    # budget dropped BELOW the prompt lengths so every long prompt
    # chunks and decode interleaves between its chunks — the
    # head-of-line latency claim (the budget is a host-side scheduling
    # knob, not program geometry: same executables at the same shapes).
    # Smaller chunks CAN hit new pow2 prefill buckets, so this workload
    # gets its own untimed warm replay before the measured one.
    peng.prefill_token_budget = mix_budget
    mix = _serve_trace(seed + 1, 12 if quick else 40, c.vocab_size,
                       max(3, max_prompt - 2), max_prompt, 2, 6,
                       mean_gap=0.3)
    _serve_replay(peng, mix)
    results["paged_longmix"] = best_of(peng, mix)

    # goodput evidence (ISSUE 19): one extra UNTIMED replay of the
    # paged engine under a scoped ledger window — the serving goodput
    # fraction (useful prefill+decode span time over wall) becomes a
    # one-sided perf_diff signal.  The instruments the ledger reads
    # must be live for this replay, so telemetry is enabled around it
    # (and restored after) — the timed A/B replays above are untouched.
    from hetu_tpu import telemetry as _tel
    from hetu_tpu.telemetry import GoodputLedger
    _was_on = _tel.enabled()
    _tel.enable()
    ledger = GoodputLedger(registry=_tel.get_registry(),
                           tracer=_tel.get_tracer(), name="serve",
                           enabled=True)
    ledger.begin()
    g0 = time.perf_counter()
    _serve_replay(peng, mix)
    goodput = ledger.account(wall_s=time.perf_counter() - g0)
    if not _was_on:
        _tel.disable()

    cont, stat = results["continuous"], results["static_batch"]
    paged, slot = results["paged"], results["slot_adjacent"]
    scache = engines["continuous"].cache
    sb = int(scache.k.nbytes) + int(scache.v.nbytes)
    pb = int(peng.cache.k.nbytes) + int(peng.cache.v.nbytes)
    usable_pb = pb * (n_pages - 1) // n_pages
    vs = round(cont["tokens_per_sec"] / stat["tokens_per_sec"], 3)
    pvs = round(paged["tokens_per_sec"] / slot["tokens_per_sec"], 3)
    signals = {
        "serve_tokens_per_s": paged["tokens_per_sec"],
        "serve_slot_tokens_per_s": slot["tokens_per_sec"],
        "serve_paged_peak_concurrency": paged["peak_active"],
        "serve_slot_peak_concurrency": slot["peak_active"],
        "kv_hbm_bytes_per_token": round(
            pb / max(1, paged["peak_live_tokens"]), 1),
        "serve_chunked_tpot_p99_s":
            results["paged_longmix"]["latency_s"]["tpot"]["p99"],
        "serve_goodput_fraction": goodput["goodput_fraction"],
    }
    return {"metric": "serve_continuous_tokens_per_sec",
            "value": cont["tokens_per_sec"], "unit": "tokens/sec",
            "vs_baseline": vs,       # > 1 iff continuous beats static
            "continuous_wins": bool(vs > 1.0),
            "compile_once": bool(
                cont["trace_counts"] == {"prefill": 1, "step": 1}),
            "platform": jax.default_backend(),
            "seed": seed, "quick": bool(quick),
            "n_requests": len(trace), "n_slots": n_slots,
            "max_len": max_len, "max_prompt_len": max_prompt,
            "paged": {"n_slots": paged_slots, "page_len": page_len,
                      "n_pages": n_pages,
                      "prefill_token_budget": prefill_budget,
                      "longmix_token_budget": mix_budget,
                      "pool_bytes": pb, "slot_pool_bytes": sb,
                      "usable_pool_bytes": usable_pb,
                      "equal_hbm": bool(usable_pb == sb),
                      "vs_slot": pvs,
                      "wins_throughput": bool(pvs >= 1.0),
                      "wins_concurrency": bool(
                          paged["peak_active"] > slot["peak_active"]),
                      "bitwise_match": bool(
                          paged["stream_sha"] == slot["stream_sha"]),
                      "compile_flat": bool(paged_flat),
                      "pages": peng.stats()["pages"]},
            "signals": signals,
            "goodput": goodput,
            "stages": results}


def _emit_serve(out):
    """Serve evidence in the same layered shape as --chaos: full
    headline to an early line + SERVE_FULL.json, compact tail line that
    fits the driver's stdout window.  The detail file is written only
    now — after the run has real results — so an aborted run never
    clobbers the previous round's committed evidence with a placeholder
    (the BENCH_FULL.json contract, REVIEW r6).  The flat ``signals``
    block also appends to benchmarks/history.jsonl so
    ``tools/perf_diff.py --current SERVE_FULL.json`` can gate the
    paged-vs-slot serving numbers like any --profile round."""
    from hetu_tpu.telemetry import JsonlWriter
    full = json.dumps(out)
    try:
        with open(SERVE_DETAIL_PATH, "w") as f:
            f.write(full + "\n")
    except OSError:
        pass
    if out.get("signals"):
        entry = {"t": round(time.time(), 3), "platform": out["platform"],
                 "quick": out["quick"], "seed": out["seed"],
                 "signals": out["signals"]}
        try:
            os.makedirs(os.path.dirname(HISTORY_PATH) or ".",
                        exist_ok=True)
            with JsonlWriter(HISTORY_PATH) as w:  # append, never truncate
                w.write(entry)
        except OSError:
            pass
    print(full, flush=True)
    lat_c = out["stages"]["continuous"]["latency_s"]
    pg = out["paged"]
    compact = {"metric": out["metric"], "value": out["value"],
               "unit": out["unit"], "vs_baseline": out["vs_baseline"],
               "continuous_wins": out["continuous_wins"],
               "compile_once": out["compile_once"],
               "occupancy": {
                   "continuous":
                       out["stages"]["continuous"]["mean_occupancy"],
                   "static_batch":
                       out["stages"]["static_batch"]["mean_occupancy"]},
               "ttft_s": {"p50": lat_c["ttft"]["p50"],
                          "p99": lat_c["ttft"]["p99"]},
               "tpot_s": {"p50": lat_c["tpot"]["p50"],
                          "p99": lat_c["tpot"]["p99"]},
               "paged": {
                   "tok_s": out["signals"]["serve_tokens_per_s"],
                   "vs_slot": pg["vs_slot"],
                   "peak": [out["signals"]["serve_paged_peak_concurrency"],
                            out["signals"]["serve_slot_peak_concurrency"]],
                   "kv_B_per_tok":
                       out["signals"]["kv_hbm_bytes_per_token"],
                   "tpot_p99_s":
                       out["signals"]["serve_chunked_tpot_p99_s"],
                   "bitwise": pg["bitwise_match"],
                   "equal_hbm": pg["equal_hbm"],
                   "compile_flat": pg["compile_flat"]},
               "detail": os.path.basename(SERVE_DETAIL_PATH)}
    if "telemetry_overhead" in out:
        compact["telemetry_overhead_frac"] = \
            out["telemetry_overhead"]["overhead_frac"]
    _print_compact(compact, drop_order=("occupancy",))


# -- speculative serve mode (bench.py --serve --spec) -----------------------
# Speculative-decoding + prefix-caching evidence (ISSUE 15): the SAME
# paged engine + arrival trace, once plain and once with spec_k draft
# lookahead, at byte-identical page-pool geometry (self-draft reuses
# the target's own weights and KV pages — zero extra HBM).  The sha256
# stream witness must match bitwise: acceptance is prefix-match against
# the teacher-forced verify step, so speculation is a latency
# optimization, never a sampler.  The trace is LOW-CONCURRENCY
# (n_slots=2, queued arrivals): speculative decoding pays off exactly
# when the batch is too small to amortize per-step dispatch — at high
# occupancy the plain engine already amortizes each step over every
# active slot and speculation's extra draft FLOPs only lose.  Three
# sub-stages:
#   * acceptance-friendly: a truncated-layer self-draft made a FAITHFUL
#     predictor by zeroing the residual-branch output projections of
#     the layers above the draft depth — the random-init stand-in for
#     a trained draft/target pair that agrees (draft cost ~1/num_layers
#     of the target per proposed token, acceptance near 1);
#   * adversarial: an injectable 1-layer random-weight ModelDraft that
#     agrees with nothing — the spec_min_accept gate must notice and
#     fall back to plain decode (bounded downside);
#   * prefix-heavy: requests sharing a system-prompt prefix through a
#     PrefixCache twin — warm prompts skip prefill chunks, so TTFT
#     drops at zero contamination (stream sha vs the uncached twin).

SERVE_SPEC_DETAIL_PATH = os.environ.get(
    "HETU_SERVE_SPEC_JSON",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "SERVE_SPEC_FULL.json"))


def run_serve_spec(quick=False, seed=0):
    import jax
    import hetu_tpu as ht
    from hetu_tpu.models import LlamaConfig, LlamaForCausalLM
    from hetu_tpu.serving import InferenceEngine, ModelDraft

    ex, model, c = _serve_build(quick)
    # acceptance-friendly target: zero the residual-branch output
    # projections of every layer ABOVE the draft depth, so the
    # truncated-layer self-draft computes the target function exactly
    # (layers >= 1 become the identity on the residual stream).  At
    # random init a truncated draft agrees with nothing; a trained
    # draft/target pair agrees most of the time — this constructs the
    # agreeing regime deterministically while the plain twin pays the
    # full per-step op count (zeroed weights are not faster on any
    # backend), so the A/B stays fair.
    draft_layers = 1
    for k in list(ex.params):
        for ly in range(draft_layers, c.num_layers):
            if (f"layer{ly}_attn_out" in k) or (f"layer{ly}_mlp_out" in k):
                ex.params[k] = ex.params[k] * 0.0
    # decode-heavy queued trace: long outputs, near-simultaneous
    # arrivals, TWO slots — the latency-bound regime where the plain
    # engine commits ~2 tokens per dispatch; headroom bound is
    # prompt + max_new <= max_len - spec_k
    spec_k = 5
    if quick:
        n_slots, max_len, max_prompt = 2, 128, 12
        page_len, prefill_budget = 8, 24
        trace = _serve_trace(seed, 8, c.vocab_size, 3, 10, 72, 80,
                             mean_gap=0.5)
    else:
        n_slots, max_len, max_prompt = 2, 224, 48
        page_len, prefill_budget = 16, 96
        trace = _serve_trace(seed, 24, c.vocab_size, 8, 32, 96, 128,
                             mean_gap=0.5)
    # pool sized for the prefix sub-stage's higher slot count below
    n_pages = (8 * max_len) // page_len + 1   # + sentinel
    pkw = dict(n_slots=n_slots, max_len=max_len,
               max_prompt_len=max_prompt, prefill_budget=2, paged=True,
               page_len=page_len, n_pages=n_pages,
               prefill_token_budget=prefill_budget, name="serve",
               seed=seed)

    plain = InferenceEngine(ex, model, instance="plain", **pkw)
    # truncated self-draft: same weights, same KV pages, zero extra
    # HBM; with the aligned target above it proposes what verify will
    # emit, so each verify dispatch commits ~k+1 tokens
    spec = InferenceEngine(ex, model, instance="spec", spec_k=spec_k,
                           draft_layers=draft_layers, **pkw)
    # adversarial: an injectable 1-layer ModelDraft with its OWN random
    # weights proposes noise against the same target; the
    # acceptance-EWMA gate must close and fall back to plain decode,
    # probing occasionally for workload shift (sparse probes: each one
    # costs a junk draft+verify round trip)
    jc = LlamaConfig(vocab_size=c.vocab_size, hidden_size=c.hidden_size,
                     num_layers=1, num_heads=c.num_heads,
                     num_kv_heads=c.num_kv_heads,
                     intermediate_size=c.intermediate_size,
                     seq_len=c.seq_len)
    jmodel = LlamaForCausalLM(jc, name="servejunk")
    jids = ht.placeholder_op("servejunk_ids", (1, 4), dtype=np.int32)
    jex = ht.Executor([jmodel(jids)])
    adv = InferenceEngine(ex, model, instance="spec_adv", spec_k=spec_k,
                          draft=ModelDraft(jex, jmodel, name="servejunk"),
                          spec_min_accept=2.0, spec_probe_every=256,
                          **pkw)
    engines = {"plain": plain, "spec": spec, "adversarial": adv}
    for eng in engines.values():
        _serve_replay(eng, trace)       # untimed warm replay
    warm_spec = dict(spec.trace_counts)
    # fair A/B: alternate replays so all three engines see the same
    # instantaneous machine state (same rationale as the paged-vs-slot
    # interleaving in run_serve), keep each engine's best
    results = {}
    for _ in range(3):
        for mode, eng in engines.items():
            r = _serve_replay(eng, trace)
            if (mode not in results or r["tokens_per_sec"]
                    > results[mode]["tokens_per_sec"]):
                results[mode] = r
    spec_flat = spec.trace_counts == warm_spec
    sspec, sadv = spec.stats()["spec"], adv.stats()["spec"]
    pool_b = {m: int(e.cache.k.nbytes) + int(e.cache.v.nbytes)
              for m, e in engines.items()}

    # prefix-heavy sub-stage: every prompt = one shared system prefix
    # (whole pages) + a short unique tail.  Cold prefill needs several
    # chunks at the dropped token budget; a prefix hit skips the shared
    # pages, so warm TTFT is chunks fewer.  Arrivals spread out so the
    # first request's pages are interned before followers arrive.
    if quick:
        pfx_len, n_pfx, tail_lo, tail_hi, pfx_budget = page_len, 12, 2, 4, 4
    else:
        pfx_len, n_pfx = 2 * page_len, 32
        tail_lo, tail_hi, pfx_budget = 2, max_prompt - 2 * page_len, 16
    rng = np.random.default_rng(seed + 2)
    sys_prompt = rng.integers(1, c.vocab_size, (pfx_len,)).astype(np.int32)
    gaps = rng.exponential(3.0, n_pfx)
    arrivals = np.floor(np.cumsum(gaps)).astype(np.int64)
    ptrace = []
    for i in range(n_pfx):
        tail = rng.integers(1, c.vocab_size,
                            (int(rng.integers(tail_lo, tail_hi + 1)),))
        ptrace.append((int(arrivals[i]),
                       np.concatenate([sys_prompt,
                                       tail.astype(np.int32)]),
                       int(rng.integers(4, 9))))
    pfx_kw = dict(pkw, n_slots=8, prefill_token_budget=pfx_budget)
    cold = InferenceEngine(ex, model, instance="noprefix", **pfx_kw)
    warm = InferenceEngine(ex, model, instance="prefix",
                           prefix_cache=True, **pfx_kw)
    for eng in (cold, warm):
        _serve_replay(eng, ptrace)      # untimed warm replay; also
    results["noprefix"] = None          # interns the shared prefix
    results["prefix"] = None
    for _ in range(2):
        for mode, eng in (("noprefix", cold), ("prefix", warm)):
            r = _serve_replay(eng, ptrace)
            if (results[mode] is None or r["latency_s"]["ttft"]["p50"]
                    < results[mode]["latency_s"]["ttft"]["p50"]):
                results[mode] = r
    pstats = warm.prefix_cache.stats()
    warm.prefix_cache.close()

    vs = round(results["spec"]["tokens_per_sec"]
               / results["plain"]["tokens_per_sec"], 3)
    adv_vs = round(results["adversarial"]["tokens_per_sec"]
                   / results["plain"]["tokens_per_sec"], 3)
    ttft_c = results["noprefix"]["latency_s"]["ttft"]["p50"]
    ttft_w = results["prefix"]["latency_s"]["ttft"]["p50"]
    signals = {
        "serve_spec_tokens_per_s": results["spec"]["tokens_per_sec"],
        "serve_spec_plain_tokens_per_s":
            results["plain"]["tokens_per_sec"],
        "spec_acceptance_rate": sspec["acceptance_rate"],
        "prefix_hit_rate": pstats["hit_rate"],
        "serve_prefix_ttft_p50_s": ttft_w,
        "serve_noprefix_ttft_p50_s": ttft_c,
    }
    return {"metric": "serve_spec_tokens_per_s",
            "value": results["spec"]["tokens_per_sec"],
            "unit": "tokens/sec",
            "vs_plain": vs,             # > 1 iff speculation pays
            "spec_wins": bool(vs >= 1.2),
            "spec_k": spec_k,
            "draft_layers": draft_layers,
            "aligned_target": True,     # layers above draft depth zeroed
            "latency_bound_slots": n_slots,
            "acceptance_rate": sspec["acceptance_rate"],
            "accepted_per_step_ewma": sspec["accepted_per_step_ewma"],
            "bitwise_match": bool(
                results["spec"]["stream_sha"]
                == results["plain"]["stream_sha"]),
            "equal_hbm": bool(len(set(pool_b.values())) == 1),
            "pool_bytes": pool_b["plain"],
            "compile_flat": bool(spec_flat),
            "adversarial": {"vs_plain": adv_vs,
                            "bounded": bool(adv_vs >= 1 / 1.05),
                            "gate_closed": bool(
                                sadv["steps"]
                                < results["adversarial"]["decode_steps"]),
                            "acceptance_rate": sadv["acceptance_rate"],
                            "bitwise_match": bool(
                                results["adversarial"]["stream_sha"]
                                == results["plain"]["stream_sha"])},
            "prefix": {"ttft_p50_s": ttft_w,
                       "noprefix_ttft_p50_s": ttft_c,
                       "ttft_reduced": bool(ttft_w < ttft_c),
                       "hits": pstats["hits"],
                       "hit_rate": pstats["hit_rate"],
                       "cow_forks": pstats["cow_forks"],
                       "prefix_len": int(pfx_len),
                       "prefill_token_budget": pfx_budget,
                       "no_contamination": bool(
                           results["prefix"]["stream_sha"]
                           == results["noprefix"]["stream_sha"])},
            "platform": jax.default_backend(),
            "seed": seed, "quick": bool(quick),
            "n_requests": len(trace), "n_prefix_requests": n_pfx,
            "paged": {"n_slots": pkw["n_slots"], "page_len": page_len,
                      "n_pages": n_pages,
                      "prefill_token_budget": prefill_budget},
            "signals": signals,
            "stages": results}


def _emit_serve_spec(out):
    """Same layered emission contract as _emit_serve: full headline +
    SERVE_SPEC_FULL.json (written only after the run has real results),
    flat signals appended to benchmarks/history.jsonl for
    tools/perf_diff.py, compact tail line inside the driver window."""
    from hetu_tpu.telemetry import JsonlWriter
    full = json.dumps(out)
    try:
        with open(SERVE_SPEC_DETAIL_PATH, "w") as f:
            f.write(full + "\n")
    except OSError:
        pass
    if out.get("signals"):
        entry = {"t": round(time.time(), 3), "platform": out["platform"],
                 "quick": out["quick"], "seed": out["seed"],
                 "signals": out["signals"]}
        try:
            os.makedirs(os.path.dirname(HISTORY_PATH) or ".",
                        exist_ok=True)
            with JsonlWriter(HISTORY_PATH) as w:  # append, never truncate
                w.write(entry)
        except OSError:
            pass
    print(full, flush=True)
    compact = {"metric": out["metric"], "value": out["value"],
               "unit": out["unit"], "vs_plain": out["vs_plain"],
               "spec_wins": out["spec_wins"],
               "acceptance_rate": out["acceptance_rate"],
               "bitwise": out["bitwise_match"],
               "equal_hbm": out["equal_hbm"],
               "compile_flat": out["compile_flat"],
               "adversarial": {
                   "vs_plain": out["adversarial"]["vs_plain"],
                   "bounded": out["adversarial"]["bounded"],
                   "gate_closed": out["adversarial"]["gate_closed"]},
               "prefix": {
                   "ttft_p50_s": out["prefix"]["ttft_p50_s"],
                   "noprefix_ttft_p50_s":
                       out["prefix"]["noprefix_ttft_p50_s"],
                   "ttft_reduced": out["prefix"]["ttft_reduced"],
                   "hits": out["prefix"]["hits"],
                   "no_contamination":
                       out["prefix"]["no_contamination"]},
               "detail": os.path.basename(SERVE_SPEC_DETAIL_PATH)}
    if "telemetry_overhead" in out:
        compact["telemetry_overhead_frac"] = \
            out["telemetry_overhead"]["overhead_frac"]
    _print_compact(compact, drop_order=("adversarial",))


# -- sharded serve mode (bench.py --serve --tp N) ---------------------------
# Tensor-parallel serving evidence: the SAME paged engine + arrival
# trace, once over a (replica=1, model=N) mesh and once on a single
# device, at EQUAL TOTAL KV HBM (identical page-pool geometry — the
# sharded pool spreads the same bytes over N chips).  The sha256 stream
# witness must match bitwise: the mesh engine shards weights on output
# dims and gathers activations before every cross-shard reduction, so
# it is a token-stream twin, not an approximation.  On forced-host-CPU
# "devices" the N shards share the same cores, so serve_tp_speedup is
# informational there and only gates on a real TPU mesh.

SERVE_TP_DETAIL_PATH = os.environ.get(
    "HETU_SERVE_TP_JSON",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "SERVE_TP_FULL.json"))


def run_serve_tp(quick=False, tp=2, seed=0):
    import jax
    from hetu_tpu.serving import InferenceEngine, serving_mesh

    # tp must divide num_kv_heads (the KV pool shards over that dim);
    # the default serve geometry covers tp<=2 quick / tp<=4 full, wider
    # meshes bump the KV-head count (both twins share the new config,
    # so the parity witness is still apples-to-apples)
    base_kv = 2 if quick else 4
    ex, model, c = _serve_build(
        quick, kv_heads=None if tp <= base_kv else tp)
    if quick:
        n_slots, max_len, max_prompt = 4, 48, 12
        trace = _serve_trace(seed, 24, c.vocab_size, 3, 12, 4, 16)
        paged_slots, page_len, prefill_budget = 8, 8, 24
    else:
        n_slots, max_len, max_prompt = 8, 160, 48
        trace = _serve_trace(seed, 80, c.vocab_size, 8, 48, 8, 64)
        paged_slots, page_len, prefill_budget = 16, 16, 96
    n_pages = (n_slots * max_len) // page_len + 1   # + sentinel
    kw = dict(n_slots=paged_slots, max_len=max_len,
              max_prompt_len=max_prompt, prefill_budget=2, name="serve",
              seed=seed, paged=True, page_len=page_len, n_pages=n_pages,
              prefill_token_budget=prefill_budget)
    mesh = serving_mesh(tp)
    teng = InferenceEngine(ex, model, instance=f"tp{tp}", mesh=mesh, **kw)
    seng = InferenceEngine(ex, model, instance="tp_single", **kw)

    # untimed warm replay per engine (hits every pow2 prefill bucket the
    # trace can reach), then pin the retrace counters: a flat counter
    # dict across the measured replays is the compile-once witness —
    # and because the mesh engine's program key carries the mesh
    # geometry, the two twins never collide in the shared cache
    _serve_replay(teng, trace)
    _serve_replay(seng, trace)
    warm_t, warm_s = dict(teng.trace_counts), dict(seng.trace_counts)

    # fair A/B: interleave the twins' measured replays (same
    # instantaneous machine state for both) and keep each one's best
    best_t = best_s = None
    for _ in range(3):
        rt = _serve_replay(teng, trace)
        rs = _serve_replay(seng, trace)
        assert rt["stream_sha"] == rs["stream_sha"], \
            "sharded engine diverged from its single-device twin"
        if best_t is None or (rt["tokens_per_sec"]
                              > best_t["tokens_per_sec"]):
            best_t = rt
        if best_s is None or (rs["tokens_per_sec"]
                              > best_s["tokens_per_sec"]):
            best_s = rs

    mstats = teng.stats()["mesh"]
    tb = int(teng.cache.k.nbytes) + int(teng.cache.v.nbytes)
    sb = int(seng.cache.k.nbytes) + int(seng.cache.v.nbytes)
    speedup = round(best_t["tokens_per_sec"] / best_s["tokens_per_sec"],
                    3)
    signals = {
        "serve_tp_tokens_per_s": best_t["tokens_per_sec"],
        "serve_tp_single_tokens_per_s": best_s["tokens_per_sec"],
        "serve_tp_speedup": speedup,
        "serve_tp_kv_per_chip_bytes": mstats["kv_per_chip_bytes"],
    }
    return {"metric": "serve_tp_tokens_per_sec",
            "value": best_t["tokens_per_sec"], "unit": "tokens/sec",
            "vs_baseline": speedup,    # > 1 iff the mesh engine wins
            "tp": tp, "devices": mstats["devices"],
            "platform": jax.default_backend(),
            "seed": seed, "quick": bool(quick),
            "n_requests": len(trace),
            "bitwise_match": bool(
                best_t["stream_sha"] == best_s["stream_sha"]),
            "compile_flat": bool(teng.trace_counts == warm_t
                                 and seng.trace_counts == warm_s),
            "hbm": {"pool_bytes": tb, "single_pool_bytes": sb,
                    "equal_hbm": bool(tb == sb),
                    "kv_per_chip_bytes": mstats["kv_per_chip_bytes"],
                    "param_per_chip_bytes":
                        mstats["param_per_chip_bytes"]},
            "paged": {"n_slots": paged_slots, "page_len": page_len,
                      "n_pages": n_pages,
                      "prefill_token_budget": prefill_budget},
            "signals": signals,
            "stages": {"tp": best_t, "single": best_s}}


def _emit_serve_tp(out):
    """Same layered emission contract as _emit_serve: full headline +
    SERVE_TP_FULL.json written only after the run has real results (the
    no-clobber rule), signals appended to benchmarks/history.jsonl for
    ``tools/perf_diff.py --current SERVE_TP_FULL.json``, compact tail
    line inside the driver's stdout window."""
    from hetu_tpu.telemetry import JsonlWriter
    full = json.dumps(out)
    try:
        with open(SERVE_TP_DETAIL_PATH, "w") as f:
            f.write(full + "\n")
    except OSError:
        pass
    if out.get("signals"):
        entry = {"t": round(time.time(), 3), "platform": out["platform"],
                 "quick": out["quick"], "seed": out["seed"],
                 "signals": out["signals"]}
        try:
            os.makedirs(os.path.dirname(HISTORY_PATH) or ".",
                        exist_ok=True)
            with JsonlWriter(HISTORY_PATH) as w:  # append, never truncate
                w.write(entry)
        except OSError:
            pass
    print(full, flush=True)
    compact = {"metric": out["metric"], "value": out["value"],
               "unit": out["unit"], "tp": out["tp"],
               "speedup": out["vs_baseline"],
               "bitwise": out["bitwise_match"],
               "equal_hbm": out["hbm"]["equal_hbm"],
               "compile_flat": out["compile_flat"],
               "kv_per_chip_B": out["hbm"]["kv_per_chip_bytes"],
               "platform": out["platform"],
               "detail": os.path.basename(SERVE_TP_DETAIL_PATH)}
    _print_compact(compact, drop_order=("kv_per_chip_B",))


# -- quantized serve mode (bench.py --serve --kv-dtype DT) ------------------
# Quantized serving-plane evidence (ISSUE 16): three sub-stages, one per
# transport leg of the shared block codec (hetu_tpu/ops/quant.py).
#   * KV twin: the SAME paged engine + arrival trace, once f32 and once
#     with kv_dtype=DT, at byte-equal page-pool HBM — quantized pages
#     are ~3-5x smaller, so the same byte budget holds MORE pages and
#     reservation-based admission admits more concurrent requests.
#     Streams are no longer bitwise, so the witness is an
#     ERROR-BOUNDED TWIN: a teacher-forced dual-cache probe replays the
#     f32 twin's greedy streams through BOTH pools step by step and
#     reports the per-token max logit divergence (the engine's real
#     compounding path — each quantized step attends to a history that
#     itself went through the codec), plus a task-level equal-quality
#     A/B (fraction of requests whose full greedy stream matches f32).
#   * wire: an in-process PSServer lookup round, raw-f32 vs 'q8' reply
#     codec — measured payload bytes per pull + round-trip error bound.
#   * TP gathers: a tp=2 mesh engine with gather_dtype=DT vs an
#     unsharded f32 reference — greedy stream agreement + analytic
#     all-gather bytes per decode step (3 hidden-width + 1
#     intermediate-width gather per layer, see llama_decode.make_block).

SERVE_QUANT_DETAIL_PATH = os.environ.get(
    "HETU_SERVE_QUANT_JSON",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "SERVE_QUANT_FULL.json"))


def _replay_tokens(engine, trace):
    """Replay a trace and return each request's full token stream (in
    trace order) — the per-request agreement witness the aggregate
    stream sha of _serve_replay can't provide."""
    submitted, it, reqs = 0, 0, []
    while submitted < len(trace) or not engine.scheduler.idle:
        while submitted < len(trace) and trace[submitted][0] <= it:
            _, prompt, max_new = trace[submitted]
            reqs.append(engine.submit(prompt, max_new))
            submitted += 1
        engine.step()
        it += 1
    return [list(r.tokens) for r in reqs]


def _kv_quant_probe(adapter, params, seqs, prompt_lens, page_len,
                    kv_dtype):
    """Teacher-forced dual-cache divergence probe: drive each f32
    greedy stream through a plain f32 page pool AND a quantized one,
    step by step, and compare the decode logits.  Each branch scatters
    its OWN new K/V rows, so the quantized branch compounds codec error
    through positions exactly like the serving engine does.  Returns
    (max_logit_div, relative_div, per_step_greedy_agreement)."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.serving.kv_cache import (QuantizedKVPool, gather_pages,
                                           scatter_rows)

    L, KV, D = adapter.layers, adapter.kv_heads, adapter.head_dim
    n_pages = max(-(-len(s) // page_len) for s in seqs)
    shape = (n_pages, L, KV, page_len, D)
    table = jnp.arange(n_pages)[None]

    @jax.jit
    def dual_step(params, tok, pos, fk, fv, qk, qv):
        lf, nfk, nfv = adapter.decode(
            params, tok[None], pos[None],
            gather_pages(fk, table), gather_pages(fv, table))
        lq, nqk, nqv = adapter.decode(
            params, tok[None], pos[None],
            gather_pages(qk, table), gather_pages(qv, table))
        pages, offs = (pos // page_len)[None], (pos % page_len)[None]

        def row(nc):        # [1, L, KV, T, D] -> the new row [1, L, KV, D]
            return jax.lax.dynamic_slice_in_dim(
                nc, pos, 1, axis=3)[:, :, :, 0]

        fk = scatter_rows(fk, pages, offs, row(nfk))
        fv = scatter_rows(fv, pages, offs, row(nfv))
        qk = scatter_rows(qk, pages, offs, row(nqk))
        qv = scatter_rows(qv, pages, offs, row(nqv))
        div = jnp.max(jnp.abs(lf - lq))
        return (fk, fv, qk, qv, div, jnp.max(jnp.abs(lf)),
                jnp.argmax(lf[0]) == jnp.argmax(lq[0]))

    max_div, max_ref, agree, steps = 0.0, 1e-9, 0, 0
    for seq, p_len in zip(seqs, prompt_lens):
        fk = jnp.zeros(shape, jnp.float32)
        fv = jnp.zeros(shape, jnp.float32)
        qk = QuantizedKVPool.zeros(shape, kv_dtype)
        qv = QuantizedKVPool.zeros(shape, kv_dtype)
        _, pk, pv = adapter.prefill(
            params, jnp.asarray(seq[:p_len], jnp.int32)[None])
        rows_k = jnp.transpose(pk, (2, 0, 1, 3))     # [P, L, KV, D]
        rows_v = jnp.transpose(pv, (2, 0, 1, 3))
        pos = np.arange(p_len)
        pages, offs = pos // page_len, pos % page_len
        fk = scatter_rows(fk, pages, offs, rows_k)
        fv = scatter_rows(fv, pages, offs, rows_v)
        qk = scatter_rows(qk, pages, offs, rows_k)
        qv = scatter_rows(qv, pages, offs, rows_v)
        for i in range(p_len, len(seq)):
            tok = jnp.asarray(seq[i], jnp.int32)
            fk, fv, qk, qv, div, ref, ok = dual_step(
                params, tok, jnp.asarray(i, jnp.int32), fk, fv, qk, qv)
            max_div = max(max_div, float(div))
            max_ref = max(max_ref, float(ref))
            agree += int(ok)
            steps += 1
    return max_div, max_div / max_ref, (agree / steps if steps else 1.0)


def run_serve_quant(quick=False, kv_dtype="int8", seed=0):
    import jax
    from hetu_tpu.ops import quant as _quant
    from hetu_tpu.serving import InferenceEngine

    ex, model, c = _serve_build(quick)
    if quick:
        max_len, max_prompt = 48, 12
        trace = _serve_trace(seed, 24, c.vocab_size, 3, 12, 4, 16)
        page_len, prefill_budget, f32_pages = 8, 24, 13
    else:
        max_len, max_prompt = 160, 48
        trace = _serve_trace(seed, 80, c.vocab_size, 8, 48, 8, 64)
        page_len, prefill_budget, f32_pages = 16, 96, 26
    # f32_pages is deliberately TIGHT (pages, not slots, bind): both
    # twins get one slot per trace request, so admitted concurrency is
    # purely a function of how many pages the byte budget holds
    kw = dict(n_slots=len(trace), max_len=max_len,
              max_prompt_len=max_prompt, prefill_budget=2, name="serve",
              seed=seed, paged=True, page_len=page_len,
              prefill_token_budget=prefill_budget)
    feng = InferenceEngine(ex, model, instance="quant_f32",
                           n_pages=f32_pages, **kw)
    fb = int(feng.cache.k.nbytes) + int(feng.cache.v.nbytes)
    # byte-equal pool HBM: the quantized twin gets as many pages as the
    # f32 twin's byte budget can hold at the quantized per-page cost
    # (codes + the per-row f32 scale overhead both counted)
    D = c.hidden_size // c.num_heads
    cb = _quant.code_bytes_per_element(kv_dtype)
    qpage_bytes = 2 * c.num_layers * c.num_kv_heads * page_len * (
        D * cb + 4)
    q_pages = max(f32_pages, fb // qpage_bytes)
    qeng = InferenceEngine(ex, model, instance=f"quant_{kv_dtype}",
                           n_pages=int(q_pages), kv_dtype=kv_dtype, **kw)
    qb = int(qeng.cache.k.nbytes) + int(qeng.cache.v.nbytes)
    assert qb <= fb, "quantized pool exceeded the byte-equal budget"

    # untimed warm replay per engine, then pin the retrace counters
    _serve_replay(feng, trace)
    _serve_replay(qeng, trace)
    warm_f, warm_q = dict(feng.trace_counts), dict(qeng.trace_counts)
    # task-level equal-quality A/B: per-request greedy stream agreement
    toks_f = _replay_tokens(feng, trace)
    toks_q = _replay_tokens(qeng, trace)
    stream_agree = (sum(a == b for a, b in zip(toks_f, toks_q))
                    / max(1, len(toks_f)))
    # fair A/B: interleave the twins' measured replays, keep each best
    best_f = best_q = None
    for _ in range(3):
        rf = _serve_replay(feng, trace)
        rq = _serve_replay(qeng, trace)
        if best_f is None or (rf["tokens_per_sec"]
                              > best_f["tokens_per_sec"]):
            best_f = rf
        if best_q is None or (rq["tokens_per_sec"]
                              > best_q["tokens_per_sec"]):
            best_q = rq

    # error-bounded-twin probe over the f32 twin's first streams
    n_probe = 3 if quick else 4
    seqs = [list(np.asarray(trace[i][1])) + toks_f[i]
            for i in range(n_probe)]
    p_lens = [len(trace[i][1]) for i in range(n_probe)]
    max_div, rel_div, step_agree = _kv_quant_probe(
        qeng.adapter, qeng.params, seqs, p_lens, page_len, kv_dtype)

    # -- wire leg: measured lookup-reply bytes, f4 vs q8 codec ----------
    wire = _wire_quant_stage(quick, seed)

    # -- TP-gather leg: quantized all-gathers vs unsharded reference ----
    tp_out = _tp_quant_stage(ex, model, c, kw, kv_dtype, quick, seed)

    conc_x = round(best_q["peak_active"] / max(1, best_f["peak_active"]),
                   3)
    signals = {
        "serve_quant_tokens_per_s": best_q["tokens_per_sec"],
        "serve_quant_f32_tokens_per_s": best_f["tokens_per_sec"],
        "serve_quant_peak_concurrency": best_q["peak_active"],
        "serve_quant_f32_peak_concurrency": best_f["peak_active"],
        "kv_quant_concurrency_x": conc_x,
        "kv_quant_hbm_bytes_per_token": round(
            qb / max(1, best_q["peak_live_tokens"]), 1),
        "kv_quant_max_logit_div": round(max_div, 6),
        "kv_quant_greedy_attainment": round(stream_agree, 4),
        "wire_bytes_per_pull": wire["q8_bytes_per_pull"],
        "tp_gather_bytes_per_step":
            tp_out["quant_gather_bytes_per_step"],
    }
    return {"metric": "serve_quant_peak_concurrency",
            "value": best_q["peak_active"], "unit": "requests",
            "vs_baseline": conc_x,   # > 1 iff quantization buys capacity
            "kv_dtype": kv_dtype,
            "platform": jax.default_backend(),
            "seed": seed, "quick": bool(quick),
            "n_requests": len(trace),
            "paged": {"page_len": page_len, "f32_pages": f32_pages,
                      "quant_pages": int(q_pages),
                      "prefill_token_budget": prefill_budget},
            "hbm": {"f32_pool_bytes": fb, "quant_pool_bytes": qb,
                    "equal_hbm_budget": bool(qb <= fb),
                    "pool_bytes_ratio": round(qb / fb, 4)},
            "divergence": {"max_logit_div": round(max_div, 6),
                           "relative_div": round(rel_div, 6),
                           "probe_step_agreement": round(step_agree, 4),
                           "stream_agreement": round(stream_agree, 4),
                           "probe_sequences": n_probe},
            "compile_flat": bool(feng.trace_counts == warm_f
                                 and qeng.trace_counts == warm_q),
            "wire": wire, "tp": tp_out,
            "signals": signals,
            "stages": {"quant": best_q, "f32": best_f}}


def _wire_quant_stage(quick, seed):
    """In-process PSServer lookup round: measured reply payload bytes
    for the raw-f32 wire vs the negotiated q8 codec, plus the
    round-trip error bound check (half an int8 step per row absmax)."""
    import socket as _socket
    import threading
    from hetu_tpu.ps.rpc import (PSServer, RemoteTable, recv_msg,
                                 send_msg)
    from hetu_tpu.ps.store import EmbeddingTable

    rows, dim, n_keys = (4096, 16, 256) if quick else (65536, 64, 1024)
    table = EmbeddingTable(rows, dim, seed=seed)
    server = PSServer(table, port=0)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    keys = np.arange(n_keys, dtype="<i8")

    def pull(codec):
        s = _socket.create_connection((server.host, server.port),
                                      timeout=30)
        try:
            hdr = {"verb": "lookup"}
            if codec:
                hdr["codec"] = codec
            send_msg(s, hdr, keys)
            reply, payloads = recv_msg(s)
            assert reply.get("verb") == "ok", reply
            return sum(len(p) for p in payloads)
        finally:
            s.close()

    f4_bytes, q8_bytes = pull(None), pull("q8")
    # parity through the real client path
    rt_f = RemoteTable(server.host, server.port)
    rt_q = RemoteTable(server.host, server.port, codec="q8")
    rows_f, rows_q = rt_f.lookup(keys), rt_q.lookup(keys)
    bound = np.abs(rows_f).max(axis=1, keepdims=True) / 127 * 0.5 + 1e-7
    err = float(np.abs(rows_q - rows_f).max())
    within = bool((np.abs(rows_q - rows_f) <= bound).all())
    rt_f.close()
    rt_q.close()
    server.stop()
    return {"n_keys": n_keys, "dim": dim,
            "f4_bytes_per_pull": f4_bytes,
            "q8_bytes_per_pull": q8_bytes,
            "bytes_ratio": round(q8_bytes / f4_bytes, 4),
            "max_roundtrip_err": round(err, 6),
            "within_bound": within}


def _tp_quant_stage(ex, model, c, kw, kv_dtype, quick, seed):
    """tp=2 mesh engine with quantized gathers vs an unsharded f32
    reference on a short trace: greedy stream agreement + analytic
    gather bytes per decode step per slot (3 hidden-width + 1
    intermediate-width gather per layer)."""
    import jax
    from hetu_tpu.ops import quant as _quant
    from hetu_tpu.serving import InferenceEngine, serving_mesh

    tp = 2
    if len(jax.devices()) < tp:
        return {"skipped": f"needs {tp} devices",
                "quant_gather_bytes_per_step": 0,
                "f32_gather_bytes_per_step": 0}
    ttrace = _serve_trace(seed + 2, 8 if quick else 16, c.vocab_size,
                          3, 10, 4, 8)
    tkw = dict(kw, n_slots=4,
               n_pages=(4 * kw["max_len"]) // kw["page_len"] + 1)
    teng = InferenceEngine(ex, model, instance=f"tp{tp}_g{kv_dtype}",
                           mesh=serving_mesh(tp), gather_dtype=kv_dtype,
                           **tkw)
    seng = InferenceEngine(ex, model, instance="tp_quant_ref", **tkw)
    toks_t = _replay_tokens(teng, ttrace)
    toks_s = _replay_tokens(seng, ttrace)
    agree = (sum(a == b for a, b in zip(toks_t, toks_s))
             / max(1, len(toks_t)))
    cb = _quant.code_bytes_per_element(kv_dtype)
    H, I, L = c.hidden_size, c.intermediate_size, c.num_layers

    def blocks(d):      # scales per gathered activation (make_gather)
        return tp if d % tp == 0 else 1

    f32_b = L * (3 * H + I) * 4
    q_b = L * (3 * (H * cb + blocks(H) * 4) + (I * cb + blocks(I) * 4))
    return {"tp": tp, "n_requests": len(ttrace),
            "stream_agreement": round(agree, 4),
            "f32_gather_bytes_per_step": f32_b,
            "quant_gather_bytes_per_step": q_b,
            "gather_bytes_ratio": round(q_b / f32_b, 4)}


def _emit_serve_quant(out):
    """Same layered emission contract as _emit_serve_tp: full headline
    + SERVE_QUANT_FULL.json written only after the run has real results
    (the no-clobber rule), signals appended to benchmarks/history.jsonl
    for ``tools/perf_diff.py --current SERVE_QUANT_FULL.json``, compact
    tail line inside the driver's stdout window."""
    from hetu_tpu.telemetry import JsonlWriter
    full = json.dumps(out)
    try:
        with open(SERVE_QUANT_DETAIL_PATH, "w") as f:
            f.write(full + "\n")
    except OSError:
        pass
    if out.get("signals"):
        entry = {"t": round(time.time(), 3), "platform": out["platform"],
                 "quick": out["quick"], "seed": out["seed"],
                 "signals": out["signals"]}
        try:
            os.makedirs(os.path.dirname(HISTORY_PATH) or ".",
                        exist_ok=True)
            with JsonlWriter(HISTORY_PATH) as w:  # append, never truncate
                w.write(entry)
        except OSError:
            pass
    print(full, flush=True)
    sg = out["signals"]
    compact = {"metric": out["metric"], "value": out["value"],
               "unit": out["unit"], "kv_dtype": out["kv_dtype"],
               "conc": [sg["serve_quant_peak_concurrency"],
                        sg["serve_quant_f32_peak_concurrency"]],
               "conc_x": sg["kv_quant_concurrency_x"],
               "kv_B_per_tok": sg["kv_quant_hbm_bytes_per_token"],
               "logit_div": sg["kv_quant_max_logit_div"],
               "greedy_attain": sg["kv_quant_greedy_attainment"],
               "wire_B_per_pull": [sg["wire_bytes_per_pull"],
                                   out["wire"]["f4_bytes_per_pull"]],
               "tp_gather_B": [sg["tp_gather_bytes_per_step"],
                               out["tp"].get(
                                   "f32_gather_bytes_per_step", 0)],
               "pool_ratio": out["hbm"]["pool_bytes_ratio"],
               "compile_flat": out["compile_flat"],
               "platform": out["platform"],
               "detail": os.path.basename(SERVE_QUANT_DETAIL_PATH)}
    _print_compact(compact, drop_order=("tp_gather_B", "pool_ratio"))


# -- serve-migrate mode (bench.py --serve --fleet --migrate) ---------------
# Live KV page migration evidence (ROADMAP direction 2, the
# disaggregation half): a mid-decode request's refcounted pages move to
# a sibling replica as a CRC32-framed blob (serving/kv_transfer.py) and
# the stream continues BITWISE where it left off.  Three stages:
#
# * ab          — the handoff A/B: snapshot -> splice -> ack on a live
#                 request at T generated tokens, timed against the
#                 teacher-forced replay rebuild of the same stream on an
#                 identical sibling.  migrate_vs_replay_speedup is the
#                 headline (perf_diff gates it one-sided at 1.0: live
#                 migration must never be slower than the PR 12 replay
#                 oracle it falls back to), migrate_bytes_per_token the
#                 static wire-cost signal.
# * drain       — scale-down A/B on a manual fleet: drain(migrate=True)
#                 moves the decode tail NOW vs drain(migrate=False)
#                 waiting it out; both parity-checked against an
#                 uninterrupted oracle.
# * failover    — crash the warm replica of a prefix-cached pair: live
#                 streams re-home by PAGE MIGRATION (not replay), the
#                 quarantined replica's interned prefixes re-install on
#                 the survivor, and the warm prompt still hits.
#
# Detail -> MIGRATE_FULL.json under the BENCH_FULL no-clobber contract;
# signals append to benchmarks/history.jsonl for tools/perf_diff.py.

SERVE_MIGRATE_DETAIL_PATH = os.environ.get(
    "HETU_MIGRATE_JSON",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "MIGRATE_FULL.json"))

#: paged engines only — page migration is a block-table splice
_MIG_EKW = dict(n_slots=4, max_len=32, max_prompt_len=8, name="serve",
                paged=True, page_len=4)


def _migrate_prompts(rng, n, vocab, lo=3, hi=8):
    return [rng.integers(1, vocab, (int(L),)).astype(np.int32)
            for L in rng.integers(lo, hi, n)]


def _migrate_ab_stage(ex, model, c, quick, seed):
    """Handoff A/B (see section comment): median over n_probe live
    requests, each decoded to T tokens on a donor, then (a) page-
    migrated and (b) replay-rebuilt onto identical siblings; both
    continuations must finish bitwise equal to the uninterrupted
    oracle."""
    from hetu_tpu.serving import InferenceEngine
    from hetu_tpu.serving import kv_transfer as kvt

    rng = np.random.default_rng(seed)
    n_probe = 4 if quick else 8
    T = 6 if quick else 12
    max_new = T + 6
    prompts = _migrate_prompts(rng, n_probe, c.vocab_size)
    oracle_eng = InferenceEngine(ex, model, instance="mig.oracle",
                                 **_MIG_EKW)
    oracle = oracle_eng.generate_many(prompts, max_new)
    oracle_eng.close()

    donor = InferenceEngine(ex, model, instance="mig.donor", **_MIG_EKW)
    recv_m = InferenceEngine(ex, model, instance="mig.recv", **_MIG_EKW)
    recv_r = InferenceEngine(ex, model, instance="mig.replay",
                             **_MIG_EKW)
    mig_t, rep_t, blob_b, tok_cov = [], [], [], []
    parity = True
    try:
        for i, p in enumerate(prompts):
            req = donor.submit(p, max_new)
            while len(req.tokens) < T:
                donor.step()
            # live path: serialize -> CRC frame -> splice -> ack
            t0 = time.perf_counter()
            blob = kvt.snapshot_request(donor, req)
            adopted = kvt.resume_request(recv_m, blob)
            mig_t.append(time.perf_counter() - t0)
            donor.release_migrated(req.rid)
            blob_b.append(len(blob))
            tok_cov.append(len(p) + len(req.tokens))
            # replay path: re-prefill + teacher-force the same stream
            replay = np.asarray(req.tokens, np.int32)
            t0 = time.perf_counter()
            rr = recv_r.submit(p, max_new, replay=replay)
            while len(rr.tokens) < len(replay):
                recv_r.step()
            rep_t.append(time.perf_counter() - t0)
            recv_m.run(max_iterations=300)
            recv_r.run(max_iterations=300)
            parity = (parity
                      and np.array_equal(adopted.result(), oracle[i])
                      and np.array_equal(rr.result(), oracle[i]))
    finally:
        for e in (donor, recv_m, recv_r):
            e.close()
    med_m, med_r = float(np.median(mig_t)), float(np.median(rep_t))
    return {"n_probe": n_probe, "tokens_at_handoff": T,
            "migrate_ms_median": round(med_m * 1e3, 3),
            "replay_ms_median": round(med_r * 1e3, 3),
            "speedup": round(med_r / max(med_m, 1e-9), 3),
            "blob_bytes_mean": int(np.mean(blob_b)),
            "bytes_per_token": round(
                float(np.sum(blob_b)) / max(1, sum(tok_cov)), 1),
            "bitwise_parity": bool(parity)}


def _migrate_drain_stage(ex, model, c, quick, seed):
    """Scale-down A/B: two identical manual fleets mid-decode; one
    drains its busiest replica with migrate=True (tail moves NOW), the
    twin waits the tail out.  Both runs' streams must match the
    uninterrupted oracle."""
    import warnings
    from hetu_tpu.serving import EngineFleet, InferenceEngine

    rng = np.random.default_rng(seed + 7)
    # fewer requests than one replica's slots: the survivor must have
    # FREE slots to adopt into (adoption cannot queue the way replay
    # can), so a full fleet would silently fall back to waiting
    n_req = 3 if quick else 4
    max_new = 24    # a long decode tail: what migrate-then-drain skips
    prompts = _migrate_prompts(rng, n_req, c.vocab_size)
    oracle_eng = InferenceEngine(ex, model, instance="mig.drain.oracle",
                                 **_MIG_EKW)
    oracle = oracle_eng.generate_many(prompts, max_new)
    oracle_eng.close()

    def episode(migrate):
        fleet = EngineFleet(ex, model, n_engines=2,
                            engine_kwargs=_MIG_EKW, threaded=False,
                            name=f"migdrain{int(migrate)}")
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            reqs = [fleet.submit(p, max_new) for p in prompts]
            fleet.pump(3)
            busy = max(fleet._replicas, key=lambda r: len(r.inflight))
            held = len(busy.inflight)
            t0 = time.perf_counter()
            fleet.drain(busy.name, wait=True, migrate=migrate)
            dt = time.perf_counter() - t0
            fleet.wait(reqs, timeout=120)
        s = fleet.stats()
        par = all(np.array_equal(r.result(), o)
                  for r, o in zip(reqs, oracle))
        audits = fleet.audit()
        balanced = all(a["allocs"] == a["frees"] and a["in_use"] == 0
                       for a in audits.values())
        fleet.stop()
        return {"drain_s": round(dt, 4), "held_at_drain": held,
                "migrations": s["migrations"],
                "bitwise_parity": bool(par),
                "slot_audit_balanced": bool(balanced)}

    mig, wait = episode(True), episode(False)
    # a time RATIO, not a gated speedup: on the quick CPU shapes the
    # waited-out tail is single-digit milliseconds, too close to the
    # handoff cost to gate — trend context (perf_diff 'info')
    return {"migrate": mig, "wait": wait,
            "drain_time_ratio": round(
                wait["drain_s"] / max(mig["drain_s"], 1e-9), 3)}


def _migrate_failover_stage(ex, model, c, quick, seed):
    """Crash the warm replica of a prefix-cached pair mid-decode: live
    requests re-home by page migration (stats show migrations, not just
    replays), the victim's interned prefixes re-install on the
    survivor, and the shared warm prompt still prefix-hits there."""
    import warnings
    from hetu_tpu.resilience import faults
    from hetu_tpu.serving import EngineFleet, InferenceEngine

    rng = np.random.default_rng(seed + 13)
    max_new = 10
    warm = np.arange(1, 9, dtype=np.int32)      # two full pages
    prompts = _migrate_prompts(rng, 3 if quick else 5, c.vocab_size)
    ekw = dict(_MIG_EKW, prefix_cache=True)
    oracle_eng = InferenceEngine(ex, model,
                                 instance="mig.fo.oracle", **ekw)
    oracle = oracle_eng.generate_many([warm] + prompts, max_new)
    oracle_eng.close()

    fleet = EngineFleet(ex, model, n_engines=2, engine_kwargs=ekw,
                        threaded=False, breaker_base=1e-4,
                        name="migfo")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        # warm the prefix cache on whichever replica takes the warm rid
        wreq = fleet.submit(warm, max_new)
        fleet.wait([wreq], timeout=60)
        victim = fleet._by_name(wreq.engine)
        reqs = [fleet.submit(p, max_new) for p in prompts]
        fleet.pump(3)
        faults.crash_engine(victim.engine)
        fleet.wait(reqs, timeout=120)
    s = fleet.stats()
    survivor = next(r for r in fleet._replicas if r is not victim)
    hit = 0
    if survivor.engine is not None \
            and survivor.engine.prefix_cache is not None:
        hit = int(survivor.engine.prefix_cache.hit_tokens(warm))
    par = all(np.array_equal(r.result(), o)
              for r, o in zip([wreq] + reqs, oracle))
    fleet.stop()
    return {"migrations": s["migrations"],
            "migration_failures": s["migration_failures"],
            "prefix_handoffs": s["prefix_handoffs"],
            "failovers": s["failovers"],
            "warm_prefix_hit_tokens": hit,
            "warm_prefix_len": int(warm.size),
            "prefix_hit_rate_after_crash": round(
                hit / float(warm.size), 4),
            "bitwise_parity": bool(par)}


def run_serve_migrate(quick=False, seed=0):
    import jax

    ex, model, c = _serve_build(quick)
    ab = _migrate_ab_stage(ex, model, c, quick, seed)
    drain = _migrate_drain_stage(ex, model, c, quick, seed)
    failover = _migrate_failover_stage(ex, model, c, quick, seed)
    signals = {
        "migrate_vs_replay_speedup": ab["speedup"],
        "migrate_bytes_per_token": ab["bytes_per_token"],
        "migrate_drain_time_ratio": drain["drain_time_ratio"],
        "migrate_prefix_hit_rate": failover[
            "prefix_hit_rate_after_crash"],
    }
    parity = bool(ab["bitwise_parity"]
                  and drain["migrate"]["bitwise_parity"]
                  and drain["wait"]["bitwise_parity"]
                  and failover["bitwise_parity"])
    return {"metric": "migrate_vs_replay_speedup",
            "value": ab["speedup"], "unit": "x",
            "vs_baseline": ab["speedup"],  # replay IS the baseline
            "platform": jax.default_backend(),
            "seed": seed, "quick": bool(quick),
            "bitwise_parity": parity,
            "stages": {"ab": ab, "drain": drain,
                       "failover": failover},
            "signals": signals}


def _emit_serve_migrate(out):
    """Layered emission (same contract as _emit_serve_quant): full
    headline + MIGRATE_FULL.json after real results, signals appended
    to benchmarks/history.jsonl, compact tail line."""
    from hetu_tpu.telemetry import JsonlWriter
    full = json.dumps(out)
    try:
        with open(SERVE_MIGRATE_DETAIL_PATH, "w") as f:
            f.write(full + "\n")
    except OSError:
        pass
    if out.get("signals"):
        entry = {"t": round(time.time(), 3), "platform": out["platform"],
                 "quick": out["quick"], "seed": out["seed"],
                 "signals": out["signals"]}
        try:
            os.makedirs(os.path.dirname(HISTORY_PATH) or ".",
                        exist_ok=True)
            with JsonlWriter(HISTORY_PATH) as w:  # append, never truncate
                w.write(entry)
        except OSError:
            pass
    print(full, flush=True)
    sg = out["signals"]
    ab = out["stages"]["ab"]
    compact = {"metric": out["metric"], "value": out["value"],
               "unit": out["unit"],
               "migrate_ms": ab["migrate_ms_median"],
               "replay_ms": ab["replay_ms_median"],
               "B_per_tok": sg["migrate_bytes_per_token"],
               "drain_x": sg["migrate_drain_time_ratio"],
               "prefix_hit": sg["migrate_prefix_hit_rate"],
               "bitwise": out["bitwise_parity"],
               "platform": out["platform"],
               "detail": os.path.basename(SERVE_MIGRATE_DETAIL_PATH)}
    _print_compact(compact, drop_order=("prefix_hit", "drain_x"))


# -- embedding-serve mode (bench.py --serve-embed) -------------------------
# Tiered-embedding serving evidence (ROADMAP direction 5): replay one
# seeded Zipfian key trace (Criteo-shaped skew) through the
# EmbeddingServer's device hot-row cache and through an UNCACHED
# host-tier twin that gathers every batch's rows from host RAM — the
# DLRM-inference bottleneck path ("Dissecting Embedding Bag
# Performance", PAPERS.md).  Host-table update churn runs during the
# replay so the staleness machinery is exercised, and the bitwise
# parity witness (staleness bound 0: served rows == host table rows,
# exactly) is asserted mid-flight.  Reported: rows/s cached vs
# uncached, device hit rate, p50/p99 lookup latency per tier, parity,
# compile-once.  Detail -> EMBED_FULL.json under the BENCH_FULL
# no-clobber contract.

EMBED_DETAIL_PATH = os.environ.get(
    "HETU_EMBED_JSON",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "EMBED_FULL.json"))


def _embed_build(quick):
    """WDL scorer + PS cold tier sized for the platform; name-seeded
    init (deterministic) — serving perf does not depend on trained
    weights."""
    import hetu_tpu as ht
    from hetu_tpu.models.ctr import WDL
    from hetu_tpu.ps import CacheSparseTable

    if quick:
        rows, dim, F, nd, hidden = 4096, 16, 8, 4, (32, 32)
    else:
        rows, dim, F, nd, hidden = 131072, 16, 26, 13, (256, 256)
    model = WDL(rows, embedding_dim=dim, num_sparse=F, num_dense=nd,
                hidden=hidden, name="embsrv")
    dense_ph = ht.placeholder_op("embsrv_dense", (1, nd))
    ids_ph = ht.placeholder_op("embsrv_ids", (1, F), dtype=np.int32)
    ex = ht.Executor([model(dense_ph, ids_ph)])
    # cold tier: the HET-cached PS host table (pull_bound=0 so the
    # device tier's staleness bound is exact); seeded from the model's
    # in-graph table so both serving paths read identical bytes
    cst = CacheSparseTable(rows, dim, cache_limit=rows // 4,
                           pull_bound=0, optimizer="sgd", lr=0.1,
                           name="embed_bench")
    cst.table.set_rows(np.arange(rows),
                       model.emb.host_table(ex.params))
    return ex, model, cst, rows, F, nd


def _embed_trace(seed, n_requests, rows, num_sparse, num_dense,
                 alpha=1.2, mean_gap=0.4):
    """Seeded open-loop arrival trace with Criteo-shaped key skew:
    bounded-Zipf ids over a seeded key permutation (so the hot set is
    not ids 0..k), dense features standard normal, Poisson-process
    arrivals measured in scheduler iterations."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, rows + 1, dtype=np.float64)
    p = ranks ** -float(alpha)
    p /= p.sum()
    perm = rng.permutation(rows)
    gaps = rng.exponential(mean_gap, n_requests)
    arrivals = np.floor(np.cumsum(gaps)).astype(np.int64)
    trace = []
    for i in range(n_requests):
        ids = perm[rng.choice(rows, size=num_sparse, p=p)].astype(
            np.int32)
        dense = rng.standard_normal(num_dense).astype(np.float32)
        trace.append((int(arrivals[i]), ids, dense))
    return trace


def _embed_replay(server, trace, cst, update_every=0, update_seed=1,
                  parity_every=0):
    """Drive one server through the trace (arrival clock = iteration
    index), interleaving host-table update churn and — for the cached
    server — the bitwise parity witness."""
    from hetu_tpu.metrics import percentile, request_latency_summary
    from hetu_tpu.resilience import faults

    urng = np.random.default_rng(update_seed)
    server.reset_stats()
    if server.hot is not None:
        server.hot.reset_stats()
    parity, parity_checks = True, 0
    t0 = time.perf_counter()
    submitted, it, reqs = 0, 0, []
    while submitted < len(trace) or not server.scheduler.idle:
        while submitted < len(trace) and trace[submitted][0] <= it:
            _, ids, dense = trace[submitted]
            reqs.append(server.submit(ids, dense=dense))
            submitted += 1
        server.step()
        it += 1
        if update_every and it % update_every == 0:
            # churn: update rows the trace just touched, so cached
            # copies go stale under load (the staleness bound must
            # force refreshes, not serve old bytes)
            hot_ids = trace[max(0, submitted - 1)][1]
            faults.stale_rows(cst, urng.choice(hot_ids, 4))
        if (parity_every and server.hot is not None and submitted
                and it % parity_every == 0):
            keys = trace[max(0, submitted - 2)][1]
            served = server.hot.gather_host(keys)
            parity = parity and np.array_equal(
                served, server.host.lookup(keys))
            parity_checks += 1
    wall = time.perf_counter() - t0
    assert all(r.finished for r in reqs), "replay left unfinished requests"
    scored = sum(1 for r in reqs if r.finish_reason == "scored")
    rows_served = scored * server.num_sparse
    lat = request_latency_summary(server.records)

    def pct(vals):
        return {"p50": round(percentile(vals, 50), 9),
                "p99": round(percentile(vals, 99), 9),
                "mean": round(float(np.mean(vals)), 9) if vals else None}

    out = {"rows_per_sec": round(rows_served / wall, 1),
           "requests_per_sec": round(scored / wall, 1),
           "total_requests": len(reqs),
           "requests_scored": scored,
           "wall_s": round(wall, 3),
           "iterations": it,
           "lookup_s": pct(server.lookup_seconds),
           "score_s": pct(server.score_seconds),
           "latency_s": {k: {q: (round(x, 9)
                                 if isinstance(x, float) else x)
                             for q, x in v.items()}
                         for k, v in lat.items()},
           "trace_counts": server.trace_counts}
    if server.hot is not None:
        out["hot_cache"] = server.hot.stats()
        out["parity_staleness0"] = bool(parity)
        out["parity_checks"] = parity_checks
    return out


def run_serve_embed(quick=False, seed=0):
    import jax
    from hetu_tpu.serving import EmbeddingServer

    ex, model, cst, rows, F, nd = _embed_build(quick)
    if quick:
        n_slots, cache_rows, n_requests = 8, 1024, 160
        update_every, parity_every = 6, 5
    else:
        n_slots, cache_rows, n_requests = 16, 16384, 1500
        update_every, parity_every = 6, 10
    trace = _embed_trace(seed, n_requests, rows, F, nd)
    kw = dict(host_table=cst, own_host_table=False, n_slots=n_slots,
              staleness_bound=0)
    results = {}
    try:
        for mode, crows in (("cached", cache_rows), ("uncached", None)):
            srv = EmbeddingServer(ex, model, cache_rows=crows,
                                  name=mode, instance=mode, **kw)
            # warm the scoring program outside the timed replay; the
            # trace counters keep counting, so a retrace DURING the
            # replay still shows up as trace_counts > 1
            srv.score_many([trace[0][1]], [trace[0][2]])
            if srv.hot is not None:
                # warm every power-of-two scatter bucket the replay can
                # hit (fetch batches are <= n_slots * F unique rows) so
                # no scatter compile lands inside the timed window
                m = n_slots * F
                b = 8
                while b <= m:
                    srv.hot.lookup_slots(
                        np.arange(rows - b, rows, dtype=np.int64))
                    b *= 2
            results[mode] = _embed_replay(
                srv, trace, cst, update_every=update_every,
                update_seed=seed + 1, parity_every=parity_every)
            srv.close()
        ps_perf = cst.perf()
    finally:
        cst.close()
    cached, uncached = results["cached"], results["uncached"]
    vs = round(cached["rows_per_sec"]
               / max(uncached["rows_per_sec"], 1e-9), 3)
    note = None
    if jax.default_backend() == "cpu":
        # on CPU "device" memory IS host memory: the uncached twin's
        # gather pays no H2D transfer, so the hot tier only shows its
        # bookkeeping cost here.  The win this bench exists to measure
        # (skipping the host->HBM row stream) needs the TPU round —
        # same caveat as every CPU-quick number (ROADMAP bench debt).
        note = "cpu_twin_pays_no_h2d"
    return {"metric": "embed_serve_rows_per_sec",
            **({"platform_note": note} if note else {}),
            "value": cached["rows_per_sec"], "unit": "rows/sec",
            "vs_uncached": vs,       # > 1 iff the hot tier pays off
            "cached_wins": bool(vs > 1.0),
            "hit_rate": cached["hot_cache"]["hit_rate"],
            "parity_staleness0": cached["parity_staleness0"],
            "compile_once": bool(
                cached["trace_counts"].get("cached") == 1
                and uncached["trace_counts"].get("direct") == 1),
            "platform": jax.default_backend(),
            "seed": seed, "quick": bool(quick),
            "n_requests": len(trace), "n_slots": n_slots,
            "table_rows": rows, "cache_rows": cache_rows,
            "num_sparse": F,
            "ps_cache_perf": {k: (round(v, 4) if isinstance(v, float)
                                  else v) for k, v in ps_perf.items()},
            "stages": results}


def _emit_embed(out):
    """Embedding-serve evidence in the same layered shape as --serve:
    full headline to an early line + EMBED_FULL.json, compact tail line
    that fits the driver's stdout window.  The detail file is written
    only now — after the run has real results — so an aborted run never
    clobbers the previous round's committed evidence (the
    BENCH_FULL.json contract)."""
    full = json.dumps(out)
    try:
        with open(EMBED_DETAIL_PATH, "w") as f:
            f.write(full + "\n")
    except OSError:
        pass
    print(full, flush=True)
    compact = {"metric": out["metric"], "value": out["value"],
               "unit": out["unit"], "vs_uncached": out["vs_uncached"],
               "cached_wins": out["cached_wins"],
               "hit_rate": out["hit_rate"],
               "parity_staleness0": out["parity_staleness0"],
               "compile_once": out["compile_once"],
               "lookup_p50_s": {
                   "cached": out["stages"]["cached"]["lookup_s"]["p50"],
                   "uncached":
                       out["stages"]["uncached"]["lookup_s"]["p50"]},
               "lookup_p99_s": {
                   "cached": out["stages"]["cached"]["lookup_s"]["p99"],
                   "uncached":
                       out["stages"]["uncached"]["lookup_s"]["p99"]},
               "detail": os.path.basename(EMBED_DETAIL_PATH)}
    if "telemetry_overhead" in out:
        compact["telemetry_overhead_frac"] = \
            out["telemetry_overhead"]["overhead_frac"]
    _print_compact(compact)


# -- profile mode (bench.py --profile) -------------------------------------
# Performance introspection evidence (ISSUE 10): capture XLA
# cost/memory for every compiled program the system owns (W&D train
# step, serving prefill/decode pair, embedding scoring program),
# attribute flops to model layers, derive MFU/roofline/throughput
# signals against the chip peak table, snapshot the HBM live-buffer
# ledger per stage, and append the flattened signal dict to
# benchmarks/history.jsonl — the feed for tools/perf_diff.py.

PROFILE_DETAIL_PATH = os.environ.get(
    "HETU_PROFILE_JSON",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "PROFILE_FULL.json"))

HISTORY_PATH = os.environ.get(
    "HETU_PERF_HISTORY",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "benchmarks", "history.jsonl"))


def _profile_train(prof, led, quick, seed, slowdown):
    """Train-step stage: capture + layer attribution on the chaos W&D
    workload, then a measured window for MFU/steps-per-sec."""
    B = 32
    ex, batch = _chaos_build("prof", B=B)
    try:
        ex.run("train", feed_dict=batch(0),
               convert_to_numpy_ret_vals=True)     # compile outside
        sub = ex.subexecutor["train"]
        feed = batch(0)
        prof.capture("train_step", sub.lower_compiled(), kind="train",
                     eval_nodes=sub.eval_nodes,
                     feed_shapes={n.name: v.shape
                                  for n, v in feed.items()})
        steps = 8 if quick else 40
        t0 = time.perf_counter()
        for i in range(steps):
            ex.run("train", feed_dict=batch(i + 1))
            if slowdown:
                time.sleep(slowdown)
        ex.run("train", feed_dict=batch(0),
               convert_to_numpy_ret_vals=True)     # sync the window
        elapsed = time.perf_counter() - t0
        import jax
        p = prof.observe("train_step", steps=steps + 1,
                         elapsed_s=elapsed, tokens=(steps + 1) * B,
                         items_name="examples",
                         n_chips=jax.device_count())
        return {"derived": p["derived"], "layers": p["layers"],
                "memory": p["memory"], "hbm": led.snapshot()}
    finally:
        ex.close()


def _profile_serve(prof, led, quick, seed):
    """Serving stage: replay a short arrival trace, then capture the
    prefill/decode pair AFTER the replay (AOT lowering re-traces the
    shared callables, so capture must stay outside any compile-once
    window) and fold the measured decode window in."""
    import jax
    from hetu_tpu.serving import InferenceEngine
    ex, model, c = _serve_build(quick)
    n = 12 if quick else 48
    trace = _serve_trace(seed, n, c.vocab_size, 3, 10, 4, 12)
    eng = InferenceEngine(ex, model, n_slots=4, max_len=48,
                          max_prompt_len=12, name="serve", seed=seed,
                          instance="prof")
    try:
        eng.generate_many([trace[0][1]], 2)        # warm the programs
        replay = _serve_replay(eng, trace)
        cp = eng.cost_programs()
        prof.capture("serve_prefill", cp["prefill"], kind="serve")
        prof.capture("serve_decode", cp["decode"], kind="serve")
        d = prof.observe("serve_decode", steps=replay["decode_steps"],
                         elapsed_s=replay["wall_s"],
                         tokens=replay["total_tokens"],
                         n_chips=jax.device_count())
        return {"derived": d["derived"],
                "prefill": prof.profile("serve_prefill")["cost"],
                "tokens_per_sec": replay["tokens_per_sec"],
                "hbm": led.snapshot()}
    finally:
        eng.close()
        ex.close()


def _profile_embed(prof, led, quick, seed):
    """Embedding-scoring stage: the cached (device hot tier) scorer
    replayed over the Zipfian trace, captured at serving shapes."""
    import jax
    from hetu_tpu.serving import EmbeddingServer
    ex, model, cst, rows, F, nd = _embed_build(quick)
    n = 60 if quick else 400
    trace = _embed_trace(seed, n, rows, F, nd)
    try:
        srv = EmbeddingServer(ex, model, host_table=cst,
                              own_host_table=False, n_slots=8,
                              cache_rows=max(1024, 8 * F),
                              staleness_bound=0, name="prof_embed",
                              instance="prof_embed")
        try:
            srv.score_many([trace[0][1]], [trace[0][2]])   # warm
            replay = _embed_replay(srv, trace, cst)
            cp = srv.cost_programs()
            prof.capture("embed_score", cp["score"], kind="embed")
            rows_served = (replay["requests_scored"] * srv.num_sparse)
            d = prof.observe("embed_score",
                             steps=replay["iterations"],
                             elapsed_s=replay["wall_s"],
                             tokens=rows_served, items_name="rows",
                             n_chips=jax.device_count())
            return {"derived": d["derived"],
                    "rows_per_sec": replay["rows_per_sec"],
                    "hit_rate": replay["hot_cache"]["hit_rate"],
                    "hbm": led.snapshot()}
        finally:
            srv.close()
    finally:
        cst.close()
        ex.close()


def _profile_signals(prof, stages):
    """Flatten the round into the flat ``{signal: value}`` dict
    tools/perf_diff.py diffs: per-program static cost + measured
    throughput/MFU, plus the PEAK per-pool HBM bytes observed across
    the stage snapshots."""
    sig = {}
    for name, p in sorted(prof.profiles().items()):
        d = p.get("derived") or {}
        for k in ("flops_per_step", "bytes_per_step", "steps_per_sec",
                  "mfu", "tokens_per_sec_per_chip",
                  "examples_per_sec_per_chip", "rows_per_sec_per_chip"):
            if d.get(k) is not None:
                sig[f"{name}.{k}"] = d[k]
    peak = {}
    for st in stages.values():
        for pool, b in st["hbm"]["pools"].items():
            peak[pool] = max(peak.get(pool, 0), int(b))
    for pool, b in sorted(peak.items()):
        if b:
            sig[f"hbm.{pool}_bytes"] = b
    return sig


def run_profile(quick=False, seed=0):
    from hetu_tpu import telemetry
    prof = telemetry.get_profiler()
    led = telemetry.get_hbm_ledger()
    # seeded degraded rounds: sleep this long per train step, so the
    # measured signals (steps/s, MFU) drop while static cost holds —
    # the perf-regression harness must trip on exactly this shape
    slowdown = float(os.environ.get("HETU_PROFILE_SLOWDOWN_S", "0") or 0)
    stages = {
        "train": _profile_train(prof, led, quick, seed, slowdown),
        "serve": _profile_serve(prof, led, quick, seed),
        "embed": _profile_embed(prof, led, quick, seed),
    }
    signals = _profile_signals(prof, stages)
    import jax
    return {"metric": "profile_train_mfu",
            "value": stages["train"]["derived"].get("mfu"),
            "unit": "mfu",
            "vs_baseline": None,
            "platform": jax.default_backend(),
            "seed": seed, "quick": bool(quick),
            "peaks": prof.peaks(),
            "n_chips": jax.device_count(),
            **({"slowdown_s": slowdown} if slowdown else {}),
            "stages": stages,
            "layer_table": prof.layer_table(),
            "signals": signals,
            "hbm_final": led.snapshot()}


def _emit_profile(out, history_path=None):
    """Profile evidence in the bench layered shape: full headline to an
    early line + PROFILE_FULL.json (written only after the run has real
    results — the no-clobber contract), one signals entry appended to
    benchmarks/history.jsonl, compact tail line with the per-stage
    ``pf`` block."""
    from hetu_tpu.telemetry import JsonlWriter
    history_path = HISTORY_PATH if history_path is None else history_path
    full = json.dumps(out)
    try:
        with open(PROFILE_DETAIL_PATH, "w") as f:
            f.write(full + "\n")
    except OSError:
        pass
    entry = {"t": round(time.time(), 3), "platform": out["platform"],
             "quick": out["quick"], "seed": out["seed"],
             "signals": out["signals"]}
    try:
        os.makedirs(os.path.dirname(history_path) or ".", exist_ok=True)
        with JsonlWriter(history_path) as w:     # append, never truncate
            w.write(entry)
    except OSError:
        pass
    print(full, flush=True)
    pf = {}
    for st, d in out["stages"].items():
        dd = d["derived"]
        row = {}
        if dd.get("mfu") is not None:
            row["mfu"] = dd["mfu"]
        row["gflops"] = round(dd.get("flops_per_step", 0) / 1e9, 4)
        for k, short in (("tokens_per_sec_per_chip", "tok_s"),
                         ("examples_per_sec_per_chip", "ex_s"),
                         ("rows_per_sec_per_chip", "rows_s")):
            if dd.get(k) is not None:
                row[short] = dd[k]
        ai = (dd.get("roofline") or {}).get("arithmetic_intensity")
        if ai is not None:
            row["ai"] = ai
        pf[st] = row
    pf["hbm_kib"] = {p: round(b / 1024, 1)
                     for p, b in out["stages"]["serve"]["hbm"]["pools"]
                     .items() if b}
    compact = {"metric": out["metric"], "value": out["value"],
               "unit": out["unit"], "platform": out["platform"],
               "pf": pf,
               "history": os.path.basename(history_path),
               "detail": os.path.basename(PROFILE_DETAIL_PATH)}
    _print_compact(compact, drop_order=("history",))


# -- plan mode (bench.py --plan) -------------------------------------------
# Auto-parallel planner evidence (ISSUE 18): calibrate per-layer
# LayerProfiles on the live backend (compiled fwd+bwd timing + XLA
# temp-bytes slope + measured ICI), run the Galvatron search, persist
# the winning plan as a versioned artifact, then EXECUTE the emitted
# plan through HybridParallelModel and gate the predicted-vs-measured
# iteration-time error (plan_pred_err) plus a hand-picked pure-DP
# baseline A/B.  A pre-existing HETU_PLAN_PROFILE artifact is reused
# instead of recalibrated — same profile in, byte-identical plan out.

PLAN_DETAIL_PATH = os.environ.get(
    "HETU_PLAN_JSON",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "PLAN_FULL.json"))

PLAN_PROFILE_PATH = os.environ.get(
    "HETU_PLAN_PROFILE",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "benchmarks", "plan_profile.json"))

PLAN_ARTIFACT_PATH = os.environ.get(
    "HETU_PLAN_ARTIFACT",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "benchmarks", "plan_train.json"))


def _plan_specs(quick):
    from hetu_tpu.galvatron.runtime import TransformerHPLayer
    n = 4 if quick else 8
    hidden = 64 if quick else 128
    return [TransformerHPLayer(hidden, 4, ffn=2 * hidden)
            for _ in range(n)]


def _plan_budget():
    """Per-device search memory budget: the backend's reported HBM
    limit when it has one, a 4 GiB nominal otherwise (CPU)."""
    try:
        import jax
        stats = jax.devices()[0].memory_stats()
        if stats and stats.get("bytes_limit"):
            return int(0.9 * stats["bytes_limit"])
    except Exception:
        pass
    return 4 << 30


def _plan_execute(cfg, specs, global_bsz, seq, reps):
    """Run the config through HybridParallelModel's real train step and
    return the measured per-iteration milliseconds (median of ``reps``
    fully-synced iterations — the same per-iteration quantity the cost
    model predicts)."""
    import statistics
    import jax
    import jax.numpy as jnp
    from hetu_tpu.galvatron.runtime import HybridParallelModel
    model = HybridParallelModel(specs, cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    step, opt_init = model.make_train_step()
    opt_state = opt_init(params)
    hidden = specs[0].hidden
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (global_bsz, seq, hidden), jnp.float32)
    tgt = jax.random.normal(jax.random.PRNGKey(2),
                            (global_bsz, seq, hidden), jnp.float32)
    params, opt_state, loss = step(params, opt_state, x, tgt)
    jax.block_until_ready(loss)                 # compile outside
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, x, tgt)
        jax.block_until_ready(loss)
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3


def run_plan(quick=False, seed=0):
    import jax
    from hetu_tpu.galvatron.config import HybridParallelConfig
    from hetu_tpu.galvatron.search import LayerProfile, load_profile_doc
    from hetu_tpu.planner import (calibrate_and_save,
                                  emit_plan_from_profile, predict,
                                  save_plan, serving_tp)
    specs = _plan_specs(quick)
    n = len(specs)
    seq = 32 if quick else 64
    global_bsz = 8
    t0 = time.perf_counter()
    reused = os.path.exists(PLAN_PROFILE_PATH)
    if not reused:
        # calibrate at the SAME batch the plan will execute, so the
        # per-sample compute_ms and the measured step share fixed costs
        calibrate_and_save(PLAN_PROFILE_PATH, specs, batch=global_bsz,
                           seq=seq, reps=5 if quick else 20)
    calibrate_s = time.perf_counter() - t0
    doc = load_profile_doc(PLAN_PROFILE_PATH)
    layers = [LayerProfile.from_json(l) for l in doc["layers"]]
    world = jax.device_count()
    t0 = time.perf_counter()
    plan = emit_plan_from_profile(
        PLAN_PROFILE_PATH, world, _plan_budget(),
        global_bsz=global_bsz, chunks_candidates=(1, 2, 4))
    search_ms = (time.perf_counter() - t0) * 1e3
    save_plan(PLAN_ARTIFACT_PATH, plan)
    cfg = HybridParallelConfig.from_json(plan["config"])
    reps = 10 if quick else 30
    meas_ms = _plan_execute(cfg, specs, global_bsz, seq, reps)
    pred_ms = plan["predicted"]["iter_ms"]
    err = abs(pred_ms - meas_ms) / meas_ms
    # hand-picked baseline: the config a person writes without a
    # search — uniform pure data parallelism, no pipeline, no ckpt
    hand_cfg = HybridParallelConfig(
        pp_deg=1, tp_sizes=[1] * n, dp_types=[0] * n, world=world,
        global_bsz=global_bsz, chunks=1)
    hand_pred = predict(hand_cfg, layers,
                        ici_gbps=doc.get("ici_gbps", 100.0))
    hand_ms = _plan_execute(hand_cfg, specs, global_bsz, seq, reps)
    signals = {
        "plan_pred_err": round(err, 6),
        "plan_iter_ms": round(meas_ms, 4),
        "plan_pred_iter_ms": round(pred_ms, 4),
        "plan_hand_iter_ms": round(hand_ms, 4),
        "plan_vs_hand_ratio": round(meas_ms / hand_ms, 4)
        if hand_ms > 0 else None,
        "plan_search_ms": round(search_ms, 3),
    }
    signals = {k: v for k, v in signals.items() if v is not None}
    return {"metric": "plan_pred_err", "value": round(err, 6),
            "unit": "frac", "vs_baseline": None,
            "platform": jax.default_backend(),
            "seed": seed, "quick": bool(quick),
            "world": world, "n_layers": n,
            "profile": {"path": os.path.basename(PLAN_PROFILE_PATH),
                        "reused": bool(reused),
                        "calibrate_s": round(calibrate_s, 3),
                        "ici_gbps": doc.get("ici_gbps"),
                        "meta": doc.get("meta")},
            "plan": plan,
            "plan_artifact": os.path.basename(PLAN_ARTIFACT_PATH),
            "serving_tp": serving_tp(plan),
            "measured": {"iter_ms": round(meas_ms, 4), "reps": reps,
                         "global_bsz": global_bsz, "seq": seq},
            "hand_baseline": {"iter_ms": round(hand_ms, 4),
                              "predicted": hand_pred,
                              "config": hand_cfg.to_json()},
            "signals": signals}


def _emit_plan(out, history_path=None):
    """Plan evidence in the bench layered shape: full headline to an
    early line + PLAN_FULL.json (written only after the run has real
    results — the no-clobber contract), one signals entry appended to
    benchmarks/history.jsonl, compact tail line with the ``pl``
    block."""
    from hetu_tpu.telemetry import JsonlWriter
    history_path = HISTORY_PATH if history_path is None else history_path
    full = json.dumps(out)
    try:
        with open(PLAN_DETAIL_PATH, "w") as f:
            f.write(full + "\n")
    except OSError:
        pass
    entry = {"t": round(time.time(), 3), "platform": out["platform"],
             "quick": out["quick"], "seed": out["seed"],
             "signals": out["signals"]}
    try:
        os.makedirs(os.path.dirname(history_path) or ".", exist_ok=True)
        with JsonlWriter(history_path) as w:     # append, never truncate
            w.write(entry)
    except OSError:
        pass
    print(full, flush=True)
    plan = out["plan"]
    cfgj = plan["config"]
    pl = {"iter_ms": out["measured"]["iter_ms"],
          "pred_ms": plan["predicted"]["iter_ms"],
          "hand_ms": out["hand_baseline"]["iter_ms"],
          "core": plan["core"],
          "pp": cfgj.get("pp_deg"),
          "tp_max": out["serving_tp"],
          "chunks": cfgj.get("chunks"),
          "world": out["world"]}
    compact = {"metric": out["metric"], "value": out["value"],
               "unit": out["unit"], "platform": out["platform"],
               "pl": pl,
               "history": os.path.basename(history_path),
               "detail": os.path.basename(PLAN_DETAIL_PATH)}
    _print_compact(compact, drop_order=("history",))


# -- SLO control-plane mode (bench.py --slo) -------------------------------
# The ISSUE 11 evidence: a seeded bursty "diurnal" arrival trace driven
# through a FleetController-supervised fleet and through its static
# single-replica twin, on a shared VIRTUAL clock (one fixed quantum per
# pump iteration), so deadlines, EWMAs, cooldowns and the admission
# estimates are exact functions of the seed — no CPU wall-clock noise.
# Headline: SLO attainment (healthy finishes / offered work).  The
# acceptance gates ride along: controller beats the twin on
# deadline-miss rate, zero accepted-rid loss, every scale/degrade
# transition visible as incident + metric, admission sheds typed
# SLOReject before taking a slot.

SLO_DETAIL_PATH = os.environ.get(
    "HETU_SLO_JSON",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "SLO_FULL.json"))

_SLO_EKW = dict(n_slots=2, max_len=32, max_prompt_len=8, name="serve")
_SLO_DT = 0.05        # virtual seconds per pump iteration


class _IterClock:
    """Deterministic virtual clock for the SLO round: the loop advances
    it one quantum per iteration; everything time-based downstream
    (deadlines, EWMAs, breaker backoff, controller cooldowns) sees the
    same seeded timeline on every run."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _slo_trace(seed, vocab, quick):
    """Bursty diurnal arrivals in ITERATION time: calm warmup, a heavy
    "peak hour" burst, a pathological spike, and a recovery tail.
    ~10% of requests carry no deadline (brownout shed fodder) and ~8%
    are DOOMED — deadlines shorter than their own decode time, which
    no capacity can meet; they are the predictive-admission witnesses
    (the static twin admits-then-expires them)."""
    rng = np.random.default_rng(seed)
    phases = [(8, 4.0),                     # warmup: under capacity
              (36 if quick else 72, 0.4),   # burst: ~8x one replica
              (40 if quick else 80, 0.05),  # spike: ~60x one replica
              (6, 4.0)]                     # recovery tail
    out, it = [], 0.0
    for phase, (n, gap) in enumerate(phases):
        for _ in range(n):
            it += float(rng.exponential(gap))
            spec = {"arrival_it": it,
                    "prompt": rng.integers(1, vocab,
                                           (int(rng.integers(3, 8)),)),
                    "max_new": int(rng.integers(4, 9)),
                    "ttl": float(rng.uniform(3.0, 6.0)),
                    "doomed": False}
            u = float(rng.random())
            if u < 0.10:
                spec["ttl"] = None          # no-deadline traffic
            elif u < 0.18 and phase in (1, 2):
                spec["ttl"] = 0.3           # < its own decode time
                spec["max_new"] = 8
                spec["doomed"] = True
            out.append(spec)
    return out


def _slo_run(ex, model, c, trace, controlled, seed):
    """Replay the trace through one fleet — controller-supervised or
    static — on a fresh virtual clock.  Returns per-run evidence."""
    import warnings
    from hetu_tpu.serving import (EngineFleet, EngineOverloaded,
                                  FleetController, FleetUnavailable,
                                  SLO, SLOReject, TERMINAL_OK)

    clk = _IterClock()
    fleet = EngineFleet(
        ex, model, n_engines=1, engine_kwargs=_SLO_EKW,
        threaded=False, clock=clk,
        name="ctl" if controlled else "static",
        replica_prefix="c" if controlled else "s")
    ctl = None
    if controlled:
        ctl = FleetController(
            fleet,
            SLO(deadline_miss_target=0.05, ttft_p99_s=1.5,
                max_shed_fraction=0.6),
            min_engines=1, max_engines=3,
            scale_up_queue=3.0, scale_down_queue=0.5,
            cooldown_s=1.5, degrade_enter_ticks=20,
            degrade_exit_ticks=40, brownout_max_new=4)
    accepted, sheds, overloaded = [], [], 0
    i, it, capped_at = 0, 0, 20000
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        while (i < len(trace) or not fleet.idle) and it < capped_at:
            while i < len(trace) and trace[i]["arrival_it"] <= it:
                spec = trace[i]
                i += 1
                try:
                    target = ctl if ctl is not None else fleet
                    freq = target.submit(spec["prompt"],
                                         spec["max_new"],
                                         ttl=spec["ttl"])
                    accepted.append((spec, freq))
                except SLOReject as e:
                    sheds.append((spec, e))
                except (EngineOverloaded, FleetUnavailable):
                    overloaded += 1
            fleet.pump()
            if ctl is not None:
                ctl.tick()
            clk.advance(_SLO_DT)
            it += 1
        # post-trace cooldown window: the controller walks the ladder
        # back down and scales in — the exit transitions are evidence
        # too, not just the entries
        if ctl is not None:
            for _ in range(240):
                fleet.pump()
                ctl.tick()
                clk.advance(_SLO_DT)
                it += 1
    drained = fleet.idle
    fc = dict(fleet.finish_counts)
    finished = sum(fc.values())
    ok = sum(fc.get(r, 0) for r in TERMINAL_OK)
    offered = len(trace)
    shed = len(sheds)
    miss_rate = fc.get("deadline", 0) / max(1, finished)
    attainment = ok / max(1, offered)
    # SLOReject typing: every shed is the typed exception, raised
    # BEFORE the fleet assigned a rid or took a slot
    typed = all(isinstance(e, SLOReject) and e.reason
                for _, e in sheds)
    doomed_shed = sum(1 for s, e in sheds
                      if s["doomed"] and e.reason == "infeasible_deadline")
    out = {"controlled": bool(controlled),
           "offered": offered,
           "accepted": len(accepted),
           "shed": shed,
           "overloaded": overloaded,
           "finished": finished,
           "finish_reasons": fc,
           "all_accepted_terminal": all(r.finished
                                        for _, r in accepted),
           "deadline_miss_rate": round(miss_rate, 4),
           "attainment": round(attainment, 4),
           "sheds_typed": bool(typed),
           "doomed_shed": doomed_shed,
           "drained": bool(drained),
           "iterations": it,
           "virtual_s": round(clk.t, 2)}
    if ctl is not None:
        out["controller"] = ctl.report()
        out["shed_reasons"] = _count_by(e.reason for _, e in sheds)
    s = fleet.stats()
    out["n_engines_final"] = s["n_engines"]
    out["failovers"] = s["failovers"]
    fleet.stop()
    return out


def _count_by(items):
    out = {}
    for x in items:
        out[x] = out.get(x, 0) + 1
    return out


def run_slo(quick=False, seed=0):
    """Controller fleet vs static twin on the same seeded bursty trace
    (run sequentially in one process; rid prefixes keep their records
    apart).  Asserts the ISSUE 11 acceptance gates inline."""
    import jax
    from hetu_tpu import telemetry

    ex, model, c = _serve_build(True)   # tiny decode model: control
    # decisions, not shapes, are the thing measured
    trace = _slo_trace(seed, c.vocab_size, quick)
    fl = telemetry.get_flight()
    scale0 = fl.incident_count("slo_scale")
    degrade0 = fl.incident_count("slo_degrade")
    ctl_out = _slo_run(ex, model, c, trace, True, seed)
    static_out = _slo_run(ex, model, c, trace, False, seed)
    ctl = ctl_out["controller"]
    transitions = {
        "scale": ctl["counters"]["scale_ups"]
        + ctl["counters"]["scale_downs"],
        "degrade": ctl["counters"]["degrade_entries"]
        + ctl["counters"]["degrade_exits"],
        "scale_incidents": fl.incident_count("slo_scale") - scale0,
        "degrade_incidents":
            fl.incident_count("slo_degrade") - degrade0}
    wins = (ctl_out["deadline_miss_rate"]
            < static_out["deadline_miss_rate"]
            and ctl_out["attainment"] > static_out["attainment"])
    # acceptance gates (the protocol test re-checks them from stdout)
    assert ctl_out["all_accepted_terminal"] \
        and static_out["all_accepted_terminal"], "accepted-rid loss"
    assert ctl_out["sheds_typed"], "untyped shed"
    assert ctl_out["shed"] > 0 and ctl_out["doomed_shed"] > 0, \
        "predictive admission never fired"
    assert ctl["counters"]["scale_ups"] >= 1, "controller never scaled"
    if fl.enabled:
        assert transitions["scale_incidents"] == transitions["scale"], \
            transitions
        assert transitions["degrade_incidents"] == \
            transitions["degrade"], transitions
    assert wins, (ctl_out["deadline_miss_rate"],
                  static_out["deadline_miss_rate"])
    out = {"metric": "slo_attainment",
           "value": ctl_out["attainment"],
           "unit": "fraction",
           "seed": seed,
           "quick": bool(quick),
           "platform": jax.default_backend(),
           "slo": ctl["slo"],
           "stages": {"controller": ctl_out, "static": static_out},
           "controller_wins": bool(wins),
           "transitions": transitions,
           "signals": {
               "slo_attainment": ctl_out["attainment"],
               "shed_fraction": round(ctl["shed_fraction"], 4),
               "slo_static_attainment": static_out["attainment"]}}
    return out


def _emit_slo(out, history_path=None):
    """SLO evidence in the bench layered shape: full headline early +
    SLO_FULL.json (no-clobber: written only after a real run), one
    flat signals entry into benchmarks/history.jsonl (slo_attainment
    is a higher-is-better one-sided signal for tools/perf_diff.py),
    compact tail line under the byte budget."""
    from hetu_tpu.telemetry import JsonlWriter
    history_path = HISTORY_PATH if history_path is None else history_path
    full = json.dumps(out)
    try:
        with open(SLO_DETAIL_PATH, "w") as f:
            f.write(full + "\n")
    except OSError:
        pass
    entry = {"t": round(time.time(), 3), "platform": out["platform"],
             "quick": out["quick"], "seed": out["seed"],
             "signals": out["signals"]}
    try:
        os.makedirs(os.path.dirname(history_path) or ".", exist_ok=True)
        with JsonlWriter(history_path) as w:     # append, never truncate
            w.write(entry)
    except OSError:
        pass
    print(full, flush=True)
    c, s = out["stages"]["controller"], out["stages"]["static"]
    ctr = c["controller"]["counters"]
    compact = {"metric": out["metric"], "value": out["value"],
               "unit": out["unit"], "platform": out["platform"],
               "wins": out["controller_wins"],
               "miss": {"ctl": c["deadline_miss_rate"],
                        "static": s["deadline_miss_rate"]},
               "attain": {"ctl": c["attainment"],
                          "static": s["attainment"]},
               "shed": {"n": c["shed"],
                        "frac": c["controller"]["shed_fraction"],
                        "doomed": c["doomed_shed"]},
               "scale": {"up": ctr["scale_ups"],
                         "down": ctr["scale_downs"],
                         "final": c["n_engines_final"]},
               "degrade": {"in": ctr["degrade_entries"],
                           "out": ctr["degrade_exits"],
                           "max": ctr["max_level_seen"]},
               "rid_audit": "ok",
               "history": os.path.basename(history_path),
               "detail": os.path.basename(SLO_DETAIL_PATH)}
    _print_compact(compact, drop_order=("history", "rid_audit",
                                        "degrade", "scale"))


# -- chaos-serve mode (bench.py --chaos --serve) ---------------------------
# Serving-side resilience evidence: inject every serving fault class
# (poisoned decode, raising step, slot leak, stalled/raising consumer,
# arrival-burst overload, deadline/cancel churn) through
# hetu_tpu.resilience.faults into the PROTECTED engine and prove it
# recovers — engine loop alive, slot audit balanced (allocs == frees),
# partial results with the right finish_reason — while the UNPROTECTED
# twin (watchdog off, queue unbounded) demonstrably dies, wedges, or
# leaks under the same seed.  Reported into CHAOS_FULL.json under the
# same no-clobber contract as --chaos.


def _chaos_serve_prompts(rng, n, vocab, lo=3, hi=9):
    return [rng.integers(1, vocab, (int(L),))
            for L in rng.integers(lo, hi, n)]


def _chaos_serve_nan_decode(ex, model, c, seed):
    """Poison one running slot's KV mid-flight: the protected engine
    quarantines exactly that request (finish_reason="error") and the
    other streams stay bitwise identical to a clean run; the
    unprotected twin serves NaN-derived tokens as if healthy."""
    import warnings
    from hetu_tpu.resilience import faults
    from hetu_tpu.serving import InferenceEngine

    rng = np.random.default_rng(seed)
    prompts = _chaos_serve_prompts(rng, 3, c.vocab_size)
    kw = dict(n_slots=3, max_len=32, max_prompt_len=8, prefill_budget=3,
              name="serve", seed=seed)
    clean = InferenceEngine(ex, model, instance="nan.clean", **kw)
    baseline = clean.generate_many(prompts, 8)

    def poisoned_run(watchdog):
        # distinct rid prefixes per engine: the --telemetry rid audit
        # keys timelines by rid, and twins whose death is the point are
        # excluded by their "twin." prefix
        eng = InferenceEngine(
            ex, model, watchdog=watchdog,
            instance="nan.prot" if watchdog else "twin.nan", **kw)
        reqs = [eng.submit(p, 8) for p in prompts]
        eng.step()
        faults.poison_slot_kv(eng, reqs[1].slot)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            eng.run(max_iterations=500)
        return eng, reqs

    eng, reqs = poisoned_run(watchdog=True)
    others_bitwise = (np.array_equal(reqs[0].result(), baseline[0])
                      and np.array_equal(reqs[2].result(), baseline[2]))
    audit = eng.cache.audit()
    recovered = (reqs[1].finish_reason == "error" and others_bitwise
                 and eng.watchdog_trips >= 1
                 and audit["allocs"] == audit["frees"])
    ueng, ureqs = poisoned_run(watchdog=False)
    return {"faults_injected": 1, "faults_recovered": int(recovered),
            "poisoned_finish_reason": reqs[1].finish_reason,
            "unaffected_streams_bitwise": bool(others_bitwise),
            "watchdog_trips": eng.watchdog_trips,
            "slot_audit": audit,
            "unprotected_served_poisoned_as_healthy": bool(
                ureqs[1].finish_reason in ("eos", "max_new"))}


def _chaos_serve_raising_step(ex, model, c, seed):
    """A decode step that RAISES: the protected engine retires the
    in-flight batch with "error" and keeps serving new requests; the
    unprotected twin dies on the spot."""
    import warnings
    from hetu_tpu.resilience import faults, InjectedFault
    from hetu_tpu.serving import InferenceEngine

    rng = np.random.default_rng(seed + 1)
    prompts = _chaos_serve_prompts(rng, 2, c.vocab_size)
    kw = dict(n_slots=2, max_len=32, max_prompt_len=8, name="serve",
              seed=seed)
    eng = InferenceEngine(ex, model, instance="raise.prot", **kw)
    reqs = [eng.submit(p, 8) for p in prompts]
    faults.raising_engine_step(eng, at=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng.run(max_iterations=500)
        after = eng.generate_many([prompts[0]], 6)
    audit = eng.cache.audit()
    recovered = (all(r.finish_reason == "error" for r in reqs)
                 and len(after[0]) == 6
                 and audit["allocs"] == audit["frees"])
    # unprotected twin: the same injected exception propagates and the
    # engine (process, in production) is gone
    ueng = InferenceEngine(ex, model, watchdog=False,
                           instance="twin.raise", **kw)
    for p in prompts:
        ueng.submit(p, 8)
    faults.raising_engine_step(ueng, at=2)
    died = False
    try:
        ueng.run(max_iterations=500)
    except InjectedFault:
        died = True
    return {"faults_injected": 1, "faults_recovered": int(recovered),
            "in_flight_finish_reasons":
                [r.finish_reason for r in reqs],
            "served_after_fault": int(len(after[0])),
            "slot_audit": audit,
            "unprotected_engine_died": bool(died)}


def _chaos_serve_slot_leak(ex, model, c, seed):
    """Leak EVERY free slot: the protected engine's reconcile sweep
    reclaims them within one iteration and the queue drains; the
    unprotected twin starves — queued requests are never admitted."""
    import warnings
    from hetu_tpu.resilience import faults
    from hetu_tpu.serving import InferenceEngine

    rng = np.random.default_rng(seed + 2)
    prompts = _chaos_serve_prompts(rng, 3, c.vocab_size)
    kw = dict(n_slots=2, max_len=32, max_prompt_len=8, name="serve",
              seed=seed)
    eng = InferenceEngine(ex, model, instance="leak.prot", **kw)
    leaked = []
    while True:
        s = faults.leak_slot(eng)
        if s is None:
            break
        leaked.append(s)
    reqs = [eng.submit(p, 6) for p in prompts]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng.run(max_iterations=500)
    audit = eng.cache.audit()
    recovered = (all(r.finished for r in reqs)
                 and eng.slot_leaks_reclaimed >= len(leaked)
                 and audit["allocs"] == audit["frees"])
    ueng = InferenceEngine(ex, model, watchdog=False,
                           instance="twin.leak", **kw)
    while faults.leak_slot(ueng) is not None:
        pass
    for p in prompts:
        ueng.submit(p, 6)
    wedged = False
    try:
        ueng.run(max_iterations=50)
    except RuntimeError:
        wedged = True       # never drains: every slot leaked away
    uaudit = ueng.cache.audit()
    return {"faults_injected": len(leaked),
            "faults_recovered": int(recovered) * len(leaked),
            "slots_leaked": len(leaked),
            "slots_reclaimed": eng.slot_leaks_reclaimed,
            "slot_audit": audit,
            "unprotected_wedged": bool(wedged),
            "unprotected_slot_audit": uaudit}


def _chaos_serve_stalled_consumer(ex, model, c, seed, quick):
    """A stream consumer that stalls (and later raises): the protected
    engine detaches it after one bounded delivery and finishes the
    request; its tokens still land in result()."""
    import warnings
    from hetu_tpu.resilience import faults
    from hetu_tpu.serving import InferenceEngine

    rng = np.random.default_rng(seed + 3)
    prompts = _chaos_serve_prompts(rng, 2, c.vocab_size)
    stall = 0.05 if quick else 0.2
    eng = InferenceEngine(ex, model, n_slots=2, max_len=32,
                          max_prompt_len=8, name="serve", seed=seed,
                          instance="stall.prot",
                          stream_stall_timeout=stall / 4)
    got = []
    stalled_cb = faults.stalling_consumer(stall, collect=got)
    raising_cb = faults.stalling_consumer(0, fail_after=1)
    r1 = eng.submit(prompts[0], 6, stream=stalled_cb)
    r2 = eng.submit(prompts[1], 6, stream=raising_cb)
    t0 = time.perf_counter()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng.run(max_iterations=500)
    wall = time.perf_counter() - t0
    audit = eng.cache.audit()
    recovered = (eng.streams_detached >= 2
                 and len(r1.tokens) == 6 and len(r2.tokens) == 6
                 and audit["allocs"] == audit["frees"])
    return {"faults_injected": 2,
            "faults_recovered": (2 if recovered else
                                 min(2, eng.streams_detached)),
            "streams_detached": eng.streams_detached,
            "stalled_deliveries_paid": len(got),
            "wall_s": round(wall, 3),
            "slot_audit": audit}


def _chaos_serve_overload(ex, model, c, seed, quick):
    """Arrival burst 4x the queue bound: the protected engine sheds with
    typed EngineOverloaded rejections at a bounded depth and finishes
    everything it admitted; the unprotected twin queues the whole burst
    (unbounded growth — the OOM path in production)."""
    import warnings
    from hetu_tpu.serving import EngineOverloaded, InferenceEngine

    rng = np.random.default_rng(seed + 4)
    n_burst = 24 if quick else 48
    max_queue = 6
    prompts = _chaos_serve_prompts(rng, n_burst, c.vocab_size)
    eng = InferenceEngine(ex, model, n_slots=2, max_len=32,
                          max_prompt_len=8, name="serve", seed=seed,
                          instance="burst.prot", max_queue=max_queue)
    accepted, rejected = [], 0
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for i, p in enumerate(prompts):
            try:
                accepted.append(eng.submit(p, 4))
            except EngineOverloaded:
                rejected += 1
            if i % 4 == 3:
                # the burst outruns decode 4:1 — admission must stay
                # closed until the queue drains to the low watermark,
                # then reopen (the hysteresis cycle, not one hard edge)
                eng.step()
        eng.run(max_iterations=2000)
    audit = eng.cache.audit()
    recovered = (rejected > 0
                 and eng.scheduler.queue_depth_peak <= max_queue
                 and all(r.finished for r in accepted)
                 and audit["allocs"] == audit["frees"])
    ueng = InferenceEngine(ex, model, n_slots=2, max_len=32,
                           max_prompt_len=8, name="serve", seed=seed,
                           instance="twin.burst", watchdog=False)
    for p in prompts:
        ueng.submit(p, 4)
    unbounded_peak = ueng.scheduler.queue_depth_peak
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        ueng.run(max_iterations=5000)
    return {"faults_injected": 1, "faults_recovered": int(recovered),
            "burst_size": n_burst, "max_queue": max_queue,
            "rejections": rejected,
            "queue_depth_peak": eng.scheduler.queue_depth_peak,
            "accepted_finished": int(sum(r.finished for r in accepted)),
            "goodput_tokens": int(sum(len(r.tokens) for r in accepted)),
            "slot_audit": audit,
            "unprotected_queue_depth_peak": int(unbounded_peak)}


def _chaos_serve_deadline_cancel(ex, model, c, seed):
    """Deadline expiry (queued AND mid-flight) + mid-flight cancel: all
    three return partial results with the right finish_reason and free
    their slots immediately."""
    import warnings
    from hetu_tpu.serving import InferenceEngine

    rng = np.random.default_rng(seed + 5)
    prompts = _chaos_serve_prompts(rng, 4, c.vocab_size)
    eng = InferenceEngine(ex, model, n_slots=1, max_len=32,
                          max_prompt_len=8, name="serve", seed=seed,
                          instance="ttl.prot")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        ra = eng.submit(prompts[0], 20)              # hogs the one slot
        rb = eng.submit(prompts[1], 8, ttl=1e-6)     # expires queued
        eng.step(); eng.step()
        rc = eng.submit(prompts[2], 20)
        rd = eng.submit(prompts[3], 20)
        # drive ra out, let rc get the slot and produce a few tokens
        eng.cancel(ra.rid)
        eng.step(); eng.step(); eng.step()
        # mid-flight expiry: force rc's deadline into the past
        rc.deadline = eng._now() - 1.0
        eng.step()
        eng.cancel(rd.rid)
        eng.run(max_iterations=500)
    audit = eng.cache.audit()
    checks = {
        "queued_expired": (rb.finish_reason == "deadline"
                           and len(rb.tokens) == 0),
        "midflight_expired_partial": (rc.finish_reason == "deadline"
                                      and 0 < len(rc.tokens) < 20),
        "cancelled_partial": (ra.finish_reason == "cancelled"
                              and 0 < len(ra.tokens) < 20
                              and rd.finish_reason == "cancelled"),
    }
    recovered = all(checks.values()) and audit["allocs"] == audit["frees"]
    return {"faults_injected": 3,
            "faults_recovered": 3 if recovered else
                sum(bool(v) for v in checks.values()),
            **{k: bool(v) for k, v in checks.items()},
            "finish_reasons": {"expired_queued": rb.finish_reason,
                               "expired_midflight": rc.finish_reason,
                               "cancelled": [ra.finish_reason,
                                             rd.finish_reason]},
            "partial_tokens": {"midflight_expired": len(rc.tokens),
                               "cancelled": len(ra.tokens)},
            "slot_audit": audit}


# -- fleet chaos mode (bench.py --chaos --serve --fleet) -------------------
# Cluster-level resilience evidence: run the EngineFleet (N supervised
# engine replicas behind the failover router) through whole-replica
# failures — crash, wedge, straggler, rolling restart, burst + crash —
# and prove ZERO accepted-request loss: every accepted rid reaches a
# terminal finish_reason, greedy streams that failed over mid-decode are
# BITWISE identical to an uninterrupted single-engine run, and every
# live replica's slot audit balances.  The single-engine twin run under
# the same seed demonstrably LOSES its in-flight streams when the
# engine dies — the gap the fleet layer closes.  Reported into
# FLEET_FULL.json under the same no-clobber contract.

FLEET_DETAIL_PATH = os.environ.get(
    "HETU_FLEET_JSON",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "FLEET_FULL.json"))

_FLEET_EKW = dict(n_slots=2, max_len=32, max_prompt_len=8, name="serve")


def _fleet_baseline(ex, model, prompts, max_new, seed, instance="base",
                    ekw=None):
    """Uninterrupted single-engine greedy streams — the parity oracle
    every failover stage compares against (shared compile-once programs
    make the comparison bitwise).  ``ekw`` overrides the engine kwargs
    (the migration stages need a PAGED twin)."""
    from hetu_tpu.serving import InferenceEngine

    eng = InferenceEngine(ex, model, seed=seed, instance=instance,
                          **(_FLEET_EKW if ekw is None else ekw))
    return eng.generate_many(prompts, max_new)


def _fleet_checks(fleet, reqs, baseline=None):
    """The zero-loss contract: every accepted rid terminal, healthy
    reasons only, per-replica audits balanced, greedy parity when an
    oracle is given."""
    terminal = all(r.finished for r in reqs)
    reasons = sorted({r.finish_reason for r in reqs if r.finished})
    healthy = all(r.finish_reason in ("eos", "max_new") for r in reqs
                  if r.finished)
    audits = fleet.audit()
    balanced = all(a["allocs"] == a["frees"] and a["in_use"] == 0
                   for a in audits.values())
    parity = None
    if baseline is not None:
        parity = all(np.array_equal(r.result(), b)
                     for r, b in zip(reqs, baseline))
    ok = bool(terminal and healthy and balanced
              and (parity is None or parity))
    return ok, {"all_terminal": bool(terminal),
                "finish_reasons": reasons,
                "token_parity": parity,
                "slot_audit": audits,
                "slot_audit_balanced": bool(balanced)}


def _chaos_fleet_engine_crash(ex, model, c, seed):
    """Kill one replica mid-decode: its in-flight requests fail over
    (replayed bitwise) and the supervisor restarts it from the shared
    program cache; the SINGLE-ENGINE twin loses every in-flight stream
    on the same seed."""
    import warnings
    from hetu_tpu.resilience import faults, InjectedFault
    from hetu_tpu.serving import EngineFleet, InferenceEngine

    rng = np.random.default_rng(seed)
    prompts = _chaos_serve_prompts(rng, 6, c.vocab_size)
    baseline = _fleet_baseline(ex, model, prompts, 10, seed,
                               instance="base.crash")
    fleet = EngineFleet(ex, model, n_engines=3, engine_kwargs=_FLEET_EKW,
                        threaded=False, breaker_base=1e-4)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        reqs = [fleet.submit(p, 10) for p in prompts]
        fleet.pump(3)
        victim = max(fleet._replicas, key=lambda r: len(r.inflight))
        in_flight = len(victim.inflight)
        faults.crash_engine(victim.engine)
        fleet.wait(reqs, timeout=120)
    trace = fleet.trace_counts()
    s = fleet.stats()
    ok, detail = _fleet_checks(fleet, reqs, baseline)
    restarted = s["engines"][victim.name]["incarnation"] >= 1
    recovered = (ok and s["failovers"] >= in_flight and restarted
                 and trace == {"prefill": 1, "step": 1})
    fleet.stop()
    # single-engine twin: the same crash with no fleet above it — the
    # process survives (it's an exception) but every in-flight stream is
    # LOST: no terminal finish_reason, no more tokens, ever
    twin = InferenceEngine(ex, model, seed=seed, instance="twin.crash",
                           **_FLEET_EKW)
    treqs = [twin.submit(p, 10) for p in prompts]
    for _ in range(3):
        twin.step()
    faults.crash_engine(twin)
    died = False
    try:
        twin.run(max_iterations=500)
    except InjectedFault:
        died = True
    lost = sum(1 for r in treqs if not r.finished)
    return {"faults_injected": 1, "faults_recovered": int(recovered),
            "in_flight_at_crash": in_flight,
            "failovers": s["failovers"],
            "victim_restarted": bool(restarted),
            "trace_counts": trace, **detail,
            "single_engine_twin": {
                "engine_died": bool(died),
                "lost_in_flight_streams": int(lost)}}


def _chaos_fleet_engine_wedge(ex, model, c, seed, quick):
    """Wedge one replica's decode step (hung device call): the driver
    thread is stuck, the heartbeat goes stale, and the SUPERVISOR must
    quarantine from outside, fail the streams over, and restart."""
    import warnings
    from hetu_tpu.resilience import faults
    from hetu_tpu.serving import EngineFleet

    rng = np.random.default_rng(seed + 11)
    prompts = _chaos_serve_prompts(rng, 4, c.vocab_size)
    baseline = _fleet_baseline(ex, model, prompts, 10, seed,
                               instance="base.wedge")
    wedge_s = 1.0 if quick else 2.5
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        fleet = EngineFleet(ex, model, n_engines=2,
                            engine_kwargs=_FLEET_EKW, threaded=True,
                            wedge_timeout=0.25, breaker_base=0.01)
        # route one warm request everywhere so EWMAs exist
        fleet.generate_many(prompts[:2], 4, timeout=60)
        victim = fleet._replicas[0]
        faults.wedge_engine(victim.engine, wedge_s)
        reqs = [fleet.submit(p, 10) for p in prompts]
        fleet.wait(reqs, timeout=120)
        # let the supervisor finish the breaker-gated restart so the
        # report shows the replica back in service
        fleet._wait_for(lambda: victim.incarnation >= 1, 60, "restart")
        s = fleet.stats()
        ok, detail = _fleet_checks(fleet, reqs, baseline)
        fleet.stop()
    recovered = ok and s["failovers"] >= 1
    return {"faults_injected": 1, "faults_recovered": int(recovered),
            "failovers": s["failovers"],
            "victim_incarnation":
                s["engines"][victim.name]["incarnation"],
            "wedge_seconds": wedge_s, **detail}


def _chaos_fleet_slow_engine(ex, model, c, seed, quick):
    """One straggler replica (every step sleeps): not a fault — the
    latency-aware router must LEARN to route around it from the TPOT
    EWMAs, while the straggler still finishes what it holds."""
    import warnings
    from hetu_tpu.resilience import faults
    from hetu_tpu.serving import EngineFleet

    rng = np.random.default_rng(seed + 22)
    n = 12 if quick else 24
    prompts = _chaos_serve_prompts(rng, n + 3, c.vocab_size)
    # threaded: in manual pump mode every replica shares the caller's
    # wall clock, so a straggler's sleeps inflate EVERYONE's TPOT and
    # the EWMAs never separate — with one driver thread each, the
    # straggler's latency is its own
    fleet = EngineFleet(ex, model, n_engines=3, engine_kwargs=_FLEET_EKW,
                        threaded=True, wedge_timeout=30.0)
    slow = fleet._replicas[0]
    # straggler is many healthy steps per step so the TPOT EWMAs
    # separate decisively from one seed round
    faults.slow_engine(slow.engine, 0.05 if quick else 0.08)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        # seed round: one request per replica so every EWMA is measured
        fleet.generate_many(prompts[:3], 4, timeout=120)
        reqs = []
        for p in prompts[3:]:
            reqs.append(fleet.submit(p, 6))
            time.sleep(0.02 if quick else 0.03)
        fleet.wait(reqs, timeout=120)
    disp = {r.name: r.dispatches for r in fleet._replicas}
    ewma = {r.name: r.tpot_ewma for r in fleet._replicas}
    ok, detail = _fleet_checks(fleet, reqs)
    # "routed around": the straggler draws no more work than any fast
    # replica AND well under a fair share (a fast sibling may absorb
    # nearly everything — that is the router working, not failing)
    fast_min = min(v for k, v in disp.items() if k != slow.name)
    total = sum(disp.values())
    routed_around = (disp[slow.name] <= fast_min
                     and disp[slow.name] < total / len(disp))
    recovered = ok and routed_around
    fleet.stop()
    return {"faults_injected": 1, "faults_recovered": int(recovered),
            "dispatches": disp,
            "tpot_ewma": {k: (None if v is None else round(v, 5))
                          for k, v in ewma.items()},
            "straggler": slow.name,
            "routed_around_straggler": bool(routed_around), **detail}


def _chaos_fleet_rolling_restart(ex, model, c, seed):
    """Drain + restart every replica in turn while requests keep
    arriving: zero accepted-rid loss, retrace counters flat (restarts
    reuse the shared compile-once program cache)."""
    import warnings
    from hetu_tpu.serving import EngineFleet

    rng = np.random.default_rng(seed + 33)
    prompts = _chaos_serve_prompts(rng, 9, c.vocab_size)
    baseline = _fleet_baseline(ex, model, prompts, 8, seed,
                               instance="base.restart")
    fleet = EngineFleet(ex, model, n_engines=3, engine_kwargs=_FLEET_EKW,
                        threaded=False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        reqs = [fleet.submit(p, 8) for p in prompts[:5]]
        fleet.pump(2)
        fleet.rolling_restart()
        reqs += [fleet.submit(p, 8) for p in prompts[5:]]
        fleet.wait(reqs, timeout=120)
    trace = fleet.trace_counts()
    s = fleet.stats()
    ok, detail = _fleet_checks(fleet, reqs, baseline)
    incs = {k: v["incarnation"] for k, v in s["engines"].items()}
    recovered = (ok and all(v >= 1 for v in incs.values())
                 and trace == {"prefill": 1, "step": 1})
    fleet.stop()
    return {"faults_injected": 3, "faults_recovered":
                3 * int(recovered),
            "incarnations": incs, "trace_counts": trace,
            "failovers": s["failovers"], **detail}


def _chaos_fleet_burst_failover(ex, model, c, seed, quick):
    """Arrival burst against bounded per-replica queues, then kill the
    replica with the deepest backlog: queued AND running requests all
    fail over; rejected requests were never accepted (honest shed, not
    loss)."""
    import warnings
    from hetu_tpu.resilience import faults
    from hetu_tpu.serving import EngineFleet, EngineOverloaded

    rng = np.random.default_rng(seed + 44)
    n_burst = 18 if quick else 36
    prompts = _chaos_serve_prompts(rng, n_burst, c.vocab_size)
    ekw = dict(_FLEET_EKW, max_queue=4)
    fleet = EngineFleet(ex, model, n_engines=3, engine_kwargs=ekw,
                        threaded=False, breaker_base=1e-4,
                        max_failovers=5)
    accepted, rejected = [], 0
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for p in prompts:
            try:
                accepted.append(fleet.submit(p, 6))
            except EngineOverloaded:
                rejected += 1
        fleet.pump(2)
        victim = max(fleet._replicas,
                     key=lambda r: len(r.engine.scheduler.queue)
                     + len(r.inflight))
        backlog = len(victim.inflight) \
            + len(victim.engine.scheduler.queue)
        faults.crash_engine(victim.engine)
        fleet.wait(accepted, timeout=240)
    s = fleet.stats()
    ok, detail = _fleet_checks(fleet, accepted)
    recovered = ok and s["failovers"] >= 1
    fleet.stop()
    return {"faults_injected": 1, "faults_recovered": int(recovered),
            "burst_size": n_burst, "accepted": len(accepted),
            "rejected": rejected,
            "victim_backlog_at_crash": backlog,
            "failovers": s["failovers"], **detail}


def _chaos_fleet_slo_controller(ex, model, c, seed):
    """Replica crash under the SLO controller, mid-burst: predictive
    admission sheds provably-infeasible work with a typed SLOReject
    BEFORE it takes a slot, the controller scales up through the same
    supervised machinery the crash exercises, and every ACCEPTED rid
    still reaches a terminal finish — the control plane never costs
    correctness."""
    import warnings
    from hetu_tpu.resilience import faults
    from hetu_tpu.serving import (EngineFleet, FleetController, SLO,
                                  SLOReject)

    rng = np.random.default_rng(seed)
    clk = _IterClock()
    fleet = EngineFleet(ex, model, n_engines=1, engine_kwargs=_SLO_EKW,
                        threaded=False, clock=clk, breaker_base=1e-4,
                        name="chaos_slo", replica_prefix="k")
    ctl = FleetController(fleet, SLO(deadline_miss_target=0.05),
                          min_engines=1, max_engines=3,
                          scale_up_queue=2.0, cooldown_s=0.5)
    prompts = _chaos_serve_prompts(rng, 16, c.vocab_size)
    reqs, doomed, sheds = [], [], []
    crashed = False
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for it in range(1200):
            if it < len(prompts):
                # one arrival per iteration: a burst one replica can't
                # absorb, plus two DOOMED deadlines once the cost
                # model has at least one finished request to learn from
                is_doomed = it in (11, 13)
                try:
                    freq = ctl.submit(prompts[it], 8,
                                      ttl=0.01 if is_doomed else 30.0)
                    (doomed if is_doomed else reqs).append(freq)
                except SLOReject as e:
                    sheds.append(e)
            fleet.pump()
            ctl.tick()
            clk.advance(_SLO_DT)
            if not crashed and ctl.scale_ups >= 1 \
                    and it >= len(prompts):
                victim = max(fleet._replicas,
                             key=lambda r: len(r.inflight))
                if victim.engine is not None:
                    faults.crash_engine(victim.engine)
                    crashed = True
            if crashed and it > len(prompts) + 10 and fleet.idle:
                break
    ok, detail = _fleet_checks(fleet, reqs)
    # a doomed request that slipped past admission must still reach a
    # TERMINAL state (deadline) — shed-vs-expire changes efficiency,
    # never bookkeeping
    doomed_terminal = all(r.finished for r in doomed)
    recovered = (ok and crashed and doomed_terminal
                 and ctl.scale_ups >= 1 and len(sheds) >= 1
                 and all(isinstance(e, SLOReject) for e in sheds))
    fleet.stop()
    ctl.stop()
    return {"faults_injected": 1, "faults_recovered": int(recovered),
            "crashed_replica": crashed,
            "scale_ups": ctl.scale_ups,
            "admission_sheds": len(sheds),
            "doomed_admitted": len(doomed),
            "accepted": len(reqs) + len(doomed), **detail}


#: paged replicas for the KV-migration chaos stages — page migration is
#: a block-table splice, so the dense-slot _FLEET_EKW can't carry it;
#: n_slots=4 leaves receivers FREE slots to adopt into
_MIG_FLEET_EKW = dict(_FLEET_EKW, n_slots=4, paged=True, page_len=4)


def _chaos_fleet_transfer_drop(ex, model, c, seed):
    """Every migration blob vanishes in flight (dropped frames): page
    migration fails LOUDLY — TransferError, migrate_failed incident,
    counted failure — and teacher-forced replay takes over with zero
    accepted-rid loss and the same bitwise streams."""
    import warnings
    from hetu_tpu.resilience import faults
    from hetu_tpu.serving import EngineFleet

    rng = np.random.default_rng(seed + 55)
    prompts = _chaos_serve_prompts(rng, 4, c.vocab_size)
    baseline = _fleet_baseline(ex, model, prompts, 10, seed,
                               instance="base.tdrop",
                               ekw=_MIG_FLEET_EKW)
    fleet = EngineFleet(ex, model, n_engines=3,
                        engine_kwargs=_MIG_FLEET_EKW, threaded=False,
                        breaker_base=1e-4)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        reqs = [fleet.submit(p, 10) for p in prompts]
        fleet.pump(3)
        # each injector drops the FIRST transfer it sees, so a stack of
        # them swallows every blob this stage can produce
        for _ in range(8):
            faults.drop_transfer(fleet, at=0)
        victim = max(fleet._replicas, key=lambda r: len(r.inflight))
        in_flight = len(victim.inflight)
        faults.crash_engine(victim.engine)
        fleet.wait(reqs, timeout=240)
    s = fleet.stats()
    ok, detail = _fleet_checks(fleet, reqs, baseline)
    recovered = (ok and s["migrations"] == 0
                 and s["migration_failures"] >= 1
                 and s["failovers"] >= in_flight)
    fleet.stop()
    return {"faults_injected": 1, "faults_recovered": int(recovered),
            "in_flight_at_crash": in_flight,
            "migrations": s["migrations"],
            "migration_failures": s["migration_failures"],
            "failovers": s["failovers"], **detail}


def _chaos_fleet_transfer_corrupt(ex, model, c, seed):
    """Every migration blob takes a flipped byte mid-wire: the CRC32
    frame rejects it (no silently-adopted garbage pages) and replay
    restores the streams bitwise."""
    import warnings
    from hetu_tpu.resilience import faults
    from hetu_tpu.serving import EngineFleet

    rng = np.random.default_rng(seed + 66)
    prompts = _chaos_serve_prompts(rng, 4, c.vocab_size)
    baseline = _fleet_baseline(ex, model, prompts, 10, seed,
                               instance="base.tcorrupt",
                               ekw=_MIG_FLEET_EKW)
    fleet = EngineFleet(ex, model, n_engines=3,
                        engine_kwargs=_MIG_FLEET_EKW, threaded=False,
                        breaker_base=1e-4)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        reqs = [fleet.submit(p, 10) for p in prompts]
        fleet.pump(3)
        # corrupted bytes flow through the whole filter chain, so each
        # injector must target a DISTINCT transfer index — and an even
        # stack of same-byte XOR flips on one blob would cancel out
        for i in range(8):
            faults.corrupt_transfer(fleet, at=i)
        victim = max(fleet._replicas, key=lambda r: len(r.inflight))
        in_flight = len(victim.inflight)
        faults.crash_engine(victim.engine)
        fleet.wait(reqs, timeout=240)
    s = fleet.stats()
    ok, detail = _fleet_checks(fleet, reqs, baseline)
    recovered = (ok and s["migrations"] == 0
                 and s["migration_failures"] >= 1
                 and s["failovers"] >= in_flight)
    fleet.stop()
    return {"faults_injected": 1, "faults_recovered": int(recovered),
            "in_flight_at_crash": in_flight,
            "migrations": s["migrations"],
            "migration_failures": s["migration_failures"],
            "failovers": s["failovers"], **detail}


def _chaos_fleet_donor_crash(ex, model, c, seed):
    """The donor dies MID-MIGRATION (scale-down drain): the first blob
    never lands (the wire died with the donor) and the stream it
    carried re-homes by replay off the corpse's quarantine; later
    streams still escape by page migration — the donor's host-side
    state outlives its wedged device step."""
    import warnings
    from hetu_tpu.resilience import faults
    from hetu_tpu.serving import EngineFleet

    rng = np.random.default_rng(seed + 77)
    prompts = _chaos_serve_prompts(rng, 4, c.vocab_size)
    baseline = _fleet_baseline(ex, model, prompts, 10, seed,
                               instance="base.tdonor",
                               ekw=_MIG_FLEET_EKW)
    fleet = EngineFleet(ex, model, n_engines=3,
                        engine_kwargs=_MIG_FLEET_EKW, threaded=False,
                        breaker_base=1e-4)
    state = {"fired": False}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        reqs = [fleet.submit(p, 10) for p in prompts]
        fleet.pump(3)
        victim = max(fleet._replicas, key=lambda r: len(r.inflight))
        in_flight = len(victim.inflight)

        def die_mid_transfer(blob):
            if not state["fired"]:
                state["fired"] = True
                faults.crash_engine(victim.engine)
                return None     # the wire died with the donor
            return blob

        fleet.transfer_filter = die_mid_transfer
        fleet.drain(victim.name, wait=False, migrate=True)
        fleet.wait(reqs, timeout=240)
    s = fleet.stats()
    ok, detail = _fleet_checks(fleet, reqs, baseline)
    recovered = (ok and state["fired"]
                 and s["migration_failures"] >= 1)
    fleet.stop()
    return {"faults_injected": 1, "faults_recovered": int(recovered),
            "in_flight_at_drain": in_flight,
            "donor_crashed_mid_transfer": bool(state["fired"]),
            "migrations": s["migrations"],
            "migration_failures": s["migration_failures"],
            "failovers": s["failovers"], **detail}


def run_chaos_fleet(quick=False, seed=0):
    import jax

    ex, model, c = _serve_build(True)   # tiny decode model: replica
    # lifecycle, not shapes, is the thing measured
    probe = _PlaneProbe("chaos_fleet")
    stages = {}
    # the engine-crash fault class under the plane probe: the killed
    # replica must fire engine_crashes alone, and the lost capacity
    # must land in failover_replay (replayed tokens priced at the
    # measured per-token decode cost)
    stages["engine_crash"] = probe.stage(
        "engine_crashes", "failover_replay",
        ("guard_trips", "migration_failures", "overload_shed"),
        _chaos_fleet_engine_crash, ex, model, c, seed)
    stages["engine_wedge"] = _staged(_chaos_fleet_engine_wedge, ex,
                                     model, c, seed, quick)
    stages["slow_engine"] = _staged(_chaos_fleet_slow_engine, ex, model,
                                    c, seed, quick)
    stages["rolling_restart"] = _staged(_chaos_fleet_rolling_restart,
                                        ex, model, c, seed)
    stages["burst_failover"] = _staged(_chaos_fleet_burst_failover, ex,
                                       model, c, seed, quick)
    stages["slo_controller"] = _staged(_chaos_fleet_slo_controller, ex,
                                       model, c, seed)
    # the transfer-fault class: dropped migration blobs must fire
    # migration_failures and charge the kv_migration bucket (the failed
    # attempts' wire time).  The stage ALSO crashes the donor on
    # purpose — engine_crashes legitimately co-fires, so only the two
    # truly-unrelated fault rules are asserted quiet.
    stages["transfer_drop"] = probe.stage(
        "migration_failures", "kv_migration",
        ("guard_trips", "overload_shed"),
        _chaos_fleet_transfer_drop, ex, model, c, seed)
    stages["transfer_corrupt"] = _staged(_chaos_fleet_transfer_corrupt,
                                         ex, model, c, seed)
    stages["donor_crash_mid_migration"] = _staged(
        _chaos_fleet_donor_crash, ex, model, c, seed)
    out = {"metric": "chaos_fleet_resilience",
           "value": sum(s["faults_recovered"] for s in stages.values()),
           "unit": "faults_recovered",
           "seed": seed,
           "quick": bool(quick),
           "platform": jax.default_backend(),
           "stages": stages,
           "slot_audit_balanced": all(
               s.get("slot_audit_balanced", True)
               for s in stages.values()),
           "zero_accepted_loss": all(
               s.get("all_terminal", True) for s in stages.values()),
           "single_engine_twin_lost_streams":
               stages["engine_crash"]["single_engine_twin"]
               ["lost_in_flight_streams"]}
    out["all_stages_recovered"] = all(
        s["faults_recovered"] >= s["faults_injected"]
        for s in stages.values())
    return out


def run_chaos_serve(quick=False, seed=0):
    import jax

    ex, model, c = _serve_build(True)   # tiny decode model: the faults,
    # not the shapes, are the thing measured — full mode only widens the
    # burst
    stages = {}
    stages["nan_decode"] = _staged(_chaos_serve_nan_decode, ex, model,
                                   c, seed)
    stages["raising_step"] = _staged(_chaos_serve_raising_step, ex,
                                     model, c, seed)
    stages["slot_leak"] = _staged(_chaos_serve_slot_leak, ex, model, c,
                                  seed)
    stages["stalled_consumer"] = _staged(_chaos_serve_stalled_consumer,
                                         ex, model, c, seed, quick)
    # the overload fault class under the plane probe: the 4x burst must
    # fire overload_shed alone, and the refused capacity must land in
    # brownout_shed (rejections priced at the measured mean request
    # cost, carved from the idle residual)
    probe = _PlaneProbe("chaos_serve")
    stages["overload_burst"] = probe.stage(
        "overload_shed", "brownout_shed",
        ("guard_trips", "engine_crashes", "migration_failures"),
        _chaos_serve_overload, ex, model, c, seed, quick)
    stages["deadline_cancel"] = _staged(_chaos_serve_deadline_cancel,
                                        ex, model, c, seed)
    audits = [s["slot_audit"] for s in stages.values()
              if "slot_audit" in s]
    out = {"metric": "chaos_serve_resilience",
           "value": sum(s["faults_recovered"] for s in stages.values()),
           "unit": "faults_recovered",
           "seed": seed,
           "quick": bool(quick),
           "platform": jax.default_backend(),
           "stages": stages,
           "slot_audit_balanced": all(
               a["allocs"] == a["frees"] and a["in_use"] == 0
               for a in audits)}
    out["all_stages_recovered"] = all(
        s["faults_recovered"] >= s["faults_injected"]
        for s in stages.values())
    return out


STAGES = {"bert": bench_bert, "gpt": bench_gpt_layer,
          "gpt_e2e": bench_gpt_e2e, "llama": bench_llama,
          "resnet": bench_resnet, "moe": bench_moe, "wdl": bench_wdl,
          "wdl_ps": bench_wdl_ps}

# run order: headline first, then the contested perf metrics (VERDICT r4
# items 2-4), then the rest — so a driver timeout preserves the numbers
# that matter most.  extra_metrics keeps the historical order regardless.
STAGE_ORDER = ["bert", "wdl", "resnet", "gpt", "gpt_e2e", "llama", "moe",
               "wdl_ps"]
EXTRA_ORDER = ["gpt", "gpt_e2e", "llama", "resnet", "moe", "wdl",
               "wdl_ps"]

# per-stage wall-clock ceilings (seconds, one attempt).  Round 4's
# uniform 1500 s x 2 attempts x 8 stages had a 6.5 h worst case — the
# driver budget fired first and, with output only at the very end,
# captured NOTHING (BENCH_r04 rc=124, empty tail).  These are sized
# ~2-3x the observed stage times.
STAGE_TIMEOUTS = {"bert": 900, "wdl": 900, "resnet": 700, "gpt": 700,
                  "gpt_e2e": 600, "llama": 600, "moe": 500,
                  "wdl_ps": 700}


DETAIL_PATH = os.environ.get(
    "HETU_BENCH_JSON",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "BENCH_FULL.json"))

#: hard cap on the FINAL stdout line (the driver keeps ~1500 bytes of
#: tail; everything bigger lives in the *_FULL.json detail files)
COMPACT_LINE_BUDGET = 1500


def _print_compact(compact, drop_order=()):
    """Print the final compact line, hard-capped at
    ``COMPACT_LINE_BUDGET`` bytes: optional keys are dropped in
    ``drop_order``, then per-stage optional short fields, until it
    fits — the full detail is already on disk, so trimming the tail
    line loses nothing."""
    line = json.dumps(compact)
    for key in drop_order:
        if len(line.encode()) <= COMPACT_LINE_BUDGET:
            break
        compact.pop(key, None)
        line = json.dumps(compact)
    if (len(line.encode()) > COMPACT_LINE_BUDGET
            and isinstance(compact.get("stages"), dict)):
        for entry in compact["stages"].values():
            if isinstance(entry, dict):
                entry.pop("rd", None)
                entry.pop("hg", None)
        line = json.dumps(compact)
    print(line, flush=True)


def _emit(results, budget_note=None, telemetry_overhead=None):
    """Emit the round's evidence in layers sized to the driver's
    ~1500-byte stdout tail (ADVICE r5: the full 8-stage headline
    overflows it and r05 parsed null).  Called after EVERY stage, so any
    prefix of a run ends in complete parseable evidence (VERDICT r4
    item 1):

    - the FULL headline (baselines, round_ratios, device traces) goes to
      an EARLIER stdout line and to ``BENCH_FULL.json``;
    - the LAST line is a compact per-stage summary — abbreviated keys
      (v=value, u=unit, r=vs_baseline, rd=vs_baseline_device,
      hg=host_gap) keep 8 stages inside the window."""
    def get(stage):
        r = results.get(stage)
        if r is None:
            return {"metric": stage, "value": None, "unit": "PENDING",
                    "vs_baseline": None}
        return r

    headline = dict(get("bert"))
    headline["extra_metrics"] = [get(s) for s in EXTRA_ORDER]
    if budget_note:
        headline["budget"] = budget_note
    if telemetry_overhead is not None:
        headline["telemetry_overhead"] = telemetry_overhead
    full = json.dumps(headline)
    # Never clobber BENCH_FULL.json with the all-PENDING placeholder: the
    # second-0 emit (and an aborted run that never finishes a stage) must
    # not destroy the previous round's committed evidence.  The detail
    # file is written only once at least one stage has reported; until
    # then the parseable line lives on stdout alone.
    if results:
        try:
            with open(DETAIL_PATH, "w") as f:
                f.write(full + "\n")
        except OSError:
            pass
    print(full, flush=True)
    compact = {"metric": headline.get("metric"),
               "value": headline.get("value"),
               "unit": headline.get("unit"),
               "vs_baseline": headline.get("vs_baseline"),
               "stages": {}}
    for s in STAGE_ORDER:
        r = get(s)
        entry = {"v": r.get("value"), "u": r.get("unit"),
                 "r": r.get("vs_baseline")}
        for k, short in (("vs_baseline_device", "rd"),
                         ("host_gap", "hg")):
            if r.get(k) is not None:
                entry[short] = r[k]
        compact["stages"][s] = entry
    if budget_note:
        compact["budget"] = budget_note
    if telemetry_overhead is not None:
        compact["telemetry_overhead_frac"] = telemetry_overhead.get(
            "overhead_frac")
    compact["detail"] = os.path.basename(DETAIL_PATH)
    _print_compact(compact, drop_order=("telemetry_overhead_frac",))


#: exit code of a full-size run that found no tpu (parent and stage child)
WRONG_PLATFORM_EXIT = 3


def _start_jax(quick):
    """First jax contact of every in-process mode and every stage child:
    place the compile cache, then refuse a full-size run anywhere but on a
    tpu.  ``--quick`` is the only size a CPU may run, and it is asked for,
    never inferred."""
    import jax
    from hetu_tpu.platform import enable_compile_cache
    enable_compile_cache()
    found = jax.default_backend()
    if not quick and found != "tpu":
        sys.stderr.write(
            f"bench.py: a full-size run needs a tpu, found {found!r}; "
            "pass --quick for the CPU smoke sizes\n")
        raise SystemExit(WRONG_PLATFORM_EXIT)


def main():
    quick = "--quick" in sys.argv
    telemetry_on = "--telemetry" in sys.argv
    if "--telemetry-overhead" in sys.argv:
        # measured-overhead twin as its own child process (the parent
        # never touches jax in stage mode)
        _start_jax(quick)
        print(json.dumps(run_telemetry_overhead(quick)), flush=True)
        return
    if "--chaos" in sys.argv:
        # chaos mode runs in-process (small shapes; no per-stage HBM
        # pressure): inject faults mid-stage, report recovery + guard
        # overhead.  Same platform selection as stage children.
        # --chaos --serve injects the SERVING fault classes through the
        # continuous-batching engine instead (same CHAOS_FULL.json
        # contract).  --chaos --elastic adds the kill-a-chip stage,
        # which needs a 2-device mesh — force host devices on CPU
        # BEFORE jax initializes its backends (no-op on a real pod).
        if "--elastic" in sys.argv:
            flag = "--xla_force_host_platform_device_count=8"
            if flag not in os.environ.get("XLA_FLAGS", ""):
                os.environ["XLA_FLAGS"] = (
                    os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
        _start_jax(quick)
        if telemetry_on:
            _telemetry_on()
        detail_path = None
        if "--fleet" in sys.argv:
            # --chaos --serve --fleet: whole-replica failures through
            # the EngineFleet (FLEET_FULL.json, same no-clobber rules)
            out = run_chaos_fleet(quick)
            detail_path = FLEET_DETAIL_PATH
        elif "--serve" in sys.argv:
            out = run_chaos_serve(quick)
        else:
            out = run_chaos(quick,
                            elastic="--elastic" in sys.argv)
        if telemetry_on:
            # unprotected "twin." engines die/wedge by design — every
            # OTHER accepted rid must show a complete stitched timeline
            out["telemetry"] = _telemetry_report(
                exclude_rids=("twin.",))
            _assert_rid_audit(out["telemetry"])
            out["telemetry_overhead"] = run_telemetry_overhead(quick)
        _emit_chaos(out, detail_path)
        return
    if "--profile" in sys.argv:
        # profile mode runs in-process: XLA cost/memory capture for the
        # train/serve/embed programs + derived MFU/roofline/HBM signals
        # into PROFILE_FULL.json and benchmarks/history.jsonl.
        # Profiling needs the live registry, so telemetry is enabled
        # unconditionally here (no separate --telemetry required).
        _start_jax(quick)
        _telemetry_on()
        out = run_profile(quick)
        out["telemetry"] = _telemetry_report()
        _emit_profile(out)
        return
    if "--plan" in sys.argv:
        # plan mode runs in-process: calibrate measured LayerProfiles,
        # run the Galvatron search, persist the profile + plan
        # artifacts, execute the emitted plan end-to-end and gate the
        # predicted-vs-measured iteration-time error (plan_pred_err).
        _start_jax(quick)
        out = run_plan(quick)
        _emit_plan(out)
        return
    if "--slo" in sys.argv:
        # SLO control-plane mode runs in-process: the seeded bursty
        # diurnal trace through a FleetController-supervised fleet vs
        # its static twin, on a shared virtual clock.  Telemetry is on
        # unconditionally — the incident + rid-audit evidence IS the
        # acceptance criterion.
        _start_jax(quick)
        _telemetry_on()
        out = run_slo(quick)
        out["telemetry"] = _telemetry_report()
        _assert_rid_audit(out["telemetry"])
        _emit_slo(out)
        return
    if "--serve-embed" in sys.argv:
        # embedding-serve mode runs in-process (host tables + a tiny
        # dense scorer): replay the Zipfian key trace through the
        # tiered EmbeddingServer + uncached host-tier twin.
        _start_jax(quick)
        if telemetry_on:
            _telemetry_on()
        out = run_serve_embed(quick)
        if telemetry_on:
            out["telemetry"] = _telemetry_report()
            _assert_rid_audit(out["telemetry"])
            out["telemetry_overhead"] = run_telemetry_overhead(quick)
        _emit_embed(out)
        return
    if "--serve" in sys.argv:
        # serve mode runs in-process (small decode shapes): replay the
        # arrival trace through the continuous engine + static twin.
        # --serve --tp N runs the tensor-parallel twin stage instead.
        tp = (int(sys.argv[sys.argv.index("--tp") + 1])
              if "--tp" in sys.argv else 1)
        if tp > 1 or "--kv-dtype" in sys.argv:
            # the forced host-device flag must be in the env BEFORE jax
            # initializes its backends; it only multiplies the CPU
            # platform's device count, so it is a no-op on a real TPU
            # (--kv-dtype needs it too: its TP-gather sub-stage builds a
            # tp=2 mesh)
            flag = "--xla_force_host_platform_device_count=8"
            if flag not in os.environ.get("XLA_FLAGS", ""):
                os.environ["XLA_FLAGS"] = (
                    os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
        _start_jax(quick)
        if telemetry_on:
            _telemetry_on()
        if "--migrate" in sys.argv:
            # --serve --fleet --migrate: live KV page migration A/B vs
            # the teacher-forced replay oracle (MIGRATE_FULL.json)
            out = run_serve_migrate(quick)
            if telemetry_on:
                out["telemetry"] = _telemetry_report()
                _assert_rid_audit(out["telemetry"])
            _emit_serve_migrate(out)
            return
        if "--spec" in sys.argv:
            out = run_serve_spec(quick)
            if telemetry_on:
                out["telemetry"] = _telemetry_report()
                _assert_rid_audit(out["telemetry"])
            _emit_serve_spec(out)
            return
        if "--kv-dtype" in sys.argv:
            kvd = sys.argv[sys.argv.index("--kv-dtype") + 1]
            out = run_serve_quant(quick, kv_dtype=kvd)
            if telemetry_on:
                out["telemetry"] = _telemetry_report()
                _assert_rid_audit(out["telemetry"])
            _emit_serve_quant(out)
            return
        if tp > 1:
            out = run_serve_tp(quick, tp)
            if telemetry_on:
                out["telemetry"] = _telemetry_report()
                _assert_rid_audit(out["telemetry"])
            _emit_serve_tp(out)
            return
        out = run_serve(quick)
        if telemetry_on:
            out["telemetry"] = _telemetry_report()
            _assert_rid_audit(out["telemetry"])
            out["telemetry_overhead"] = run_telemetry_overhead(quick)
        _emit_serve(out)
        return
    if "--stage" in sys.argv:
        # only stage children may touch jax: the backend check in the
        # PARENT would acquire the TPU exclusively and starve them
        _start_jax(quick)
        stage = sys.argv[sys.argv.index("--stage") + 1]
        if telemetry_on:
            _telemetry_on()
            out = STAGES[stage](quick)
            out["telemetry"] = _telemetry_report()
        else:
            out = STAGES[stage](quick)
        print(json.dumps(out))
        return
    # each stage in its own process: ours + the flax baseline together
    # exceed one chip's HBM at the BERT headline shapes, and a fresh
    # process returns the chip clean for the next stage.  This parent
    # never imports jax: a parent that has touched jax holds the chip and
    # its children could not have it.  One retry per stage if the budget
    # allows; a stage that still fails is reported as FAILED rather than
    # sinking the whole benchmark.
    import subprocess
    t0 = time.time()
    # global wall-clock budget: once exceeded, remaining stages are
    # marked SKIPPED_BUDGET instead of run — a bounded, fully-reported
    # run beats an unbounded one the driver kills mid-flight
    budget = float(os.environ.get("HETU_BENCH_BUDGET_S", "3300"))
    results = {}
    _emit(results)                  # parseable line exists from second 0
    for stage in STAGE_ORDER:
        remaining = budget - (time.time() - t0)
        if remaining < 90:
            results[stage] = {"metric": stage, "value": None,
                              "unit": "SKIPPED_BUDGET",
                              "vs_baseline": None}
            continue
        cmd = [sys.executable, os.path.abspath(__file__), "--stage", stage]
        if quick:
            cmd.append("--quick")
        if telemetry_on:
            cmd.append("--telemetry")
        for attempt in (0, 1):
            # per-attempt timeout clamped to the REMAINING budget: a
            # stage that hangs must surface as a failed stage, and a
            # retry must not push the run past the budget it promises
            timeout = min(STAGE_TIMEOUTS.get(stage, 700),
                          max(90, budget - (time.time() - t0)))
            try:
                proc = subprocess.run(cmd, capture_output=True, text=True,
                                      timeout=timeout)
            except subprocess.TimeoutExpired:
                sys.stderr.write(f"stage {stage} timed out\n")
                break   # timeouts aren't transient; don't burn another slot
            if proc.returncode == 0:
                results[stage] = json.loads(
                    proc.stdout.strip().splitlines()[-1])
                break
            sys.stderr.write(proc.stderr[-2000:])
            if proc.returncode == WRONG_PLATFORM_EXIT:
                raise SystemExit(WRONG_PLATFORM_EXIT)
            if budget - (time.time() - t0) < timeout * 0.5:
                break   # not enough budget left for a meaningful retry
        if stage not in results:
            results[stage] = {"metric": stage, "value": None,
                              "unit": "FAILED", "vs_baseline": None}
        _emit(results)
    overhead = None
    if telemetry_on and budget - (time.time() - t0) > 60:
        # the measured-overhead line: telemetry-on vs -off twin in its
        # own child (same platform selection as the stages)
        cmd = [sys.executable, os.path.abspath(__file__),
               "--telemetry-overhead"]
        if quick:
            cmd.append("--quick")
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True,
                timeout=min(600, max(60, budget - (time.time() - t0))))
            if proc.returncode == 0:
                overhead = json.loads(
                    proc.stdout.strip().splitlines()[-1])
                print(json.dumps(overhead), flush=True)
            else:
                sys.stderr.write(proc.stderr[-2000:])
        except subprocess.TimeoutExpired:
            sys.stderr.write("telemetry-overhead twin timed out\n")
    elapsed = round(time.time() - t0, 1)
    skipped = [s for s in STAGE_ORDER
               if results[s].get("unit") == "SKIPPED_BUDGET"]
    _emit(results,
          {"budget_s": budget, "elapsed_s": elapsed,
           "skipped_stages": skipped} if skipped else
          {"budget_s": budget, "elapsed_s": elapsed},
          telemetry_overhead=overhead)


if __name__ == "__main__":
    main()
