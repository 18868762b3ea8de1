#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that hetu_tpu still starts on the chip.

One process drives the two hot paths once, through the entry points a user
calls (``import hetu_tpu as ht``, ``ht.Executor``, ``InferenceEngine``, the
``parallel/strategies.py`` strategies, ``serving_mesh``, ``EngineFleet``):

* trainer leg: BERT-base at its published size (hidden 768,
  12 layers, 12 heads, vocabulary 30,522, batch 64, sequence 512, bf16 over
  f32 masters, AdamW, dropout on with ``rng_impl="rbg"``), eight steps on
  one fixed batch;
* server leg: ``LlamaForCausalLM`` at the Llama-3-8B widths with the depth
  cut to four layers, seeded random weights, the paged engine with chunked
  prefill, eight requests of 100 to 1,500 prompt tokens and 32 new tokens
  each, twice;
* on a host with four devices, both legs again on all four: the trainer
  under ``MegatronLM(dp=2, tp=2)`` and ``DataParallel(ndev=4)``, the server
  under ``serving_mesh(4)`` and as an ``EngineFleet`` of four one-chip
  replicas.

It selects no platform and cuts no size.  When jax finds anything but a TPU
it says what it found and fails.  ``--cpu-rehearsal`` is the one exception,
asked for by name: the same control flow at toy sizes, every line labelled,
no result line, for debugging the script where there is no chip.

Every check that fails raises; nothing here catches it.  The last line of a
passing run is one JSON object naming the device as jax reports it.  Times
it prints are observations of this run, not performance figures.
"""

from __future__ import annotations

import argparse
import gc
import importlib.metadata
import importlib.util
import json
import math
import re
import sys
import time

REHEARSAL_TAG = "[REHEARSAL cpu toy-size] "

#: the Mosaic kernels a BERT train step must contain on the chip
FLASH_KERNELS = ("hetu_flash_fwd", "hetu_flash_bwd")
CE_KERNELS = ("hetu_softmax_ce_fwd", "hetu_softmax_ce_bwd")
DROPOUT_KERNELS = ("hetu_dropout_mask",)

FULL = {
    "bert": dict(batch=64, seq=512, config=dict(
        vocab_size=30522, hidden_size=768, num_hidden_layers=12,
        max_position_embeddings=512), steps=8),
    # models/llama.py LLAMA_CONFIGS["llama3-8b"], depth cut to 4 of 32: one
    # v5e holds 16 GB, and f32 weights at these widths are 0.87 GB a layer
    # beside 4.2 GB of embedding and head
    "llama": dict(widths="llama3-8b", layers=4,
                  engine=dict(paged=True, page_len=16, n_slots=8,
                              max_len=2048, max_prompt_len=1536,
                              prefill_token_budget=512),
                  prompt_lens=(100, 300, 520, 777, 1000, 1234, 1500, 150),
                  fleet_prompt_lens=(100, 120, 110, 127), max_new=32),
}

REHEARSAL = {
    "bert": dict(batch=8, seq=128, config=dict(
        vocab_size=2048, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=128,
        max_position_embeddings=128), steps=8),
    "llama": dict(widths=dict(vocab_size=512, hidden_size=64, num_heads=4,
                              num_kv_heads=4, intermediate_size=128,
                              rope_theta=500000.0), layers=2,
                  engine=dict(paged=True, page_len=16, n_slots=8,
                              max_len=256, max_prompt_len=192,
                              prefill_token_budget=64),
                  prompt_lens=(13, 38, 65, 97, 125, 154, 188, 19),
                  fleet_prompt_lens=(13, 15, 14, 16), max_new=8),
}


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


class Smoke:
    """What every leg needs: the sizes, a labelled printer and a checker
    that raises."""

    def __init__(self, rehearsal):
        self.rehearsal = rehearsal
        self.sizes = REHEARSAL if rehearsal else FULL
        self.tag = REHEARSAL_TAG if rehearsal else ""

    def say(self, msg=""):
        for line in str(msg).splitlines() or [""]:
            print(self.tag + line, flush=True)

    def check(self, ok, what):
        if not ok:
            raise SmokeFailure(what)
        self.say(f"  ok    {what}")


# -- what the registry and the devices say ----------------------------------

def counter(name, **labels):
    """Current value of one labelled series of a registry counter."""
    from hetu_tpu import telemetry
    metric = telemetry.get_registry().snapshot().get(name, {"samples": []})
    return sum(s["value"] for s in metric["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def kernel_choices():
    """``{(kernel, form, reason): count}`` of the trace-time choices
    between a Pallas kernel and its jnp form, so far."""
    from hetu_tpu.ops.pallas import dispatch
    return dispatch.choices()


def memory(devices):
    """Per device ``(bytes_in_use, peak_bytes_in_use)``, or None where the
    backend keeps no statistics (cpu)."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        if not stats:
            return None
        out.append((int(stats["bytes_in_use"]),
                    int(stats["peak_bytes_in_use"])))
    return out


def check_memory_rose(smoke, devices, before, during, what):
    """Every device held more while the leg's state was resident than
    before the leg.  The high-water mark is printed beside it: it can only
    rise where this leg needed more than any earlier leg did, so on a
    device an earlier leg filled, the live bytes are the evidence."""
    if before is None:
        smoke.say("  note  cpu keeps no memory statistics; not checked")
        return
    for d, (live0, peak0), (live1, peak1) in zip(devices, before, during):
        smoke.say(f"        device {d.id}: in use {live0 / 2**30:.2f} -> "
                  f"{live1 / 2**30:.2f} GiB, peak {peak0 / 2**30:.2f} -> "
                  f"{peak1 / 2**30:.2f} GiB")
        if not (live1 > live0 and peak1 >= live1):
            raise SmokeFailure(f"{what}: device {d.id} held no more memory "
                               "during the leg than before it")
    smoke.say(f"  ok    {what}: memory in use rose on all "
              f"{len(devices)} devices")


def check_peaks_rose(smoke, devices, before, after, what):
    """Over ``what`` as a whole, every device's high-water mark rose."""
    if before is None:
        smoke.say("  note  cpu keeps no memory statistics; not checked")
        return
    flat = [d.id for d, (_, p0), (_, p1) in zip(devices, before, after)
            if not p1 > p0]
    if flat:
        raise SmokeFailure(f"{what}: peak_bytes_in_use did not rise on "
                           f"device(s) {flat}")
    smoke.say(f"  ok    {what}: peak_bytes_in_use rose on every device: "
              + ", ".join(f"{d.id}: {p0 / 2**30:.2f} -> {p1 / 2**30:.2f} "
                          "GiB" for d, (_, p0), (_, p1)
                          in zip(devices, before, after)))


def mosaic_calls(hlo_text):
    """``{kernel name: [first operand shape, ...]}`` for every Mosaic
    custom call of a compiled program.  The name is the ``name=`` its
    ``pallas_call`` was given, which XLA keeps in the op metadata."""
    calls = {}
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.search(r'op_name="[^"]*?(hetu_\w+)', line)
        shape = re.search(r"operand_layout_constraints=\{(\w+\[[\d,]*\])",
                          line)
        calls.setdefault(name.group(1) if name else "unnamed", []).append(
            shape.group(1) if shape else "?")
    return calls


def shard_shapes(array):
    return sorted((s.device.id, tuple(s.data.shape))
                  for s in array.addressable_shards)


# -- trainer ----------------------------------------------------------------

def trainer_leg(smoke, label, strategy=None, expect=None):
    """BERT pretraining steps through ``ht.Executor``.  ``strategy`` is a
    ``parallel/strategies.py`` strategy or None (one chip); ``expect``
    describes the layout it must produce (four-chip legs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import hetu_tpu as ht
    from hetu_tpu.models import BertConfig, BertForPreTraining

    smoke.say(f"== trainer leg: {label}")
    z = smoke.sizes["bert"]
    B, S, steps = z["batch"], z["seq"], z["steps"]
    c = BertConfig(seq_len=S, **z["config"])
    devices = (list(strategy.mesh.devices.flat) if strategy is not None
               else jax.devices()[:1])
    mem0 = memory(devices)
    choices0 = kernel_choices()
    traces0 = counter("hetu_executor_retraces_total", subgraph="train")

    input_ids = ht.placeholder_op("input_ids", (B, S), dtype=np.int32)
    token_type = ht.placeholder_op("token_type_ids", (B, S), dtype=np.int32)
    attn_mask = ht.placeholder_op("attention_mask", (B, S))
    mlm_labels = ht.placeholder_op("mlm_labels", (B * S,), dtype=np.int32)
    nsp_labels = ht.placeholder_op("nsp_labels", (B,), dtype=np.int32)
    model = BertForPreTraining(c)
    loss = model.loss(input_ids, token_type, attn_mask, mlm_labels,
                      nsp_labels)
    opt = ht.AdamWOptimizer(learning_rate=1e-4, weight_decay=0.01)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                     compute_dtype=jnp.bfloat16, rng_impl="rbg",
                     dist_strategy=strategy)
    n_params = sum(int(np.prod(v.shape)) for v in ex.params.values())
    smoke.say(f"  BERT hidden {c.hidden_size}, {c.num_hidden_layers} layers, "
              f"{c.num_attention_heads} heads, vocabulary {c.vocab_size}, "
              f"batch {B}, sequence {S}, {n_params / 1e6:.1f} M parameters, "
              "bf16 compute over f32 masters, AdamW, dropout on (rbg)")

    rng = np.random.default_rng(0)
    mlm = np.full((B * S,), -1, np.int64)
    masked = rng.random(B * S) < 0.15
    mlm[masked] = rng.integers(0, c.vocab_size, masked.sum())
    batch = {input_ids: rng.integers(0, c.vocab_size, (B, S)),
             token_type: rng.integers(0, 2, (B, S)),
             attn_mask: np.ones((B, S), np.float32),
             mlm_labels: mlm,
             nsp_labels: rng.integers(0, 2, (B,))}
    # one fixed batch, placed the way an input pipeline places it: with the
    # sharding the compiled step expects
    with ht.prefetch_feeds(ex, [batch], "train", depth=1) as pf:
        feed = next(pf)

    losses, times = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = ex.run("train", feed_dict=feed, convert_to_numpy_ret_vals=True)
        times.append(time.perf_counter() - t0)
        losses.append(float(out[0]))
        if len(losses) == 1:
            traces1 = counter("hetu_executor_retraces_total",
                              subgraph="train")
            mem1 = memory(devices)
    smoke.say("  losses " + " ".join(f"{x:.4f}" for x in losses))
    smoke.say(f"  observed: first step {times[0]:.1f} s with compilation, "
              f"later steps {1e3 * min(times[1:]):.1f} to "
              f"{1e3 * max(times[1:]):.1f} ms each (host clock around a "
              "step that returns the loss)")

    uniform = math.log(c.vocab_size) + math.log(2)
    smoke.check(uniform - 1.0 < losses[0] < uniform + 1.0,
                f"first loss {losses[0]:.3f} within 1.0 of ln {c.vocab_size}"
                f" + ln 2 = {uniform:.3f}")
    smoke.check(all(math.isfinite(x) for x in losses),
                "every loss is finite")
    smoke.check(losses[-1] < losses[0],
                f"last loss {losses[-1]:.3f} below the first")
    traces = counter("hetu_executor_retraces_total", subgraph="train")
    smoke.check(traces1 - traces0 == 1 and traces == traces1,
                "the step was traced and compiled once, and not again "
                f"after the first step ({steps} steps)")

    choices = {k: n - choices0.get(k, 0)
               for k, n in kernel_choices().items() if n > choices0.get(k, 0)}
    smoke.say("  kernel choices while tracing (kernel, form, reason): "
              + ", ".join(f"{k}={n}" for k, n in sorted(choices.items())))
    check_kernels(smoke, ex, feed, choices, strategy, c, B)
    check_memory_rose(smoke, devices, mem0, mem1, label)
    if expect is not None:
        check_layout(smoke, ex, feed, input_ids, expect)
    ex.close()


def check_kernels(smoke, ex, feed, choices, strategy, c, batch):
    """The Pallas kernels are in the compiled step, as Mosaic calls on the
    local shard's shape, and no kernel gave way to jnp unannounced."""
    axes = dict(strategy.mesh.shape) if strategy is not None else {}
    dp, tp = axes.get("dp", 1), axes.get("tp", 1)
    # the 2-class NSP head is below the softmax-CE kernel's 1024-class
    # floor by design; under a tp axis the MLM head's vocabulary is sharded
    # and the loss keeps its jnp form, and so does the dropout mask, which
    # the shards of a replicated activation must agree on
    allowed = {("softmax_ce", "jnp", "vocab<1024")}
    if tp > 1:
        allowed |= {(k, "jnp", f"mesh_axis:tp={tp}")
                    for k in ("softmax_ce", "dropout")}
    if smoke.rehearsal:
        allowed |= {(k, "jnp", "platform:cpu")
                    for k in ("flash_attention", "dropout")}
    fallbacks = {k: n for k, n in choices.items() if k[1] == "jnp"}
    unexpected = {k: n for k, n in fallbacks.items() if k not in allowed}
    smoke.check(not unexpected,
                f"jnp fallbacks on the path: {sum(unexpected.values())} "
                f"unexpected, {sum(fallbacks.values())} expected "
                f"({sorted(k[0] + ':' + k[2] for k in fallbacks)})")
    if smoke.rehearsal:
        smoke.say("  note  cpu has no Mosaic; the compiled step is not "
                  "searched for kernels")
        return
    t0 = time.perf_counter()
    hlo = ex.subexecutor["train"].lower_compiled(feed).as_text()
    calls = mosaic_calls(hlo)
    smoke.say(f"  Mosaic custom calls in the per-device program "
              f"(lowered again for reading in {time.perf_counter() - t0:.1f}"
              " s): " + ", ".join(f"{k} x{len(v)} on {v[0]}"
                                  for k, v in sorted(calls.items())))
    want = FLASH_KERNELS + (CE_KERNELS + DROPOUT_KERNELS if tp == 1 else ())
    for name in want:
        smoke.check(len(calls.get(name, ())) >= 1,
                    f"{name} is in the compiled step as a Mosaic call")
    # the kernel reads the projections' [batch, seq, hidden] in place
    q_shape = f"bf16[{batch // dp},{c.seq_len},{c.hidden_size // tp}]"
    smoke.check(set(calls["hetu_flash_fwd"]) == {q_shape},
                f"flash attention runs on the local shard {q_shape} "
                f"(batch/{dp}, hidden/{tp})")
    if tp == 1:
        smoke.check(all(s.endswith(f",{c.vocab_size}]")
                        for s in calls["hetu_softmax_ce_fwd"]),
                    "softmax-CE reads the whole vocabulary per row: "
                    f"{sorted(set(calls['hetu_softmax_ce_fwd']))}")


def check_layout(smoke, ex, feed, batch_node, expect):
    """Parameters and batch sit on the devices as the strategy names."""
    n_dev = expect["devices"]
    shapes = shard_shapes(feed[batch_node])
    smoke.say(f"  batch shards (device, shape): {shapes}")
    smoke.check(len({d for d, _ in shapes}) == n_dev
                and {s for _, s in shapes} == {expect["batch_shard"]},
                f"the batch is split {expect['batch_shard']} a device over "
                f"{n_dev} devices")
    sharded, replicated = 0, 0
    example = None
    for var in ex.variables:
        arr = ex.params[var.name]
        shards = shard_shapes(arr)
        if len({d for d, _ in shards}) != n_dev:
            raise SmokeFailure(f"{var.name} lives on {len(shards)} of "
                               f"{n_dev} devices")
        if var.dist_state is None or not var.dist_state.splits:
            replicated += 1
            if {s for _, s in shards} != {tuple(arr.shape)}:
                raise SmokeFailure(f"{var.name} should be replicated: "
                                   f"{shards}")
            continue
        sharded += 1
        (dim, axis), = var.dist_state.splits.items()
        want = list(arr.shape)
        want[dim] //= ex.mesh.shape[axis]
        if {s for _, s in shards} != {tuple(want)}:
            raise SmokeFailure(f"{var.name} should be split on dim {dim} "
                               f"over {axis}: {shards}")
        example = example or (var.name, tuple(arr.shape), tuple(want), axis)
    smoke.say(f"  parameters: {sharded} sharded, {replicated} replicated"
              + (f"; e.g. {example[0]} {example[1]} -> {example[2]} a "
                 f"device over '{example[3]}'" if example else ""))
    smoke.check((sharded > 0) == expect["sharded_params"],
                "parameters are " + ("sharded as MegatronLM names them"
                                     if expect["sharded_params"] else
                                     "replicated, as DataParallel keeps "
                                     "them") + ", on every device")


# -- server -----------------------------------------------------------------

def build_served_model(smoke):
    """``LlamaForCausalLM`` + the Executor that owns its weights."""
    import numpy as np
    import hetu_tpu as ht
    from hetu_tpu.models import LlamaConfig, LlamaForCausalLM
    from hetu_tpu.models.llama import LLAMA_CONFIGS

    z = smoke.sizes["llama"]
    widths = (LLAMA_CONFIGS[z["widths"]] if isinstance(z["widths"], str)
              else z["widths"])
    c = LlamaConfig(**{**widths, "num_layers": z["layers"],
                       "seq_len": z["engine"]["max_len"]})
    name = "smoke_llama"
    model = LlamaForCausalLM(c, name=name)
    ids = ht.placeholder_op(f"{name}_ids", (1, 4), dtype=np.int32)
    ex = ht.Executor([model(ids)], seed=0)
    weight_bytes = sum(int(v.nbytes) for v in ex.params.values())
    smoke.say(f"  Llama hidden {c.hidden_size}, {c.num_heads} heads over "
              f"{c.num_kv_heads} KV heads of size "
              f"{c.hidden_size // c.num_heads}, feed-forward "
              f"{c.intermediate_size}, vocabulary {c.vocab_size}, "
              f"rope_theta {c.rope_theta:g}; depth {c.num_layers} layers; "
              f"weights {weight_bytes / 2**30:.2f} GiB "
              f"({next(iter(ex.params.values())).dtype}), seeded random")
    return ex, model, name


def prompts_for(smoke, lens, vocab):
    import numpy as np
    rng = np.random.default_rng(1)
    return [rng.integers(1, vocab, (n,)).astype(np.int32) for n in lens]


def drain(step, reqs, limit):
    n = 0
    while not all(r.finished for r in reqs):
        if n >= limit:
            raise SmokeFailure(f"requests did not finish in {limit} "
                               "iterations")
        step()
        n += 1
    return n


def check_streams(smoke, reqs, max_new, vocab):
    reasons = sorted({r.finish_reason for r in reqs})
    # the engine's name for the length stop is "max_new"
    smoke.check(reasons == ["max_new"],
                f"every request ended with finish_reason {reasons} "
                "(the length stop)")
    smoke.check(all(len(r.tokens) == max_new
                    and all(0 <= t < vocab for t in r.tokens)
                    for r in reqs),
                f"every request returned exactly {max_new} tokens inside "
                "the vocabulary")


def server_leg(smoke, served, label, mesh=None, compare=None):
    """Eight requests through the paged engine, twice.  Returns the token
    streams of the first batch."""
    import numpy as np
    from hetu_tpu import telemetry
    from hetu_tpu.serving import InferenceEngine

    smoke.say(f"== server leg: {label}")
    ex, model, name = served
    z = smoke.sizes["llama"]
    vocab, max_new = model.config.vocab_size, z["max_new"]
    devices = (list(mesh.devices.flat) if mesh is not None
               else ex.params[f"{name}_embed_table"].devices())
    devices = sorted(devices, key=lambda d: d.id)
    mem0 = memory(devices)
    incidents0 = telemetry.get_flight().incident_count()
    mesh_kw = {} if mesh is None else {"mesh": mesh}
    # every other engine option at its default, the watchdog included
    eng = InferenceEngine(ex, model, name=name, **z["engine"], **mesh_kw)
    pool_bytes = sum(int(x.nbytes) for x in (eng.cache.k, eng.cache.v))
    smoke.say(f"  page pool {eng.cache.n_pages} pages of "
              f"{eng.cache.page_len} tokens, {pool_bytes / 2**30:.2f} GiB; "
              f"{eng.cache.n_slots} slots, max_len {eng.max_len}, prompts "
              f"up to {eng.max_prompt_len}, prefill budget "
              f"{eng.prefill_token_budget} tokens an iteration")
    prompts = prompts_for(smoke, z["prompt_lens"], vocab)
    limit = 50 * len(prompts) * max_new

    streams, mem1 = [], None
    for round_ in (1, 2):
        t0 = time.perf_counter()
        reqs = [eng.submit(p, max_new) for p in prompts]
        iters = drain(eng.step, reqs, limit)
        dt = time.perf_counter() - t0
        if mem1 is None:
            mem1 = memory(devices)
        smoke.say(f"  batch {round_}: {len(reqs)} requests, prompts "
                  f"{[int(p.size) for p in prompts]} tokens, {iters} "
                  f"iterations, {eng.prefill_chunks} prefill chunks so far; "
                  f"observed {dt:.1f} s"
                  + (" with compilation" if round_ == 1 else
                     f", {1e3 * dt / iters:.1f} ms an iteration"))
        check_streams(smoke, reqs, max_new, vocab)
        streams.append([list(r.tokens) for r in reqs])
    smoke.check(streams[0] == streams[1],
                "the second, identical batch returned the same tokens")
    smoke.check(eng.prefill_chunks > 2 * len(prompts),
                f"prompts were prefilled in chunks ({eng.prefill_chunks} "
                f"chunks for {2 * len(prompts)} prompts)")
    smoke.say(f"  trace_counts {eng.trace_counts}")
    smoke.check(set(eng.trace_counts.values()) == {1},
                "every serving program was traced once across both batches")
    smoke.check(eng.watchdog_trips == 0,
                "watchdog_trips == 0")
    smoke.check(telemetry.get_flight().incident_count() == incidents0,
                "no incident was recorded")
    audit = eng.cache.audit()
    smoke.check(audit["allocs"] == audit["frees"] and audit["in_use"] == 0
                and audit["page_allocs"] == audit["page_frees"]
                and audit["pages_in_use"] == 0,
                f"cache.audit() balances at slot and page level: {audit}")
    check_memory_rose(smoke, devices, mem0, mem1, label)

    if mesh is not None:
        q = eng.params[f"{name}_layer0_attn_q_weight"]
        smoke.say(f"  {name}_layer0_attn_q_weight {tuple(q.shape)} shards: "
                  f"{shard_shapes(q)}")
        smoke.say(f"  page pool {tuple(eng.cache.k.shape)} shards: "
                  f"{shard_shapes(eng.cache.k)}")
        tp = mesh.shape["model"]
        smoke.check({s for _, s in shard_shapes(q)}
                    == {(q.shape[0], q.shape[1] // tp)}
                    and {s[2] for _, s in shard_shapes(eng.cache.k)}
                    == {eng.cache.k.shape[2] // tp},
                    f"weights split their output dim and the page pool its "
                    f"KV heads over the {tp} devices of the mesh")
    if compare is not None:
        same = sum(a == b for a, b in zip(streams[0], compare))
        smoke.say(f"  reported, not checked: {same} of {len(compare)} "
                  "requests return the one-chip engine's tokens")
    else:
        report_reference(smoke, served, prompts[0], streams[0][0])
    eng.close()
    return streams[0]


def report_reference(smoke, served, prompt, tokens):
    """How far the engine's greedy stream agrees with the one-shot decoder
    on the same weights.  Reported, never failed on: random weights make
    near-ties, and chunked paged prefill sums in another order."""
    import numpy as np
    from hetu_tpu.models.llama_decode import greedy_generate

    ex, model, name = served
    ref = np.asarray(greedy_generate(ex, model, prompt[None], len(tokens),
                                     name=name))[0, prompt.size:]
    agree = 0
    for a, b in zip(tokens, ref.tolist()):
        if a != b:
            break
        agree += 1
    smoke.say(f"  reported, not checked: the first {agree} of "
              f"{len(tokens)} tokens of the {prompt.size}-token request "
              "agree with models/llama_decode.greedy_generate")


def fleet_leg(smoke, served):
    """Four one-chip replicas, one request each."""
    import jax
    from hetu_tpu.serving import EngineFleet

    smoke.say("== server leg: EngineFleet of four one-chip replicas")
    ex, model, name = served
    z = smoke.sizes["llama"]
    devices = jax.devices()[:4]
    mem0 = memory(devices)
    # every fleet option at its default but the manual drive: the wedge
    # bound in particular, which must let a replica compile
    fleet = EngineFleet(ex, model, n_engines=4, threaded=False,
                        engine_kwargs=dict(z["engine"], name=name),
                        name="smoke_fleet")
    prompts = prompts_for(smoke, z["fleet_prompt_lens"],
                          model.config.vocab_size)
    t0 = time.perf_counter()
    reqs = [fleet.submit(p, z["max_new"]) for p in prompts]
    iters = drain(fleet.pump, reqs, 50 * z["max_new"])
    smoke.say(f"  {len(reqs)} requests over replicas "
              f"{[r.engines for r in reqs]}, {iters} pump iterations; "
              f"observed {time.perf_counter() - t0:.1f} s with compilation")
    mem1 = memory(devices)
    check_streams(smoke, reqs, z["max_new"], model.config.vocab_size)
    smoke.check(sorted(r.engines[0] for r in reqs)
                == sorted(f"e{i}" for i in range(4))
                and all(len(r.engines) == 1 for r in reqs),
                "each replica answered one request, with no failover")
    stats = fleet.stats()
    smoke.check(all(e["engine"]["watchdog_trips"] == 0
                    and e["state"] == "healthy"
                    for e in stats["engines"].values()),
                "every replica is healthy with watchdog_trips == 0")
    smoke.check(all(a["allocs"] == a["frees"] and a["in_use"] == 0
                    and a["page_allocs"] == a["page_frees"]
                    for a in fleet.audit().values()),
                f"every replica's cache.audit() balances: {fleet.audit()}")
    smoke.say(f"  trace_counts {fleet.trace_counts()}")
    check_memory_rose(smoke, devices, mem0, mem1, "EngineFleet")
    fleet.stop()


# -- the run ----------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="toy sizes on the cpu platform, every line "
                         "labelled, no result line: for debugging this "
                         "script where there is no chip")
    ns = ap.parse_args(argv)
    smoke = Smoke(ns.cpu_rehearsal)

    import jax
    libtpu_version = (importlib.metadata.version("libtpu")
                      if importlib.util.find_spec("libtpu") else "absent")
    devices = jax.devices()
    d0 = devices[0]
    smoke.say(f"platform {d0.platform}, device_kind {d0.device_kind}, "
              f"{len(devices)} device(s), jax {jax.__version__}, "
              f"libtpu {libtpu_version}")
    wanted = "cpu" if smoke.rehearsal else "tpu"
    if d0.platform != wanted:
        smoke.say(f"FAIL: this run needs platform {wanted!r} and jax found "
                  f"{d0.platform!r} ({d0.device_kind}); nothing was run")
        return 1

    from hetu_tpu import telemetry
    from hetu_tpu.platform import enable_compile_cache
    smoke.say(f"compile cache: {enable_compile_cache()}")
    # the registry counts kernel choices and retraces, and the flight
    # recorder keeps incidents, only while telemetry is on
    telemetry.enable()

    trainer_leg(smoke, "one chip")
    gc.collect()
    smoke.say("== served model")
    served = build_served_model(smoke)
    one_chip = server_leg(smoke, served, "one chip, paged engine")
    gc.collect()

    if len(devices) >= 4:
        from hetu_tpu.parallel import DataParallel, MegatronLM
        from hetu_tpu.serving.sharding import serving_mesh
        z = smoke.sizes["bert"]
        peaks0 = memory(devices[:4])
        trainer_leg(smoke, "four chips, MegatronLM(dp=2, tp=2)",
                    MegatronLM(dp=2, tp=2),
                    dict(devices=4, sharded_params=True,
                         batch_shard=(z["batch"] // 2, z["seq"])))
        gc.collect()
        trainer_leg(smoke, "four chips, DataParallel(ndev=4)",
                    DataParallel(ndev=4),
                    dict(devices=4, sharded_params=False,
                         batch_shard=(z["batch"] // 4, z["seq"])))
        gc.collect()
        server_leg(smoke, served, "four chips, mesh=serving_mesh(4)",
                   mesh=serving_mesh(4), compare=one_chip)
        gc.collect()
        fleet_leg(smoke, served)
        check_peaks_rose(smoke, devices[:4], peaks0, memory(devices[:4]),
                         "the four-chip legs together")
    else:
        smoke.say(f"== {len(devices)} device(s): the four-chip legs need "
                  "four and were not run")

    telemetry.shutdown()
    if smoke.rehearsal:
        smoke.say("rehearsal complete; this is not a chip result")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
