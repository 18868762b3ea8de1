"""Causal-LM pretraining of the Ling-3.0 language model at one chip's share
of an expert-parallel job: ``Ling3ForCausalLM`` from the configuration's
published keys, ``loss`` and ``opt.minimize`` through ``ht.Executor``, a
fresh numpy batch of ids and next-token labels fed every step, each expert
layer's load and its router's selection bias fetched beside the loss and
counted by ``hetu_tpu.layers.moe.record_moe_load``.  Knows nothing of cells:
sizes come from the configuration file, batch shape from the traffic file.

The family's files: ``configs/ling-3.0-flash-vl-pretrain.json`` (the
published keys of the language model; ``num_experts`` there is the experts
HELD on this chip and ``vocab_size`` the slice, both listed in ``reduced``;
the ``deployment`` group holds the published counts; ``job`` the optimizer
and what is recomputed), this builder, ``reference/ling3.py`` (the plain
reference, given the same held experts and the same slice),
``flops_ling3.py`` (operations and bytes) and the readers
``metrics/*.ling3.py``, ``metrics/kda_*.py``.
"""

from __future__ import annotations

import numpy as np

from .common import jax_seed
from .granite_hybrid import logits_gap, relative_gaps
from .llama import Program as LlamaProgram
from .qwen3_next import Program as Qwen3NextProgram

#: published keys that are Ling3Config arguments under their own names
HF_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
           "num_attention_heads", "head_dim", "layer_group_size",
           "first_k_dense_replace", "intermediate_size",
           "num_experts_per_tok", "moe_intermediate_size",
           "moe_shared_expert_intermediate_size", "score_function",
           "moe_router_enable_expert_bias", "n_group", "topk_group",
           "norm_topk_prob", "routed_scaling_factor", "kv_lora_rank",
           "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
           "v_head_dim", "rope_theta", "use_qk_norm",
           "short_conv_kernel_size", "kda_lower_bound", "kda_safe_gate",
           "rms_norm_eps", "expert_swiglu_limit_list",
           "share_expert_swiglu_limit_list")

TERMS = ("ce",)

#: heads the probe runs at a time: a layer's 32 at once, with the reference's
#: f32 copies and the scan's transposed operands, were 1.2 GB beside the
#: training state and the process's peak of device memory (95.2% of HBM for
#: the step's own 88%; PERF.md, PR 40)
PROBE_HEADS = 8

#: the decay a position (``-g``) of the slowest and the fastest channel of
#: the probe (``kda_state_gap``), log-spaced between over a head's channels:
#: a memory of about 10,000 positions down to none, the whole of the bounded
#: gate's range
PROBE_DECAY = (1e-4, 5.0)


def reference_params(model, params):
    """The program's weights under the plain reference's names
    (``chipbench/reference/ling3.py`` ``WEIGHTS``), found by walking the
    model object, not by parsing variable names.  The values are ``params``'
    own arrays: nothing is copied."""
    out = {"embed": model.model.embed.weight, "norm": model.model.norm.scale,
           "lm_head": model.lm_head.weight}
    for i, layer in enumerate(model.model.layers):
        m, f = layer.mixer, layer.mlp
        named = [("input_norm", layer.input_norm.scale),
                 ("post_norm", layer.post_norm.scale)]
        if layer.kind == "attention":
            named += [("q", m.q_proj), ("kva", m.kva_proj),
                      ("kv_norm", m.kv_norm), ("kvb", m.kvb_proj),
                      ("q_norm", m.q_norm), ("k_norm", m.k_norm),
                      ("gate", m.gate_proj), ("o", m.out_proj)]
        else:
            named += [("kda_in", m.in_proj), ("kda_beta", m.beta_proj),
                      ("conv", m.conv), ("a_log", m.a_log),
                      ("dt_bias", m.dt_bias), ("kda_norm", m.norm),
                      ("kda_out", m.out_proj)]
        if layer.dense:
            named += [("mlp_gate", f.gate.weight), ("mlp_up", f.up.weight),
                      ("mlp_down", f.down.weight)]
        else:
            named += [("router", f.gate.wg), ("router_bias", f.gate.bias),
                      ("w_gate", f.w1), ("w_up", f.w3), ("w_down", f.w2)]
            named += zip(("shared_gate", "shared_up", "shared_down"),
                         f.shared)
        out.update({f"layers.{i}.{k}": v for k, v in named if v is not None})
    return {k: params[v.name] for k, v in out.items()}


def probe_inputs(config, seq, seed):
    """Seeded inputs of the probe, on the host: q, k (unit keys), v in the
    compute type, g and beta in f32 for ONE LAYER's heads of the published size over ``seq``
    positions.  Channel ``j`` of every head forgets ``PROBE_DECAY``
    (log-spaced over the channels, in an order of its own a head) a position,
    times a draw about one, bounded at the configuration's lower bound."""
    import jax.numpy as jnp
    nh, d = config["num_attention_heads"], config["head_dim"]
    rng = np.random.default_rng([int(seed), 7])
    ct = getattr(jnp, config["job"]["compute_dtype"])
    shape = (1, seq, nh, d)

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    q, k, v = (np.asarray(jnp.asarray(x, ct)) for x in (
        unit(rng.standard_normal(shape, dtype=np.float32)) * d ** -0.5,
        unit(rng.standard_normal(shape, dtype=np.float32)),
        rng.standard_normal(shape, dtype=np.float32)))
    rate = np.stack([rng.permutation(np.geomspace(*PROBE_DECAY, d))
                     for _ in range(nh)]).astype(np.float32)
    g = -np.minimum(-float(config["kda_lower_bound"]), rate * np.logaddexp(
        0.0, rng.standard_normal(shape, dtype=np.float32) + 0.5))
    beta = 1.0 / (1.0 + np.exp(-rng.standard_normal((1, seq, nh))))
    return q, k, v, g.astype(np.float32), beta.astype(np.float32)


def kda_state_gap(config, seq, seed, say, rule):
    """How far a delta rule with a decay a channel ends from the plain
    reference's: the largest relative gap (L2, a head) between the last
    states of ``rule(q, k, v, g, beta) -> (o, last state)`` and of the
    reference's recurrence with its f32 state, both given ``probe_inputs``.
    At its initial values the model's decays are slow and alike, where
    neither the type of the state nor a decay that is one number a head shows
    in the loss; here the channels differ by four orders of magnitude."""
    import jax
    import jax.numpy as jnp
    from ..reference import ling3 as ref
    inputs = probe_inputs(config, seq, seed)
    rule, recurrence = jax.jit(rule), jax.jit(ref.kda_recurrence)
    state, out = [], []
    for lo in range(0, inputs[0].shape[2], PROBE_HEADS):
        q, k, v, g, beta = (jnp.asarray(x[:, :, lo:lo + PROBE_HEADS])
                            for x in inputs)
        o, last = (np.asarray(x.astype(jnp.float32))
                   for x in rule(q, k, v, g, beta))
        with jax.default_matmul_precision("highest"):
            o_ref, last_ref = (np.asarray(x) for x in recurrence(
                *(x.astype(jnp.float32) for x in (q, k, v)), g, beta))
        state.append(relative_gaps(last, last_ref, 1))
        out.append(relative_gaps(o, o_ref, 2))
    state, out = np.concatenate(state), np.concatenate(out)
    q, v = inputs[0], inputs[2]

    def shown(gaps):
        return [float(f"{x:.2e}") for x in (
            gaps.min(), *np.quantile(gaps, (0.25, 0.5, 0.75)), gaps.max())]
    say(f"delta rule with a decay a channel ({q.shape[2]} heads of "
        f"{q.shape[3]} x {v.shape[3]} over {seq} positions, decays a position "
        f"{PROBE_DECAY[0]:.0e} .. {PROBE_DECAY[1]:.0e} over a head's "
        f"channels): relative gap to the recurrence with an f32 state, least, "
        f"quartiles and largest over heads: last state {shown(state)}, "
        f"outputs {shown(out)}")
    return float(state.max())


class Program(Qwen3NextProgram):
    """One Executor with a ``train`` subgraph (loss, update, per-layer expert
    load and router bias) and, for the correctness check, a ``validate``
    subgraph of the same loss, the logits under it, the same load and what
    each token chose.  ``make_batches``, ``retraces``, ``uniform_loss`` and
    ``kernel_choices`` are the Llama builder's, ``close`` (the line of pairs
    against the static row bound) the Qwen3-Next builder's.

    Dropless routing is held as in the Qwen3-Next builder (a step that left a
    routed pair without a row reports a loss that is not finite); the f32
    state and the decay a channel by ``kda_state_gap``."""

    KERNELS = LlamaProgram.KERNELS + (
        "hetu_moe_rows_sum", "hetu_kda_fwd", "hetu_kda_bwd", "hetu_conv_fwd",
        "hetu_conv_bwd")

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu.models import Ling3Config, Ling3ForCausalLM
        from hetu_tpu.ops import kda
        from hetu_tpu.ops.pallas import dispatch

        self.config, self.mix, self._say = config, mix, say
        self.seed = seed
        self.held_peak, self.steps_dropping = 0.0, 0
        job, dep = config["job"], config["deployment"]
        self._choices_before = dispatch.choices()
        assert job["scan_chunk"] == kda.CHUNK, (
            "the chunk the readers credit is the one the program runs")
        B, S = int(mix["batch"]), int(mix["seq"])
        assert S <= config["max_position_embeddings"]
        self.batch, self.seq = B, S
        self.tokens_per_step = B * S
        self.held = tuple(dep["experts_held"])
        assert self.held[1] == config["num_experts"], (
            "num_experts in the configuration file is the experts held here")
        c = Ling3Config(
            seq_len=S, num_experts=dep["num_experts"],
            experts_held=self.held, remat=job["remat"],
            router_bias_update_rate=job["router_bias_update_rate"],
            **{key: config[key] for key in HF_KEYS})
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, S), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, S), dtype=np.int32)}
        self.model = Ling3ForCausalLM(c)
        logits = self.model(self.nodes["ids"])
        loss, terms = self.model.loss_terms(
            self.nodes["ids"], self.nodes["labels"], logits=logits)
        loads = self.model.moe_loads()
        biases = self.model.router_biases()
        chosen = [m.chosen() for m in self.model.moe_layers()]
        self.n_layers = len(loads)
        # the latent layer's own output beside the logits: one layer of
        # seven moves the logits by less than the program's bf16 does
        # (a missing head gate 0.017 against 0.061), its own output not
        self.probed_layer = c.layer_types.index("attention")
        mixer_out = self.model.model.layers[self.probed_layer].mixer_out
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        self.ex = ht.Executor(
            {"train": [loss, opt.minimize(loss)] + loads + biases,
             "validate": [loss, logits, mixer_out] + loads + chosen},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]))
        self.params_m = sum(int(np.prod(v.shape))
                            for v in self.ex.params.values()) / 1e6
        kinds = "".join("A" if k == "attention" else "K"
                        for k in c.layer_types)
        say(f"Ling-3.0 decoder (text only): hidden {c.hidden_size}, layers "
            f"{kinds} (K KDA: {c.num_heads} heads of {c.head_dim}, decay a "
            f"channel bounded at {c.kda_lower_bound}, chunks of {kda.CHUNK} "
            f"in sub-chunks of {kda.SUB}; A latent attention: {c.num_heads} "
            f"heads, keys {c.qk_nope_head_dim}+{c.qk_rope_head_dim}, values "
            f"{c.v_head_dim}, latent {c.kv_lora_rank}, a gate a head), the "
            f"first {c.first_k_dense_replace} FFN(s) dense "
            f"{c.dense_intermediate_size} wide, then router {c.num_experts} "
            f"wide in {c.router_groups[0]} groups ({c.router_groups[1]} "
            f"kept), {c.moe_k} a token, experts {self.held[0]}.."
            f"{self.held[0] + self.held[1] - 1} held (width "
            f"{c.intermediate_size}), shared expert {c.shared_width}; "
            f"vocabulary slice {c.vocab_size} of {dep['vocab_size']}; batch "
            f"{B} x {S}, {self.params_m:.1f} M parameters, "
            f"{job['compute_dtype']} compute over f32 masters, "
            f"{job['optimizer']}, recomputed: {job['remat']}, loss = ce")

    def step(self, feed):
        """The Qwen3-Next builder's step: a step in which any layer routed
        more pairs to its held experts than it computed reports NaN."""
        from hetu_tpu.layers.moe import record_moe_load
        out = self.ex.run("train", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        n = self.n_layers
        dropped = 0.0
        for i, (load, bias) in enumerate(zip(out[2:2 + n], out[2 + n:])):
            record_moe_load(f"layer{i}", load, bias=bias)
            self.held_peak = max(self.held_peak, float(load[0].sum()))
            dropped += float(load[0].sum() - load[1].sum())
        if dropped:
            self.steps_dropping += 1
            return float("nan")
        return float(out[0])

    def pallas_ops(self):
        from hetu_tpu.ops.pallas import dispatch
        return (("flash_attention", "softmax_ce", "moe_gmm", "moe_rows",
                 "kda", "causal_conv") if dispatch.mosaic() else ())

    @property
    def forward_passes(self):
        """The most forward passes of a layer a step: two where whole layers
        are recomputed in the backward pass (a step that keeps the kernel's
        output through the recomputation runs one)."""
        return 2 if self.config["job"]["remat"] == "layer" else 1

    def expected_kernel_shapes(self):
        """Flash attention's work: the forward pass writes batch x heads x
        positions x the VALUES' head size; ``attention_passes`` is the passes
        a step REQUIRES (one forward and one backward an attention layer),
        ``attention_layers`` the MOST forward calls a step may make (a
        recomputed layer's twice); the scores' width beside them for the
        roofline."""
        c = self.config
        heads, dv = c["num_attention_heads"], c["v_head_dim"]
        return {"flash_dims": (self.batch, heads, self.seq, dv),
                "flash_elements": self.batch * heads * self.seq * dv,
                "flash_rows": self.batch * heads, "head_dim": dv,
                "score_dim": c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
                "attention_passes": self.model.attention_layers,
                "attention_layers": (self.model.attention_layers
                                     * self.forward_passes),
                "causal": True,
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.batch * self.seq,
                "moe_pairs": self.tokens_per_step * c["num_experts_per_tok"]}

    def eval_loss(self, feed):
        """The program's loss on ``feed``, ``{"loss", "ce", "logits_gap",
        "attention_gap", "dropped", "routing_mismatch", "kda_state_gap"}``,
        through the executor's ``validate`` subgraph.  ``logits_gap`` is the
        relative L2 distance of the program's logits from those
        ``reference_loss`` kept from the same batch (it runs first),
        ``attention_gap`` that of the latent-attention layer's output from
        the reference's at the same layer; ``dropped`` and
        ``routing_mismatch`` as the Qwen3-Next builder's; ``kda_state_gap``
        is not of ``feed``."""
        # to the host: the [B S, V] logits stay on the device no longer than
        # the fetch
        out = self.ex.run("validate", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        n = self.n_layers
        got = {"loss": float(out[0]), "ce": float(out[0])}
        got["logits_gap"] = logits_gap(out[1], self._ref_logits)
        got["attention_gap"] = logits_gap(out[2], self._ref_mixer)
        del self._ref_logits, self._ref_mixer
        out = [out[0], None] + list(out[3:])
        loads = np.asarray(out[2:2 + n], np.float64)    # [layers, 4, held]
        got["dropped"] = float(1.0 - loads[:, 1].sum() / loads[:, 0].sum())
        want = self._ref_chosen
        E = self.config["deployment"]["num_experts"]
        shared = 0
        for mine, theirs in zip(out[2 + n:], want):
            hot = np.zeros((len(theirs), E), bool)
            np.put_along_axis(hot, np.asarray(theirs), True, axis=1)
            shared += np.take_along_axis(hot, np.asarray(mine), 1).sum()
        got["routing_mismatch"] = float(1.0 - shared / want.size)
        got["kda_state_gap"] = self.kda_state_gap()
        return got

    def kda_state_gap(self):
        """``kda_state_gap`` of the function the layers' ``hetu_kda_scan``
        nodes call, at the cell's sequence length, from the run's seed."""
        from hetu_tpu.ops.kda import chunk_kda
        return kda_state_gap(self.config, self.seq, self.seed, self._say,
                             chunk_kda)

    def reference_loss(self, feed, chunk, **lower):
        """The plain reference's loss, as ``eval_loss`` names it, on all of
        ``feed`` with this executor's present weights (its f32 masters, read
        in place), the same held experts and the same vocabulary slice,
        ``chunk`` sequences at a time.  ``lower``: the reference's
        ``matmul_inputs``, ``state_dtype`` or ``without`` (the builder's
        readings of a lower precision)."""
        import jax
        from ..reference import ling3 as ref
        params = reference_params(self.model, self.ex.params)
        sums = jax.jit(lambda p, i, l: ref.loss_sums(
            p, self.config, i, l, held=self.held, keep_logits=True,
            keep_mixer=self.probed_layer, **lower))
        ids = np.asarray(feed[self.nodes["ids"]])
        labels = np.asarray(feed[self.nodes["labels"]])
        tot, chosen, logits, mixer = None, [], [], []
        for lo in range(0, self.batch, chunk):
            part = jax.device_get(sums(params, ids[lo:lo + chunk],
                                       labels[lo:lo + chunk]))
            chosen.append(part.pop("chosen"))
            logits.append(part.pop("logits"))
            mixer.append(part.pop("mixer"))
            tot = part if tot is None else {k: tot[k] + v
                                            for k, v in part.items()}
        self._ref_chosen = np.concatenate(chosen, axis=1)  # [layers, T, k]
        self._ref_logits = np.concatenate(logits)          # [B S, V] f32
        self._ref_mixer = np.concatenate(mixer)            # [B, S, H] f32
        out = {k: float(v) for k, v in ref.loss_from_sums(tot).items()}
        out.update(logits_gap=0.0, attention_gap=0.0, dropped=0.0,
                   routing_mismatch=0.0, kda_state_gap=0.0)
        return out


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
