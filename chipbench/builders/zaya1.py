"""Causal-LM pretraining of ZAYA1 at one chip's share of an expert-parallel
job: ``Zaya1ForCausalLM`` from the configuration's published keys, ``loss``
and ``opt.minimize`` through ``ht.Executor``, a fresh numpy batch of ids and
next-token labels fed every step, the loss, each layer's load and its router's
selection bias fetched as ONE value and the loads counted by
``hetu_tpu.layers.moe.record_moe_load``.  Knows nothing of cells: sizes come
from the configuration file, batch shape from the traffic file.

The family's files: ``configs/zaya1-8b-pretrain.json`` (the published keys;
``num_experts`` there is the experts HELD on this chip and ``vocab_size`` the
slice, both listed in ``reduced``; ``deployment`` holds the published counts;
``job`` the optimizer, what is recomputed and the bias's rate), this builder,
``reference/zaya1.py`` (the plain reference, given the same held experts and
slice), ``reference/zaya1_controls.py``, ``flops_zaya1.py`` and the readers
``metrics/*.zaya1.py``, ``metrics/cca_*.py`` and ``metrics/moe_skipped_share
.py``.

What CCA, the router and the residual scaling learn starts at an identity
(taps ``(0, 1)``, ``A_1 = I``, ``t = 0``, ``gamma = 1``, ``s = 1``, ``b = 0``,
``beta = 0``): ``seed_parts`` draws them from the seed instead, so that no part
is near its identity start when program and reference are compared, and the
step that is timed runs them too.
"""

from __future__ import annotations

import numpy as np

from .common import jax_seed
from .granite_hybrid import logits_gap
from .llama import Program as LlamaProgram
from .qwen3_next import Program as Qwen3NextProgram

#: published keys that are Zaya1Config arguments under their own names
HF_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
           "num_attention_heads", "num_key_value_heads", "head_dim",
           "cca_time0", "cca_time1", "partial_rotary_factor",
           "rope_parameters", "layer_types", "sliding_window",
           "router_hidden_size", "num_experts_per_tok",
           "moe_intermediate_size", "rms_norm_eps", "tie_word_embeddings",
           "max_position_embeddings", "attention_bias", "hidden_act",
           "lm_head_bias")

#: the jnp forms a step takes that the model explains: the depthwise taps have
#: no activation, and the convolution's kernel pair applies SiLU
EXPLAINED = {("causal_conv", "jnp", "act:none")}

#: the experts' selection biases are N(0, SPREAD^2), about the distance
#: between a token's two largest probabilities at the published widths (0.03),
#: and the skip choice's is SKIP_BIAS.  No fixed bias holds the skipped share
#: to a range: which output a fresh MLP favours is the draw's, and the first
#: batch's share read 0.08-45% over fifteen seeds (PERF.md section 6)
SPREAD, SKIP_BIAS = 0.02, 0.026


def reference_params(model, params):
    """The program's weights under the plain reference's names
    (``chipbench/reference/zaya1.py`` ``WEIGHTS``), found by walking the model
    object.  The values are ``params``' own arrays."""
    out = {"embed": model.model.embed.weight, "norm": model.model.norm.scale}
    for i, layer in enumerate(model.model.layers):
        a, f = layer.attn, layer.mlp
        r = f.router
        named = [("input_norm", layer.input_norm.scale),
                 ("post_norm", layer.post_norm.scale),
                 ("qk", a.qk_proj.weight), ("v", a.v_proj.weight),
                 ("o", a.out_proj.weight), ("taps", a.taps),
                 ("tap_bias", a.tap_bias), ("mix", a.mix),
                 ("mix_bias", a.mix_bias), ("temp", a.temp),
                 ("w_gate", f.w1), ("w_up", f.w3), ("w_down", f.w2),
                 ("router.down", r.down), ("router.down_bias", r.down_bias),
                 ("router.norm", r.norm), ("router.w1", r.w1),
                 ("router.b1", r.b1), ("router.w2", r.w2),
                 ("router.b2", r.b2), ("router.w3", r.w3),
                 ("router.bias", r.bias)]
        if i:                       # the first layer's gamma is in no graph
            named.append(("router.gamma", r.gamma))
        for key, m in (("attn_merge", layer.attn_merge),
                       ("mlp_merge", layer.mlp_merge)):
            named += [(f"{key}.s_r", m.s_r), (f"{key}.b_r", m.b_r),
                      (f"{key}.s_f", m.s_f), (f"{key}.b_f", m.b_f)]
        out.update({f"layers.{i}.{k}": v for k, v in named})
    return {k: params[v.name] for k, v in out.items()}


def seed_parts(ex, model, seed, skip_bias=SKIP_BIAS):
    """What starts at an identity, drawn from ``seed`` and written over the
    executor's masters: the depthwise taps N(0, 1) and their bias N(0, 1/4);
    a head's mixing taps N(0, 1/d) (so that ``z''`` is as wide as ``z'``) and
    their bias N(0, 1/4); the temperature U(-1/2, 1/2); ``gamma`` and the four
    merge scales U(1/2, 3/2); ``b_r`` N(0, 0.02^2) and ``b_f`` N(0, 0.1^2)
    (about half of what each is added to in a fresh model); the router's
    selection bias N(0, ``SPREAD``^2) over the experts and ``skip_bias`` on
    the choice that is no expert."""
    import jax.numpy as jnp
    rng = np.random.default_rng([int(seed), 11])

    def put(var, value):
        ex.params[var.name] = jnp.asarray(value, jnp.float32)
    for i, layer in enumerate(model.model.layers):
        a, r = layer.attn, layer.mlp.router
        d = a.head_dim
        put(a.taps, rng.normal(0, 1, a.taps.shape))
        put(a.tap_bias, rng.normal(0, 0.5, a.tap_bias.shape))
        put(a.mix, rng.normal(0, d ** -0.5, a.mix.shape))
        put(a.mix_bias, rng.normal(0, 0.5, a.mix_bias.shape))
        put(a.temp, rng.uniform(-0.5, 0.5, a.temp.shape))
        gamma = rng.uniform(0.5, 1.5, r.gamma.shape)
        if i:
            put(r.gamma, gamma)
        beta = rng.normal(0, SPREAD, r.bias.shape)
        if r.skip:
            beta[-r.skip:] = skip_bias
        put(r.bias, beta)
        for m in (layer.attn_merge, layer.mlp_merge):
            put(m.s_r, rng.uniform(0.5, 1.5, m.s_r.shape))
            put(m.s_f, rng.uniform(0.5, 1.5, m.s_f.shape))
            put(m.b_r, rng.normal(0, 0.02, m.b_r.shape))
            put(m.b_f, rng.normal(0, 0.1, m.b_f.shape))


def skipped_output(y, chosen, num_experts):
    """The RMS of the expert sublayer's output ``y [.., C]`` over the tokens
    whose choice ``chosen [T]`` is no expert, over its RMS over the others: 0
    where a skipped token gets exact zeros, about 1 where it gets an
    expert's output."""
    y = np.asarray(y, np.float64).reshape(len(chosen), -1)
    none = np.asarray(chosen) >= num_experts
    if not none.any() or none.all():
        return 0.0
    rms = lambda rows: np.sqrt(np.mean(rows ** 2))
    return float(rms(y[none]) / max(rms(y[~none]), 1e-30))


def norm_gap(qk, temps, heads, kv_heads):
    """How far the heads' L2 norms of ``qk = [q^ | k^] [B, S, (H + J) d]`` lie
    from ``sqrt(d)`` and ``exp(t_j) sqrt(d)``: the largest, relative."""
    x = np.asarray(qk, np.float64)
    n = heads + kv_heads
    d = x.shape[-1] // n
    norms = np.sqrt((x.reshape(-1, n, d) ** 2).sum(-1))
    want = np.sqrt(d) * np.concatenate(
        [np.ones(heads), np.exp(np.asarray(temps, np.float64))])
    return float(np.abs(norms / want - 1).max())


class Program(Qwen3NextProgram):
    """One Executor with a ``train`` subgraph (loss, update, per-layer load
    and router bias) and, for the correctness check, a ``validate`` subgraph
    of the same loss, the logits, the first layer's CCA output, its ``q^ |
    k^`` and its experts' output, the last layer's router state, the same load
    and what each token chose.  ``make_batches``, ``retraces`` and
    ``uniform_loss`` are the Llama builder's, ``close`` the Qwen3-Next
    builder's; ``step`` is the Ling builder's (a step that left a routed pair
    without a row reports a loss that is not finite) on the ONE value the
    train step hands out."""

    KERNELS = LlamaProgram.KERNELS + ("hetu_moe_rows_sum", "hetu_rope_fwd",
                                      "hetu_rope_bwd")
    #: the layer whose CCA output and ``q^ | k^`` the comparison looks at
    PROBED = 0

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu.graph.node import scope
        from hetu_tpu.models import Zaya1Config, Zaya1ForCausalLM
        from hetu_tpu.ops.pallas import dispatch

        self.config, self.mix, self._say = config, mix, say
        self.seed = seed
        self.held_peak, self.steps_dropping = 0.0, 0
        job, dep = config["job"], config["deployment"]
        self._choices_before = dispatch.choices()
        B, S = int(mix["batch"]), int(mix["seq"])
        self.batch, self.seq = B, S
        self.tokens_per_step = B * S
        self.held = tuple(dep["experts_held"])
        assert self.held[1] == config["num_experts"], (
            "num_experts in the configuration file is the experts held here")
        c = Zaya1Config(
            seq_len=S, num_experts=dep["num_experts"],
            experts_held=self.held, remat=job["remat"] or None,
            router_bias_update_rate=job["router_bias_update_rate"],
            use_eda=config["sibling_rows"]["zaya_use_eda"],
            use_mod=config["sibling_rows"]["zaya_use_mod"],
            scale_residual_merge=config["sibling_rows"][
                "scale_residual_merge"],
            **{key: config[key] for key in HF_KEYS})
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, S), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, S), dtype=np.int32)}
        self.model = Zaya1ForCausalLM(c)
        logits = self.model(self.nodes["ids"])
        loss, _ = self.model.loss_terms(
            self.nodes["ids"], self.nodes["labels"], logits=logits)
        loads = self.model.moe_loads()
        biases = self.model.router_biases()
        chosen = [m.chosen() for m in self.model.moe_layers()]
        self.n_layers = len(loads)
        # a fetched value is a copy of its own with the device idle, so the
        # train step hands out ONE: the loss, then the ten small vectors
        self.stat_vars = [(n.var.name, tuple(n.var.shape))
                          for n in loads + biases]
        with scope("hetu_moe_other"):
            stats = ht.concatenate_op([
                ht.array_reshape_op(n, output_shape=(-1,))
                for n in [loss] + loads + biases])
        probed = self.model.model.layers[self.PROBED].attn
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        self.ex = ht.Executor(
            {"train": [stats, opt.minimize(loss)],
             "validate": ([loss, logits, probed.out, *probed.qk,
                           self.model.router_states()[-1],
                           self.model.moe_layers()[self.PROBED].last_op]
                          + loads + chosen)},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]))
        seed_parts(self.ex, self.model, seed)
        self.params_m = sum(int(np.prod(v.shape))
                            for v in self.ex.params.values()) / 1e6
        say(f"ZAYA1 decoder: hidden {c.hidden_size}, {c.num_layers} layers; "
            f"CCA {c.num_heads} query on {c.num_kv_heads} key heads of "
            f"{c.head_dim} in a latent of "
            f"{(c.num_heads + c.num_kv_heads) * c.head_dim}, taps "
            f"{c.conv_taps}, rotary on {c.rotary_dim} lanes at base "
            f"{c.rope_theta:g}; router an MLP of width {c.router_width} with "
            f"{c.num_experts} + {1 if c.use_mod else 0} outputs (state "
            f"carried: {c.use_eda}), {c.moe_k} a token, experts "
            f"{self.held[0]}..{self.held[0] + self.held[1] - 1} held (width "
            f"{c.intermediate_size}); residual scaling "
            f"{c.scale_residual_merge}; tied vocabulary slice {c.vocab_size} "
            f"of {dep['vocab_size']}; batch {B} x {S}, {self.params_m:.1f} M "
            f"parameters, {job['compute_dtype']} compute over f32 masters, "
            f"{job['optimizer']}, recomputed: {job['remat']}, loss = ce")

    def step(self, feed):
        """The Ling builder's step on the ONE value a step hands out (the
        loss, then every layer's load and router bias): a step in which any
        layer routed more pairs to its held experts than it computed reports
        NaN."""
        from hetu_tpu.layers.moe import record_moe_load
        stats = self.ex.run("train", feed_dict=feed,
                            convert_to_numpy_ret_vals=True)[0]
        shapes = [shape for _, shape in self.stat_vars]
        ends = np.cumsum([1] + [int(np.prod(shape)) for shape in shapes])
        loss, *parts = np.split(stats, ends[:-1])
        parts = [part.reshape(shape) for part, shape in zip(parts, shapes)]
        n = self.n_layers
        dropped = 0.0
        for i, (load, bias) in enumerate(zip(parts[:n], parts[n:])):
            record_moe_load(f"layer{i}", load, bias=bias)
            self.held_peak = max(self.held_peak, float(load[0].sum()))
            dropped += float(load[0].sum() - load[1].sum())
        if dropped:
            self.steps_dropping += 1
            return float("nan")
        return float(loss[0])

    def kernel_choices(self):
        taken, fallbacks = super().kernel_choices()
        return taken, [k for k in fallbacks if k not in EXPLAINED]

    def pallas_ops(self):
        from hetu_tpu.ops.pallas import dispatch
        return (("flash_attention", "softmax_ce", "moe_gmm", "moe_rows",
                 "rotary") if dispatch.mosaic() else ())

    def expected_kernel_shapes(self):
        """Flash attention's work: the forward pass writes batch x query heads
        x positions x head size, the key heads read in place
        (``kv_heads``); one pass a layer, nothing recomputed.  ``ce_rows`` is
        the rows of the loss kernel, ``moe_pairs`` the pairs a step routes
        over all ``E + 1`` choices, ``cca_sublayers`` the applications of the
        mixing a step."""
        c = self.config
        heads, hd = c["num_attention_heads"], c["head_dim"]
        layers = self.model.attention_layers
        passes = 2 if c["job"]["remat"] == "layer" else 1
        return {"flash_dims": (self.batch, heads, self.seq, hd),
                "flash_elements": self.batch * heads * self.seq * hd,
                "flash_rows": self.batch * heads, "head_dim": hd,
                "kv_heads": c["num_key_value_heads"],
                "attention_passes": layers,
                "attention_layers": layers * passes,
                "causal": True,
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.batch * self.seq,
                "cca_sublayers": layers,
                "moe_pairs": self.tokens_per_step * c["num_experts_per_tok"]}

    def eval_loss(self, feed):
        """The program's loss on ``feed``, ``{"loss", "ce", "logits_gap",
        "attention_gap", "cca_qk_gap", "cca_norm_gap", "router_state_gap",
        "routing_mismatch", "skipped", "skipped_output", "dropped"}``, through
        the executor's
        ``validate`` subgraph.  ``logits_gap`` is the relative L2 distance of
        the program's logits from those ``reference_loss`` kept from the same
        batch (it runs first), ``attention_gap`` that of the first layer's CCA
        output, ``cca_qk_gap`` that of its ``[q^ | k^]`` behind the mixing,
        ``cca_norm_gap`` how far the program's own heads' norms lie from
        ``sqrt(d)`` and ``exp(t) sqrt(d)``, ``router_state_gap`` that of the
        LAST layer's router state; ``routing_mismatch`` the share of the
        reference's choices (a token and its one choice of ``E + 1``, a
        layer) that the program did not make, ``skipped`` the share of all
        pairs that chose no expert, ``skipped_output`` what the first layer's
        experts hand a token that chose none (``skipped_output``: 0),
        ``dropped`` the share of the pairs routed to held experts that got no
        row."""
        out = self.ex.run("validate", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        n, kept = self.n_layers, self.kept
        c = self.config
        got = {"loss": float(out[0]), "ce": float(out[0])}
        got["logits_gap"] = logits_gap(out[1], kept.pop("logits"))
        got["attention_gap"] = logits_gap(out[2], kept["attention"])
        qk = np.concatenate([np.asarray(out[3], np.float32),
                             np.asarray(out[4], np.float32)], -1)
        got["cca_qk_gap"] = logits_gap(qk, kept["qk"])
        temp = self.model.model.layers[self.PROBED].attn.temp
        got["cca_norm_gap"] = norm_gap(
            qk, self.ex.params[temp.name], c["num_attention_heads"],
            c["num_key_value_heads"])
        got["router_state_gap"] = logits_gap(out[5], kept["state"])
        experts = out[6]
        out = out[7:]
        loads = np.asarray(out[:n], np.float64)          # [layers, 5, held]
        got["dropped"] = float(1.0 - loads[:, 1].sum() / loads[:, 0].sum())
        pairs = n * self.tokens_per_step * c["num_experts_per_tok"]
        got["skipped"] = float(loads[:, 4, 0].sum() / pairs)
        mine = np.stack([np.asarray(x).reshape(-1) for x in out[n:]])
        got["routing_mismatch"] = float(np.mean(mine != kept["chosen"]))
        got["skipped_output"] = skipped_output(
            experts, mine[self.PROBED], c["deployment"]["num_experts"])
        return got

    def reference_loss(self, feed, chunk, **lower):
        """The plain reference's loss, as ``eval_loss`` names it, on all of
        ``feed`` with this executor's present weights (its f32 masters, read
        in place), the same held experts and the same vocabulary slice,
        ``chunk`` sequences at a time.  What the comparison needs beside the
        sums stays on ``self.kept``.  ``lower``: the reference's
        ``matmul_inputs`` or ``without`` (a control's reading)."""
        import jax
        from ..reference import zaya1 as ref
        params = reference_params(self.model, self.ex.params)
        sums = jax.jit(lambda p, i, l: ref.loss_sums(
            p, self.config, i, l, held=self.held, keep_logits=True,
            keep=self.PROBED, **lower))
        ids = np.asarray(feed[self.nodes["ids"]])
        labels = np.asarray(feed[self.nodes["labels"]])
        #: what is kept of a chunk, and the axis its sequences lie along
        axes = {"chosen": 1, "logits": 0, "attention": 0, "qk": 0, "state": 0,
                "experts": 0}
        tot, kept = None, {}
        for lo in range(0, self.batch, chunk):
            part = jax.device_get(sums(params, ids[lo:lo + chunk],
                                       labels[lo:lo + chunk]))
            for k in axes:
                kept.setdefault(k, []).append(part.pop(k))
            tot = part if tot is None else {k: tot[k] + v
                                            for k, v in part.items()}
        self.kept = {k: np.concatenate(v, axis=axes[k])
                     for k, v in kept.items()}
        out = {k: float(v) for k, v in ref.loss_from_sums(tot).items()}
        c = self.config
        temp = self.model.model.layers[self.PROBED].attn.temp
        out.update(
            logits_gap=0.0, attention_gap=0.0, cca_qk_gap=0.0,
            cca_norm_gap=norm_gap(self.kept["qk"], self.ex.params[temp.name],
                                  c["num_attention_heads"],
                                  c["num_key_value_heads"]),
            router_state_gap=0.0, routing_mismatch=0.0, dropped=0.0,
            skipped=float(tot["skipped"]) / self.kept["chosen"].size,
            skipped_output=skipped_output(
                self.kept["experts"], self.kept["chosen"][self.PROBED],
                c["deployment"]["num_experts"]))
        return out


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
