"""Causal-LM pretraining of Xing4.0 at one chip's share of an expert-parallel
job: ``Xing4ForCausalLM`` from the configuration's published keys, ``loss``
(the next token's cross-entropy plus the multi-token-prediction depth's) and
``opt.minimize`` through ``ht.Executor``, a fresh numpy batch of ids and
next-token labels fed every step, each expert layer's load and its router's
selection bias fetched beside the loss and counted by
``hetu_tpu.layers.moe.record_moe_load``.  Knows nothing of cells: sizes come
from the configuration file, batch shape from the traffic file.

The family's files: ``configs/xing4.0-29b-a4b-pretrain.json`` (the published
keys; ``n_routed_experts`` there is the experts HELD on this chip and
``vocab_size`` the slice, both listed in ``reduced``; ``deployment`` holds the
published counts; ``job`` the optimizer, what is recomputed and the MTP
term's weight), this builder, ``reference/xing4.py`` (the plain reference,
given the same held experts and slice), ``reference/xing4_controls.py``,
``flops_xing4.py`` and the readers ``metrics/*.xing4.py``, ``metrics/hc_*.py``
and ``metrics/mtp_*.py``.

The hyper-connections' ``phi``, ``b`` and gains are drawn from the seed
(``seed_maps``) in place of their initial values, at which every map is a
constant: the comparison with the reference, and the step that is timed, then
run maps that differ from token to token.
"""

from __future__ import annotations

import numpy as np

from .common import jax_seed
from .granite_hybrid import logits_gap
from .ling3 import Program as Ling3Program
from .llama import Program as LlamaProgram
from .qwen3_next import Program as Qwen3NextProgram

#: published keys that are Xing4Config arguments under their own names
HF_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
           "num_attention_heads", "num_key_value_heads",
           "first_k_dense_replace", "intermediate_size",
           "moe_intermediate_size", "moe_layer_freq", "n_shared_experts",
           "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
           "routed_scaling_factor", "scoring_func", "topk_method",
           "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
           "qk_rope_head_dim", "v_head_dim", "rope_theta", "rope_scaling",
           "max_position_embeddings", "hc_mult", "hc_sinkhorn_iters",
           "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max",
           "num_nextn_predict_layers", "rms_norm_eps", "hidden_act",
           "attention_bias", "tie_word_embeddings")

TERMS = ("ce", "mtp")

#: the one entry of ``b_res`` (row, column) of the LAST sublayer that
#: ``seed_maps`` sets beyond the clamp, and its value
BEYOND_CLAMP = ((3, 3), 100.0)


def reference_params(model, params):
    """The program's weights under the plain reference's names
    (``chipbench/reference/xing4.py`` ``WEIGHTS``), found by walking the
    model object.  The values are ``params``' own arrays."""
    out = {"embed": model.model.embed.weight, "norm": model.model.norm.scale,
           "lm_head": model.lm_head.weight}
    if model.mtp_layer is not None:
        out.update({"mtp.enorm": model.mtp_enorm.scale,
                    "mtp.hnorm": model.mtp_hnorm.scale,
                    "mtp.norm": model.mtp_norm.scale,
                    "mtp.eh": model.mtp_proj})
    for i, layer in enumerate(model.decoder_layers()):
        m, f = layer.mixer, layer.mlp
        named = [("input_norm", layer.input_norm.scale),
                 ("post_norm", layer.post_norm.scale),
                 ("qa", m.qa_proj), ("qa_norm", m.qa_norm), ("qb", m.q_proj),
                 ("kva", m.kva_proj), ("kv_norm", m.kv_norm),
                 ("kvb", m.kvb_proj), ("o", m.out_proj)]
        for key, hc in (("attn_hc", layer.attn_hc), ("mlp_hc", layer.mlp_hc)):
            named += [(f"{key}.phi", hc.phi), (f"{key}.b", hc.b),
                      (f"{key}.alpha", hc.alpha)]
        if layer.dense:
            named += [("mlp_gate", f.gate.weight), ("mlp_up", f.up.weight),
                      ("mlp_down", f.down.weight)]
        else:
            named += [("router", f.gate.wg), ("router_bias", f.gate.bias),
                      ("w_gate", f.w1), ("w_up", f.w3), ("w_down", f.w2)]
            named += zip(("shared_gate", "shared_up", "shared_down"),
                         f.shared)
        out.update({f"layers.{i}.{k}": v for k, v in named})
    return {k: params[v.name] for k, v in out.items()}


def seed_maps(ex, model, seed, beyond_clamp=True):
    """Every hyper-connection's ``phi`` ~ N(0, 1 / (n C)) (so ``v' phi`` ~
    N(0, 1)), gains uniform in 0.5 .. 1 and ``b`` ~ N(0, 1/4) (logits of
    ``Hres`` about 0.9 wide: twenty Sinkhorn rounds bring them to within 1e-4
    of doubly stochastic, two do not), from ``seed``, written over the
    executor's masters; with ``beyond_clamp`` one entry of the last
    sublayer's ``b_res`` is ``BEYOND_CLAMP``: ``exp`` of it overflows f32
    where the clamp is left out."""
    import jax.numpy as jnp
    rng = np.random.default_rng([int(seed), 9])
    hcs = [hc for layer in model.decoder_layers()
           for hc in (layer.attn_hc, layer.mlp_hc)]
    for hc in hcs:
        n = hc.n
        rows, width = hc.phi.shape
        b = rng.normal(0, 0.5, 2 * n + n * n)
        if beyond_clamp and hc is hcs[-1]:
            (i, j), value = BEYOND_CLAMP
            b[2 * n + i * n + j] = value
        for var, value in (
                (hc.phi, rng.normal(0, rows ** -0.5, (rows, width))),
                (hc.b, b), (hc.alpha, rng.uniform(0.5, 1.0, 3))):
            ex.params[var.name] = jnp.asarray(value, jnp.float32)


def sums_gap(hres):
    """How far the rows and columns of ``[.., n, n]`` matrices sum from 1:
    the largest."""
    h = np.asarray(hres, np.float64)
    return float(max(np.abs(h.sum(-1) - 1).max(),
                     np.abs(h.sum(-2) - 1).max()))


class Program(Qwen3NextProgram):
    """One Executor with a ``train`` subgraph (loss, update, per-layer expert
    load and router bias) and, for the correctness check, a ``validate``
    subgraph of the same loss, its two terms, the logits, the first expert
    layer's MLA output, every sublayer's ``Hres``, the same load and what
    each token chose.  ``make_batches``, ``retraces``, ``uniform_loss`` and
    ``kernel_choices`` are the Llama builder's, ``close`` the Qwen3-Next
    builder's, ``step`` and ``forward_passes`` the Ling builder's (a step
    that left a routed pair without a row reports a loss that is not
    finite)."""

    KERNELS = LlamaProgram.KERNELS + ("hetu_moe_rows_sum",)

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu.models import Xing4Config, Xing4ForCausalLM
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
        assert self.held[1] == config["n_routed_experts"], (
            "n_routed_experts in the configuration file is the experts held")
        c = Xing4Config(
            seq_len=S, n_routed_experts=dep["n_routed_experts"],
            experts_held=self.held, remat=job["remat"],
            router_bias_update_rate=job["router_bias_update_rate"],
            mtp_loss_weight=job["mtp_loss_weight"],
            **{key: config[key] for key in HF_KEYS})
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, S), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, S), dtype=np.int32)}
        self.model = Xing4ForCausalLM(c)
        logits = self.model(self.nodes["ids"])
        loss, terms = self.model.loss_terms(
            self.nodes["ids"], self.nodes["labels"], logits=logits)
        loads = self.model.moe_loads()
        biases = self.model.router_biases()
        chosen = [m.chosen() for m in self.model.moe_layers()]
        self.n_layers = len(loads)
        self.hres = self.model.hc_maps()
        # the first expert layer's MLA output beside the logits
        self.probed_layer = min(c.first_k_dense_replace, c.num_layers - 1)
        mixer_out = self.model.model.layers[self.probed_layer].mixer_out
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        self.ex = ht.Executor(
            {"train": [loss, opt.minimize(loss)] + loads + biases,
             "validate": ([loss] + [terms[t] for t in TERMS]
                          + [logits, mixer_out] + self.hres + loads
                          + chosen)},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]))
        seed_maps(self.ex, self.model, seed)
        self.params_m = sum(int(np.prod(v.shape))
                            for v in self.ex.params.values()) / 1e6
        dense = c.first_k_dense_replace
        say(f"Xing4.0 decoder: hidden {c.hidden_size} in {c.hc_mult} streams "
            f"(hyper-connections: {len(self.hres)} sublayers, "
            f"{c.hc_iters} Sinkhorn rounds, clamp {c.hc_clamp}), "
            f"{c.num_layers} layers + {c.mtp_layers} MTP depth; MLA "
            f"{c.num_heads} heads, keys {c.qk_nope_head_dim}+"
            f"{c.qk_rope_head_dim}, values {c.v_head_dim}, query rank "
            f"{c.q_lora_rank}, latent {c.kv_lora_rank}, YaRN "
            f"{c.rope_scaling}, scale x {c.softmax_scale_mult}; the first "
            f"{dense} FFN(s) dense {c.dense_intermediate_size} wide, then "
            f"router {c.num_experts} wide, {c.moe_k} a token, experts "
            f"{self.held[0]}..{self.held[0] + self.held[1] - 1} held (width "
            f"{c.intermediate_size}), shared expert {c.shared_width}; "
            f"vocabulary slice {c.vocab_size} of {dep['vocab_size']}; batch "
            f"{B} x {S}, {self.params_m:.1f} M parameters, "
            f"{job['compute_dtype']} compute over f32 masters, "
            f"{job['optimizer']}, recomputed: {job['remat']}, loss = ce + "
            f"{c.mtp_loss_weight} mtp")

    #: a step in which any layer routed more pairs to its held experts than
    #: it computed reports NaN; two forward passes of a layer a step at the
    #: most where whole layers are recomputed
    step = Ling3Program.step
    forward_passes = Ling3Program.forward_passes

    def close(self):
        # the Qwen3-Next builder's line reads the router's width by its key
        self.config = dict(self.config, deployment=dict(
            self.config["deployment"],
            num_experts=self.config["deployment"]["n_routed_experts"]))
        super().close()

    def pallas_ops(self):
        from hetu_tpu.ops.pallas import dispatch
        return (("flash_attention", "softmax_ce", "moe_gmm", "moe_rows")
                if dispatch.mosaic() else ())

    def expected_kernel_shapes(self):
        """Flash attention's work: the forward pass writes batch x heads x
        positions x the VALUES' head size (128; the scores are 192 wide:
        ``score_dim``); ``attention_passes`` is the passes a step REQUIRES
        (one forward and one backward a decoder layer, the MTP depth's
        among them), ``attention_layers`` the MOST forward calls a step may
        make (a recomputed layer's twice).  ``ce_rows`` is the rows of ONE
        loss call; a step makes two (the head is walked twice)."""
        c = self.config
        heads, dv = c["num_attention_heads"], c["v_head_dim"]
        layers = self.model.attention_layers
        return {"flash_dims": (self.batch, heads, self.seq, dv),
                "flash_elements": self.batch * heads * self.seq * dv,
                "flash_rows": self.batch * heads, "head_dim": dv,
                "score_dim": c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
                "attention_passes": layers,
                "attention_layers": layers * self.forward_passes,
                "causal": True,
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.batch * self.seq,
                "hc_sublayers": len(self.hres),
                "moe_pairs": self.tokens_per_step * c["num_experts_per_tok"]}

    def eval_loss(self, feed):
        """The program's loss on ``feed``, ``{"loss", "ce", "mtp",
        "logits_gap", "attention_gap", "hc_res_gap", "hc_sums_gap",
        "dropped", "routing_mismatch"}``, through the executor's
        ``validate`` subgraph.  ``logits_gap`` is the relative L2 distance of
        the program's logits from those ``reference_loss`` kept from the same
        batch (it runs first), ``attention_gap`` that of the first expert
        layer's MLA output, ``hc_res_gap`` the largest difference of an entry
        of ``Hres`` over all sublayers and positions, ``hc_sums_gap`` how far
        the program's own ``Hres`` rows and columns sum from 1; ``dropped``
        and ``routing_mismatch`` as the Qwen3-Next builder's."""
        out = self.ex.run("validate", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        n, h = self.n_layers, len(self.hres)
        kept = self.kept
        got = dict(zip(("loss",) + TERMS, map(float, out[:3])))
        got["logits_gap"] = logits_gap(out[3], kept.pop("logits"))
        got["attention_gap"] = logits_gap(out[4], kept["mixer"])
        hres = np.stack(out[5:5 + h]).astype(np.float32)
        got["hc_res_gap"] = float(np.abs(hres - kept["hres"]).max())
        got["hc_sums_gap"] = sums_gap(hres)
        out = out[5 + h:]
        loads = np.asarray(out[:n], np.float64)          # [layers, 4, held]
        got["dropped"] = float(1.0 - loads[:, 1].sum() / loads[:, 0].sum())
        want = kept["chosen"]
        E = self.config["deployment"]["n_routed_experts"]
        shared = 0
        for mine, theirs in zip(out[n:], want):
            hot = np.zeros((len(theirs), E), bool)
            np.put_along_axis(hot, np.asarray(theirs), True, axis=1)
            shared += np.take_along_axis(hot, np.asarray(mine), 1).sum()
        got["routing_mismatch"] = float(1.0 - shared / want.size)
        return got

    def reference_loss(self, feed, chunk, **lower):
        """The plain reference's loss, as ``eval_loss`` names it, on all of
        ``feed`` with this executor's present weights (its f32 masters, read
        in place), the same held experts and the same vocabulary slice,
        ``chunk`` sequences at a time.  What the comparison needs beside the
        sums stays on ``self.kept``.  ``lower``: the reference's
        ``matmul_inputs`` or ``without`` (a control's reading)."""
        import jax
        from ..reference import xing4 as ref
        params = reference_params(self.model, self.ex.params)
        sums = jax.jit(lambda p, i, l: ref.loss_sums(
            p, self.config, i, l, held=self.held, keep_logits=True,
            keep_mixer=self.probed_layer, **lower))
        ids = np.asarray(feed[self.nodes["ids"]])
        labels = np.asarray(feed[self.nodes["labels"]])
        #: what is kept of a chunk, and the axis its sequences lie along
        axes = {"chosen": 1, "logits": 0, "mixer": 0, "hres": 1}
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
        out = {k: float(v) for k, v in ref.loss_from_sums(
            tot, self.config["job"]["mtp_loss_weight"]).items()}
        out.update(logits_gap=0.0, attention_gap=0.0, hc_res_gap=0.0,
                   hc_sums_gap=sums_gap(self.kept["hres"]), dropped=0.0,
                   routing_mismatch=0.0)
        return out


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
