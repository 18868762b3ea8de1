"""Causal-LM pretraining of a Qwen3-Next decoder at one chip's share of an
expert-parallel job, built the way ``examples/nlp/train_llama.py --model
qwen3-next-80b-a3b`` builds it: ``Qwen3NextForCausalLM`` from the
configuration's published keys, ``loss`` and ``opt.minimize`` through
``ht.Executor``, a fresh numpy batch of ids and next-token labels fed every
step, each layer's per-expert load fetched beside the loss and counted by
``hetu_tpu.layers.moe.record_moe_load``.  Knows nothing of cells: sizes come
from the configuration file, batch shape from the traffic file.

The family's files: ``configs/qwen3-next-80b-a3b-pretrain.json`` (the
published ``config.json`` keys; ``num_experts`` there is the experts HELD on
this chip and ``vocab_size`` the slice, both listed in ``reduced``; the
``deployment`` group holds the published counts, over how many chips a layer
is shared and which experts this one holds; ``job`` the optimizer, the loss
weight, what is recomputed),
this builder, ``reference/qwen3_next.py`` (the plain reference, given the
same held experts and the same slice), ``flops_qwen3next.py`` (operations and
bytes) and the readers ``metrics/*.qwen3_next.py``, ``metrics/gdn_*.py``,
``metrics/moe_held_pair_share.py`` with ``metrics/_scopes.py``.
"""

from __future__ import annotations

import numpy as np

from .common import jax_seed
from .llama import Program as LlamaProgram

#: published keys that are Qwen3NextConfig arguments under their own names
HF_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
           "num_attention_heads", "num_key_value_heads", "head_dim",
           "partial_rotary_factor", "rope_theta", "rms_norm_eps",
           "full_attention_interval", "linear_conv_kernel_dim",
           "linear_key_head_dim", "linear_value_head_dim",
           "linear_num_key_heads", "linear_num_value_heads",
           "num_experts_per_tok", "moe_intermediate_size",
           "shared_expert_intermediate_size", "norm_topk_prob",
           "tie_word_embeddings")

TERMS = ("ce", "lbl")

#: value heads of the long-memory probe (``Program.delta_rule_gap``) and the
#: decay a position of the first and the last of them, log-spaced between: a
#: memory of about 10,000 down to 10 positions
PROBE_HEADS = 4
PROBE_DECAY = (1e-4, 1e-1)


def reference_params(model, params):
    """The program's weights under the plain reference's names
    (``chipbench/reference/qwen3_next.py`` ``WEIGHTS``), found by walking
    the model object, not by parsing variable names."""
    out = {"embed": model.model.embed.weight, "norm": model.model.norm.scale,
           "lm_head": model.lm_head.weight}
    for i, layer in enumerate(model.model.layers):
        m = layer.mlp
        named = [("input_norm", layer.input_norm.scale),
                 ("post_norm", layer.post_norm.scale),
                 ("router", m.gate.wg), ("w_gate", m.w1), ("w_up", m.w3),
                 ("w_down", m.w2)]
        named += zip(("shared_gate", "shared_up", "shared_down",
                      "shared_sigmoid"), m.shared)
        if layer.kind == "full_attention":
            a = layer.attn
            named += [("q", a.q_proj.weight), ("k", a.k_proj.weight),
                      ("v", a.v_proj.weight), ("o", a.out_proj.weight),
                      ("q_norm", a.q_norm.scale), ("k_norm", a.k_norm.scale)]
        else:
            g = layer.gdn
            named += [("qkvz", g.in_proj_qkvz), ("ba", g.in_proj_ba),
                      ("conv", g.conv), ("a_log", g.a_log),
                      ("dt_bias", g.dt_bias), ("gdn_norm", g.norm),
                      ("gdn_out", g.out_proj)]
        out.update({f"layers.{i}.{k}": v for k, v in named})
    return {k: params[v.name] for k, v in out.items()}


def delta_rule_gap(config, seq, seed, say, rule):
    """How far a delta rule ends from the plain reference's where the state
    has to remember: the largest relative gap (L2, a head) between the last
    states of ``rule(q, k, v, g, beta) -> (o, last state)`` and of the
    reference's recurrence with its f32 state, both given the same seeded q,
    k, v (unit keys, in the compute type), g and beta (f32) for
    ``PROBE_HEADS`` value heads of the published size over ``seq``
    positions, with decays of ``PROBE_DECAY`` a position.  At its initial
    values the model forgets within a few positions (``A ~ U(0, 16)``), where
    no type of the state shows in the loss; a trained model's heads do not,
    and there a state carried in bf16 is wrong by 2% where the chunked rule
    with bf16 products is by 0.06% (the traffic file's
    ``reference_tolerance_why``)."""
    import jax
    import jax.numpy as jnp
    from ..reference import qwen3_next as ref
    shape = (1, seq, PROBE_HEADS)
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    rng = np.random.default_rng([int(seed), 7])

    def unit(x):
        return x / np.sqrt((x * x).sum(-1, keepdims=True) + 1e-6)
    ct = getattr(jnp, config["job"]["compute_dtype"])
    q, k, v = (jnp.asarray(x, ct) for x in (
        unit(rng.standard_normal(shape + (dk,))) * dk ** -0.5,
        unit(rng.standard_normal(shape + (dk,))),
        rng.standard_normal(shape + (dv,))))
    rate = np.geomspace(*PROBE_DECAY, PROBE_HEADS)
    g = jnp.asarray(-rate * np.logaddexp(
        0.0, rng.standard_normal(shape) + 1.0), jnp.float32)
    beta = jnp.asarray(1.0 / (1.0 + np.exp(-rng.standard_normal(shape))),
                       jnp.float32)
    o, last = jax.jit(rule)(q, k, v, g, beta)
    with jax.default_matmul_precision("highest"):
        o_ref, last_ref = jax.jit(ref.delta_rule)(
            *(x.astype(jnp.float32) for x in (q, k, v)), g, beta)

    def gaps(got, want, head_axis):
        got, want = (np.asarray(x, np.float64) for x in (got, want))
        rest = tuple(i for i in range(got.ndim) if i != head_axis)
        return np.sqrt(((got - want) ** 2).sum(rest) / (want ** 2).sum(rest))
    state, out = gaps(last, last_ref, 1), gaps(o, o_ref, 2)
    say(f"delta rule at long memory ({PROBE_HEADS} heads of {dk} x {dv} over "
        f"{seq} positions, decays a position "
        f"{[float(f'{r:.0e}') for r in rate]}): relative gap to the "
        f"recurrence with an f32 state, by head: last state "
        f"{[float(f'{x:.2e}') for x in state]}, outputs "
        f"{[float(f'{x:.2e}') for x in out]}")
    return float(state.max())


class Program(LlamaProgram):
    """One Executor with a ``train`` subgraph (loss, update, per-layer
    expert load) and, for the correctness check, a ``validate`` subgraph of
    the same loss, its terms, the same load and what each token chose.
    ``make_batches``, ``retraces``, ``uniform_loss``, ``kernel_choices``,
    ``pallas_ops`` and ``KERNELS`` are the Llama builder's.

    Two things the configuration states are held here beyond the loss terms.
    Dropless routing: a step of the window in which a pair routed to a held
    expert got no row (the static row bound) reports a loss that is not
    finite, so the run counts it as failed and is not ``correct``
    (``step``).  The f32 DeltaNet state: ``delta_rule_gap``."""

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu.models import Qwen3NextConfig, Qwen3NextForCausalLM
        from hetu_tpu.ops.pallas import dispatch

        self.config, self.mix, self._say = config, mix, say
        self.seed = seed
        self.held_peak, self.steps_dropping = 0.0, 0
        job, dep = config["job"], config["deployment"]
        self._choices_before = dispatch.choices()
        assert config["hidden_act"] == "silu" and not config["mlp_only_layers"]
        assert config["decoder_sparse_step"] == 1
        B, S = int(mix["batch"]), int(mix["seq"])
        assert S <= config["max_position_embeddings"]
        self.batch, self.seq = B, S
        self.tokens_per_step = B * S
        self.held = tuple(dep["experts_held"])
        assert self.held[1] == config["num_experts"], (
            "num_experts in the configuration file is the experts held here")
        c = Qwen3NextConfig(
            seq_len=S, num_experts=dep["num_experts"],
            experts_held=self.held, router_aux_loss_coef=job["lbl_weight"],
            remat=job["remat"],
            **{key: config[key] for key in HF_KEYS})
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, S), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, S), dtype=np.int32)}
        self.model = Qwen3NextForCausalLM(c)
        loss, terms = self.model.loss_terms(self.nodes["ids"],
                                            self.nodes["labels"])
        loads = self.model.moe_loads()
        chosen = [layer.mlp.chosen() for layer in self.model.model.layers]
        self.n_layers = len(loads)
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        self.ex = ht.Executor(
            {"train": [loss, opt.minimize(loss)] + loads,
             "validate": ([loss] + [terms[t] for t in TERMS] + loads
                          + chosen)},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]))
        self.params_m = sum(int(np.prod(v.shape))
                            for v in self.ex.params.values()) / 1e6
        say(f"Qwen3-Next decoder: hidden {c.hidden_size}, layers "
            f"{list(c.layer_types)}, attention {c.num_heads}/"
            f"{c.num_kv_heads} heads of {c.head_dim} (rotary {c.rotary_dim}), "
            f"DeltaNet {c.linear_num_key_heads}/{c.linear_num_value_heads} "
            f"heads of {c.linear_key_head_dim}; router {c.num_experts} wide, "
            f"{c.moe_k} a token, experts {self.held[0]}.."
            f"{self.held[0] + self.held[1] - 1} held (width "
            f"{c.intermediate_size}), shared "
            f"expert {c.shared_width}; vocabulary slice {c.vocab_size} of "
            f"{dep['vocab_size']}; batch {B} x {S}, {self.params_m:.1f} M "
            f"parameters, {job['compute_dtype']} compute over f32 masters, "
            f"{job['optimizer']}, recomputed: {job['remat']}, loss = ce + "
            f"{c.moe_aux_coeff} lbl")

    def step(self, feed):
        """The Llama builder's step.  The configuration states dropless
        routing and the rows buffer is bounded: a step in which any layer
        routed more pairs to its held experts than it computed (``load``
        rows 0 and 1) has not done the configuration's work, and its loss is
        returned as NaN, which the loop counts as a failed step and an
        incorrect run.  Also keeps the most pairs any layer laid out in a
        step, which ``close`` says against the static row bound."""
        from hetu_tpu.layers.moe import record_moe_load
        out = self.ex.run("train", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        dropped = 0.0
        for i, load in enumerate(out[2:]):
            record_moe_load(f"layer{i}", load)
            self.held_peak = max(self.held_peak, float(load[0].sum()))
            dropped += float(load[0].sum() - load[1].sum())
        if dropped:
            self.steps_dropping += 1
            return float("nan")
        return float(out[0])

    def close(self):
        from hetu_tpu.ops.moe import held_rows
        c = self.config
        bound = held_rows(self.tokens_per_step * c["num_experts_per_tok"],
                          c["deployment"]["num_experts"], self.held[1])
        self._say(f"pairs on held experts, the fullest layer and step: "
                  f"{self.held_peak:.0f} of the {bound} rows the bound "
                  f"allows ({self.held_peak / bound:.2f}); steps that "
                  f"dropped a pair, each reported with a loss that is not "
                  f"finite: {self.steps_dropping}")
        super().close()

    def expected_kernel_shapes(self):
        """Flash attention's work (batch, query heads, positions, head size:
        the KV heads are repeated before the kernel; one attention layer a
        period), the rows of the loss kernel and the pairs a step routes over
        all experts."""
        c = self.config
        heads, hd = c["num_attention_heads"], c["head_dim"]
        layers = self.model.attention_layers
        return {"flash_dims": (self.batch, heads, self.seq, hd),
                "flash_elements": self.batch * heads * self.seq * hd,
                "flash_rows": self.batch * heads, "head_dim": hd,
                "attention_layers": layers,
                "causal": True,
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.batch * self.seq,
                "moe_pairs": self.tokens_per_step * c["num_experts_per_tok"]}

    def eval_loss(self, feed):
        """The program's loss on ``feed`` and its terms, ``{"loss", "ce",
        "lbl", "dropped", "routing_mismatch", "delta_rule_gap"}``, through
        the executor's ``validate`` subgraph.  ``dropped`` is the share of
        the pairs routed to held experts that got no row (the static row
        bound); ``routing_mismatch`` the share of the reference's (token,
        expert) pairs, over all experts, that the program did not choose;
        ``delta_rule_gap`` is not of ``feed`` (``delta_rule_gap``)."""
        out = self.ex.run("validate", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        n = self.n_layers
        got = dict(zip(("loss",) + TERMS, map(float, out[:3])))
        loads = np.asarray(out[3:3 + n], np.float64)   # [layers, 3, held]
        got["dropped"] = float(1.0 - loads[:, 1].sum() / loads[:, 0].sum())
        want = self._ref_chosen            # reference_loss runs first
        E = self.config["deployment"]["num_experts"]
        shared = 0
        for mine, theirs in zip(out[3 + n:], want):
            hot = np.zeros((len(theirs), E), bool)
            np.put_along_axis(hot, np.asarray(theirs), True, axis=1)
            shared += np.take_along_axis(hot, np.asarray(mine), 1).sum()
        got["routing_mismatch"] = float(1.0 - shared / want.size)
        got["delta_rule_gap"] = self.delta_rule_gap()
        return got

    def delta_rule_gap(self):
        """``delta_rule_gap`` of the function the layer's ``hetu_gdn_scan``
        node calls, at the cell's sequence length, from the run's seed."""
        from hetu_tpu.ops.gated_delta import chunk_gated_delta_rule
        return delta_rule_gap(self.config, self.seq, self.seed, self._say,
                              chunk_gated_delta_rule)

    def reference_loss(self, feed, chunk):
        """The plain reference's loss and terms, as ``eval_loss`` names
        them, on all of ``feed`` with this executor's present weights, the
        same held experts and the same vocabulary slice, ``chunk`` sequences
        at a time."""
        import jax
        from ..reference import qwen3_next as ref
        params = reference_params(self.model, self.ex.params)
        sums = jax.jit(lambda p, i, l: ref.loss_sums(
            p, self.config, i, l, held=self.held))
        ids = np.asarray(feed[self.nodes["ids"]])
        labels = np.asarray(feed[self.nodes["labels"]])
        tot, chosen = None, []
        for lo in range(0, self.batch, chunk):
            part = jax.device_get(sums(params, ids[lo:lo + chunk],
                                       labels[lo:lo + chunk]))
            chosen.append(part.pop("chosen"))
            tot = part if tot is None else {k: tot[k] + v
                                            for k, v in part.items()}
        self._ref_chosen = np.concatenate(chosen, axis=1)  # [layers, T, k]
        out = {k: float(v) for k, v in ref.loss_from_sums(
            tot, self.config["job"]["lbl_weight"]).items()}
        out.update(dropped=0.0, routing_mismatch=0.0, delta_rule_gap=0.0)
        return out


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
