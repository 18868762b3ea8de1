"""Causal-LM pretraining of a Nemotron-H decoder at one chip's share of an
expert-parallel job, built the way ``examples/nlp/train_llama.py --model
nemotron-3-nano-30b-a3b`` builds it: ``NemotronHForCausalLM`` from the
configuration's published keys, ``loss`` and ``opt.minimize`` through
``ht.Executor``, a fresh numpy batch of ids and next-token labels fed every
step, each expert block's per-expert load and router bias fetched beside the
loss and counted by ``hetu_tpu.layers.moe.record_moe_load``.  Knows nothing
of cells: sizes come from the configuration file, batch shape from the
traffic file.

The family's files: ``configs/nemotron-3-nano-30b-a3b-pretrain.json`` (the
published ``config.json`` keys; ``n_routed_experts`` there is the experts
HELD on this chip, ``vocab_size`` the slice, ``num_hidden_layers`` and
``hybrid_override_pattern`` one pipeline stage, all listed in ``reduced``;
the ``deployment`` group holds the published values, over how many chips a
layer is shared and which experts this one holds; ``job`` the optimizer, the
the loss weight, the bias's update rate, what is
recomputed), this builder,
``reference/nemotron_h.py`` (the plain reference, given the same held experts
and the same slice), ``flops_nemotronh.py`` (operations and bytes) and the
readers ``metrics/*.nemotron_h.py`` and
``metrics/ssm_block_device_ms_per_step.py`` with ``metrics/_scopes.py``.
"""

from __future__ import annotations

import numpy as np

from .common import jax_seed
from .llama import Program as LlamaProgram

#: published keys that are NemotronHConfig arguments under their own names
HF_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
           "hybrid_override_pattern", "num_attention_heads",
           "num_key_value_heads", "head_dim", "mamba_num_heads",
           "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
           "chunk_size", "time_step_min", "time_step_max", "time_step_floor",
           "num_experts_per_tok", "moe_intermediate_size",
           "moe_shared_expert_intermediate_size", "n_shared_experts",
           "norm_topk_prob", "routed_scaling_factor", "layer_norm_epsilon",
           "rescale_prenorm_residual", "tie_word_embeddings")

TERMS = ("ce", "lbl")

#: heads of the long-memory probe (``ssd_state_gap``) and the decay a
#: position (``dt |A|``) of the first and the last of them, log-spaced
#: between: a memory of about 10,000 down to 10 positions
PROBE_HEADS = 4
PROBE_DECAY = (1e-4, 1e-1)


def reference_params(model, params):
    """The program's weights under the plain reference's names
    (``chipbench/reference/nemotron_h.py`` ``WEIGHTS``), found by walking
    the model object, not by parsing variable names."""
    out = {"embed": model.model.embed.weight, "norm": model.model.norm.scale,
           "lm_head": model.lm_head.weight}
    for i, block in enumerate(model.model.layers):
        m = block.mixer
        named = [("norm", block.norm.scale)]
        if block.kind == "M":
            named += [("in_proj", m.in_proj), ("conv", m.conv),
                      ("conv_bias", m.conv_bias), ("dt_bias", m.dt_bias),
                      ("a_log", m.a_log), ("d", m.d_skip),
                      ("ssm_norm", m.norm), ("out_proj", m.out_proj)]
        elif block.kind == "*":
            named += [("q", m.q_proj.weight), ("k", m.k_proj.weight),
                      ("v", m.v_proj.weight), ("o", m.out_proj.weight)]
        else:
            named += [("router", m.gate.wg), ("router_bias", m.gate.bias),
                      ("w_up", m.w1), ("w_down", m.w2)]
            named += zip(("shared_up", "shared_down"), m.shared)
        out.update({f"layers.{i}.{k}": v for k, v in named})
    return {k: params[v.name] for k, v in out.items()}


def probe_inputs(config, seq, seed):
    """Seeded inputs of the long-memory probe: ``x``, ``B``, ``C`` in the
    compute type, ``dt`` and ``A`` in f32 for ``PROBE_HEADS`` heads of the
    published size in one group over ``seq`` positions; head ``j`` forgets
    ``PROBE_DECAY`` (log-spaced) a position at ``dt = 1``, and ``dt`` is
    drawn about 1."""
    import jax.numpy as jnp
    p, n = config["mamba_head_dim"], config["ssm_state_size"]
    rng = np.random.default_rng([int(seed), 7])
    ct = getattr(jnp, config["job"]["compute_dtype"])
    x = jnp.asarray(rng.standard_normal((1, seq, PROBE_HEADS, p)), ct)
    B, C = (jnp.asarray(rng.standard_normal((1, seq, 1, n)) * n ** -0.5, ct)
            for _ in range(2))
    dt = jnp.asarray(np.logaddexp(0.0, rng.standard_normal(
        (1, seq, PROBE_HEADS)) + 0.5), jnp.float32)
    A = jnp.asarray(-np.geomspace(*PROBE_DECAY, PROBE_HEADS), jnp.float32)
    return x, dt, A, B, C


def relative_gaps(got, want, head_axis):
    """The relative L2 gap a head."""
    got, want = (np.asarray(t, np.float64) for t in (got, want))
    rest = tuple(i for i in range(got.ndim) if i != head_axis)
    return np.sqrt(((got - want) ** 2).sum(rest) / (want ** 2).sum(rest))


def ssd_state_gap(config, seq, seed, say, scan):
    """How far a state-space scan ends from the plain reference's where the
    state has to remember: the largest relative gap (L2, a head) between the
    last states of ``scan(x, dt, A, B, C) -> (y, last state)`` and of the
    reference's recurrence with its f32 state, both given ``probe_inputs``.
    At its initial values the model's heads forget within tens to thousands
    of positions (``dt`` 0.001 to 0.1 times ``A`` 1 to 16) and the loss
    terms alone hold no type of the state; a state carried in bf16 shows
    here (the traffic file's ``reference_tolerance_why``)."""
    import jax
    import jax.numpy as jnp
    from ..reference import nemotron_h as ref
    x, dt, A, B, C = probe_inputs(config, seq, seed)
    y, last = jax.jit(scan)(x, dt, A, B, C)
    with jax.default_matmul_precision("highest"):
        y_ref, last_ref = jax.jit(ref.ssm_recurrence)(
            x.astype(jnp.float32), dt, A, B.astype(jnp.float32),
            C.astype(jnp.float32))
    state, out = relative_gaps(last, last_ref, 1), relative_gaps(y, y_ref, 2)
    say(f"state-space scan at long memory ({PROBE_HEADS} heads of "
        f"{x.shape[-1]} x {B.shape[-1]} over {seq} positions, decays a "
        f"position {[float(f'{-a:.0e}') for a in np.asarray(A)]} x dt): "
        f"relative gap to the recurrence with an f32 state, by head: last "
        f"state {[float(f'{g:.2e}') for g in state]}, outputs "
        f"{[float(f'{g:.2e}') for g in out]}")
    return float(state.max())


class Program(LlamaProgram):
    """One Executor with a ``train`` subgraph (loss, update, per-block
    expert load and router bias) and, for the correctness check, a
    ``validate`` subgraph of the same loss, its terms, the same load and what
    each token chose.  ``make_batches``, ``retraces``, ``uniform_loss``,
    ``kernel_choices``, ``pallas_ops`` and ``KERNELS`` are the Llama
    builder's.

    Two things the configuration states are held here beyond the loss terms.
    Dropless routing: a step of the window in which a pair routed to a held
    expert was not computed reports a loss that is not finite, so the run
    counts it as failed and is not ``correct`` (``step``).  The f32
    state-space state: ``ssd_state_gap``."""

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu.models import NemotronHConfig, NemotronHForCausalLM
        from hetu_tpu.ops.pallas import dispatch

        self.config, self.mix, self._say = config, mix, say
        self.seed = seed
        self.held_peak, self.steps_dropping = 0.0, 0
        self.bias_peak, self.steps_over, self.pairs_over = 0.0, 0, 0.0
        self.held_by_step = []      # the fullest block's pairs, every step
        job, dep = config["job"], config["deployment"]
        self._choices_before = dispatch.choices()
        assert (config["mlp_hidden_act"] == "relu2"
                and config["mamba_hidden_act"] == "silu")
        assert config["use_conv_bias"] and not any(
            config[k] for k in ("attention_bias", "mamba_proj_bias",
                                "mlp_bias", "use_bias"))
        assert config["n_group"] == config["topk_group"] == 1, (
            "no group-limited routing")
        B, S = int(mix["batch"]), int(mix["seq"])
        assert S <= config["max_position_embeddings"]
        self.batch, self.seq = B, S
        self.tokens_per_step = B * S
        self.held = tuple(dep["experts_held"])
        assert self.held[1] == config["n_routed_experts"], (
            "n_routed_experts in the configuration file is the experts held "
            "here")
        c = NemotronHConfig(
            seq_len=S, n_routed_experts=dep["n_routed_experts"],
            experts_held=self.held, router_aux_loss_coef=job["lbl_weight"],
            router_bias_update_rate=job["router_bias_update_rate"],
            remat=job["remat"],
            **{key: config[key] for key in HF_KEYS})
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, S), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, S), dtype=np.int32)}
        self.model = NemotronHForCausalLM(c)
        loss, terms = self.model.loss_terms(self.nodes["ids"],
                                            self.nodes["labels"])
        loads = self.model.moe_loads()
        chosen = [m.chosen() for m in self.model.moe_layers()]
        self.n_layers = len(loads)
        # a pattern without an E block (the rehearsal's) has no lbl node
        self.terms = [t for t in TERMS if t in terms]
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        self.ex = ht.Executor(
            {"train": ([loss, opt.minimize(loss)] + loads
                       + self.model.router_biases()),
             "validate": ([loss] + [terms[t] for t in self.terms] + loads
                          + chosen)},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]))
        self.params_m = sum(int(np.prod(v.shape))
                            for v in self.ex.params.values()) / 1e6
        say(f"Nemotron-H decoder: hidden {c.hidden_size}, blocks "
            f"{c.pattern} (M Mamba-2: {c.mamba_num_heads} heads of "
            f"{c.mamba_head_dim}, state {c.ssm_state_size}, {c.n_groups} "
            f"groups, chunks of {c.chunk_size}; * attention "
            f"{c.num_heads}/{c.num_kv_heads} heads of {c.head_dim}, no "
            f"rotary; E router {c.num_experts} wide by sigmoid with a bias "
            f"moved by {c.router_bias_update_rate} a step, {c.moe_k} a "
            f"token x {c.routed_scaling_factor}, relu2 experts "
            f"{self.held[0]}..{self.held[0] + self.held[1] - 1} held (width "
            f"{c.intermediate_size}), shared expert {c.shared_width}); "
            f"vocabulary slice {c.vocab_size} of {dep['vocab_size']}; batch "
            f"{B} x {S}, {self.params_m:.1f} M parameters, "
            f"{job['compute_dtype']} compute over f32 masters, "
            f"{job['optimizer']}, recomputed: "
            f"{job['remat']}, loss = ce + {c.moe_aux_coeff} lbl")

    def step(self, feed):
        """The Llama builder's step, with the router's bias counted beside
        the load.  The configuration states dropless routing: a step in
        which any block routed more pairs to its held experts than it
        computed (``load`` rows 0 and 1) has not done the configuration's
        work, and its loss is returned as NaN, which the loop counts as a
        failed step and an incorrect run.  Also keeps the most pairs any
        block routed here in a step, which ``close`` says against the rows
        of one pass, and the steps and pairs that took a further pass
        (``load`` row 3)."""
        from hetu_tpu.layers.moe import record_moe_load
        out = self.ex.run("train", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        n = self.n_layers
        dropped, fullest, over = 0.0, 0.0, 0.0
        for i, (load, bias) in enumerate(zip(out[2:2 + n], out[2 + n:])):
            record_moe_load(f"layer{i}", load, bias=bias)
            fullest = max(fullest, float(load[0].sum()))
            self.bias_peak = max(self.bias_peak, float(np.abs(bias).max()))
            dropped += float(load[0].sum() - load[1].sum())
            over += float(load[3].sum())
        self.held_by_step.append(fullest)
        self.held_peak = max(self.held_peak, fullest)
        self.steps_over += bool(over)
        self.pairs_over += over
        if dropped:
            self.steps_dropping += 1
            return float("nan")
        return float(out[0])

    def close(self):
        from hetu_tpu.ops.moe import held_rows
        c = self.config
        bound = held_rows(self.tokens_per_step * c["num_experts_per_tok"],
                          c["deployment"]["n_routed_experts"], self.held[1])
        self._say(f"pairs on held experts, the fullest block and step: "
                  f"{self.held_peak:.0f} against the {bound} rows of one "
                  f"pass ({self.held_peak / bound:.2f}); steps in which a "
                  f"block took a further pass: {self.steps_over} of "
                  f"{len(self.held_by_step)}, {self.pairs_over:.0f} pairs; "
                  f"the router's bias moved to at most "
                  f"{self.bias_peak:.4f}; steps that dropped a pair, each "
                  f"reported with a loss that is not finite: "
                  f"{self.steps_dropping}")
        every = max(1, len(self.held_by_step) // 16)
        shown = [round(v / bound, 2) for v in self.held_by_step[::every]]
        self._say(f"the fullest block's pairs over one pass's rows, every "
                  f"{every}th step: {shown}")
        super().close()

    def expected_kernel_shapes(self):
        """Flash attention's work (batch, query heads, positions, head size:
        the KV heads are repeated before the kernel; one call an attention
        block), the rows of the loss kernel and the pairs a step routes over
        all experts."""
        c = self.config
        heads, hd = c["num_attention_heads"], c["head_dim"]
        return {"flash_dims": (self.batch, heads, self.seq, hd),
                "flash_elements": self.batch * heads * self.seq * hd,
                "flash_rows": self.batch * heads, "head_dim": hd,
                "attention_layers": self.model.attention_layers,
                "causal": True,
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.batch * self.seq,
                "moe_pairs": self.tokens_per_step * c["num_experts_per_tok"]}

    def eval_loss(self, feed):
        """The program's loss on ``feed`` and its terms, ``{"loss", "ce",
        "lbl", "dropped", "routing_mismatch", "ssd_state_gap"}``, through
        the executor's ``validate`` subgraph.  ``dropped`` is the share of
        the pairs routed to held experts that were not computed;
        ``routing_mismatch`` the share of the reference's (token, expert)
        pairs, over all experts, that the program did not choose;
        ``ssd_state_gap`` is not of ``feed`` (``ssd_state_gap``)."""
        out = self.ex.run("validate", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        n, at = self.n_layers, 1 + len(self.terms)
        got = dict.fromkeys(TERMS, 0.0)
        got.update(zip(["loss"] + self.terms, map(float, out[:at])))
        loads = np.asarray(out[at:at + n], np.float64)  # [blocks, 3, held]
        want = self._ref_chosen            # reference_loss runs first
        E = self.config["deployment"]["n_routed_experts"]
        shared = 0
        for mine, theirs in zip(out[at + n:], want):
            hot = np.zeros((len(theirs), E), bool)
            np.put_along_axis(hot, np.asarray(theirs), True, axis=1)
            shared += np.take_along_axis(hot, np.asarray(mine), 1).sum()
        got["dropped"] = got["routing_mismatch"] = 0.0
        if n:
            got["dropped"] = float(
                1.0 - loads[:, 1].sum() / loads[:, 0].sum())
            got["routing_mismatch"] = float(1.0 - shared / want.size)
        got["ssd_state_gap"] = self.ssd_state_gap()
        return got

    def ssd_state_gap(self):
        """``ssd_state_gap`` of the function the blocks' ``hetu_ssm_scan``
        nodes call, at the cell's sequence length and the configuration's
        chunk, from the run's seed."""
        from hetu_tpu.ops import ssd
        chunk = self.config["chunk_size"]
        return ssd_state_gap(
            self.config, self.seq, self.seed, self._say,
            lambda *a: ssd.chunk_ssd(*a, chunk=chunk))

    def reference_loss(self, feed, chunk):
        """The plain reference's loss and terms, as ``eval_loss`` names
        them, on all of ``feed`` with this executor's present weights, the
        same held experts and the same vocabulary slice, ``chunk`` sequences
        at a time."""
        import jax
        from ..reference import nemotron_h as ref
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
        self._ref_chosen = np.concatenate(chosen, axis=1)  # [blocks, T, k]
        out = {k: float(v) for k, v in ref.loss_from_sums(
            tot, self.config["job"]["lbl_weight"]).items()}
        out.update(dropped=0.0, routing_mismatch=0.0, ssd_state_gap=0.0)
        return out


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
