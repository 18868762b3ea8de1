"""Causal-LM pretraining of an Ouro looped decoder at one pipeline stage, built
the way ``examples/nlp/train_llama.py`` builds a Llama: ``OuroForCausalLM``
from the configuration's published keys, ``loss_terms`` and ``opt.minimize``
through ``ht.Executor``, a fresh numpy batch of ids and next-token labels fed
every step, the ``[P]`` vector of mean exit shares fetched beside the loss
and set as ``hetu_loop_exit_share{pass}``
(``hetu_tpu.models.ouro.record_exit_shares``).  Knows nothing of cells: sizes
come from the configuration file, batch shape from the traffic file.

The family's files: ``configs/ouro-2.6b-pretrain.json`` (the published
``config.json`` keys; ``num_hidden_layers`` and ``layer_types`` there are one
pipeline stage, both in ``reduced``; ``deployment`` holds the published values;
``job`` the optimizer, the compute type, the entropy term's weight and what is
recomputed), this builder, ``reference/ouro.py`` (the plain reference),
``flops_ouro.py`` (operations) and the readers ``metrics/mfu.ouro.py``,
``metrics/flash_roofline.ouro.py``, ``metrics/exit_block_device_ms_per_step.py``
and ``metrics/loop_recompute_device_share.py``; ``README.ouro.md`` says what
each does.
"""

from __future__ import annotations

import numpy as np

from .common import counter, jax_seed
from .granite_hybrid import logits_gap
from .llama import Program as LlamaProgram

#: OuroConfig argument <- published key
HF_KEYS = {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
           "num_layers": "num_hidden_layers",
           "num_heads": "num_attention_heads",
           "num_kv_heads": "num_key_value_heads",
           "intermediate_size": "intermediate_size",
           "rope_theta": "rope_theta", "rms_eps": "rms_norm_eps",
           "tie_embeddings": "tie_word_embeddings",
           "total_ut_steps": "total_ut_steps"}

TERMS = ("ce", "entropy")
#: how far a step's mean exit shares may add up from 1 before the step
#: counts as failed
SHARE_SUM_TOLERANCE = 1e-3
#: positions a call of the reference's head: ``[2048, V]`` f32 logits
HEAD_BLOCK = 2048


def reference_params(model, params):
    """The program's weights under the plain reference's names
    (``chipbench/reference/ouro.py`` ``WEIGHTS``), found by walking the model
    object, not by parsing variable names.  The values are ``params``' own
    arrays: nothing is copied."""
    out = {"embed": model.model.embed.weight, "norm": model.model.norm.scale,
           "lm_head": model.lm_head.weight,
           "gate_w": model.exit_gate.weight, "gate_b": model.exit_gate.bias}
    for i, layer in enumerate(model.model.layers):
        a, m = layer.attn, layer.mlp
        out.update({f"layers.{i}.{k}": v for k, v in (
            ("n1", layer.input_norm.scale), ("n2", layer.attn_out_norm.scale),
            ("n3", layer.post_norm.scale), ("n4", layer.mlp_out_norm.scale),
            ("q", a.q_proj.weight), ("k", a.k_proj.weight),
            ("v", a.v_proj.weight), ("o", a.out_proj.weight),
            ("mlp_gate", m.gate.weight), ("mlp_up", m.up.weight),
            ("mlp_down", m.down.weight))})
    return {k: params[v.name] for k, v in out.items()}


def reference_run(params, c, ids, labels, beta, **how):
    """The plain reference on ONE sequence at the timed shape without its
    whole graph in one program: a layer application a call (one compilation
    serves all ``P x k``), the head a block of ``HEAD_BLOCK`` positions a
    call, each pass's logits taken to the host as they come (the process's
    peak of HBM is then the step's, not the comparison's).  ``how``:
    ``reference/ouro.py``'s ``passes``, ``matmul_inputs``, ``leave_out``.
    Returns ``finish``'s terms as floats, ``p [P, T]``, the ``P`` host arrays
    ``[T, V]`` of logits and the mean shares ``[P]``."""
    import jax
    import jax.numpy as jnp
    from ..reference import ouro as ref
    passes = how.get("passes") or c["total_ut_steps"]
    rounded, leave_out = how.get("matmul_inputs"), how.get("leave_out", ())
    # the weights go in as arguments: closed over, they would be constants
    # of the compiled programs (1.1 GB of them for the head)
    layer = jax.jit(lambda x, w: ref.layer(x, w, c, rounded, leave_out))
    norm = jax.jit(lambda x, s: ref._norm(x, s, c["rms_norm_eps"]))
    head = jax.jit(lambda h, w, lab: ref.head(h, w, lab, rounded))
    gate = jax.jit(lambda h, w: ref.gate(h, w, rounded))
    tops = {k: params[k] for k in ("lm_head", "gate_w", "gate_b")}
    flat = jnp.asarray(np.asarray(labels).reshape(-1))
    x = jnp.asarray(params["embed"], jnp.float32)[np.asarray(ids)]
    zs, ces, logits = [], [], []
    for _ in range(passes):
        for i in range(c["num_hidden_layers"]):
            x = layer(x, ref.layer_weights(params, i))
        h = norm(x, params["norm"])
        if "fed_norm" not in leave_out:
            x = h
        h = h.reshape(-1, h.shape[-1])
        zs.append(gate(h, tops))
        rows, ce = [], []
        for lo in range(0, h.shape[0], HEAD_BLOCK):
            out, part = head(h[lo:lo + HEAD_BLOCK], tops,
                             flat[lo:lo + HEAD_BLOCK])
            ce.append(part)
            rows.append(np.asarray(out))
            del out
        ces.append(jnp.concatenate(ce))
        logits.append(np.concatenate(rows))
    p = ref.exit_distribution(jnp.stack(zs), leave_out)
    out = {k: np.asarray(v, np.float64) for k, v in ref.finish(
        p, jnp.stack(ces), flat, beta, leave_out).items()}
    return ({k: float(v) for k, v in out.items() if k != "shares"},
            np.asarray(p), logits, out["shares"])


class Program(LlamaProgram):
    """One Executor with a ``train`` subgraph (loss, update, the ``[P]`` exit
    shares) and, for the correctness check, the model's three pieces walked
    a pass at a time (``eval_loss``): ``embed``, ``pass`` (``OuroModel.walk``
    and ``exit_terms`` on a fed state: the next state, the pass's gate
    pre-activations, cross-entropies and logits) and, in an Executor of its
    own that computes in f32 as the step's exit block does, ``exit_loss`` on
    the ``P`` passes' fetched terms.  ONE pass is one program run ``P``
    times: a quarter of the whole forward graph to compile, small enough to
    stay in a 192 MiB compile cache beside the step's executable (PERF.md
    section 6, PR 47), and one pass's ``[T, V]`` logits in memory at a time.
    ``make_batches``, ``retraces``, ``uniform_loss``, ``kernel_choices`` and
    ``close`` are the Llama builder's."""

    #: the Mosaic kernels of a train step that are held by name
    KERNELS = ("hetu_softmax_ce_fwd", "hetu_softmax_ce_bwd")

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu import telemetry
        from hetu_tpu.models import OuroConfig, OuroForCausalLM
        from hetu_tpu.ops.pallas import dispatch

        self.config, self.mix, self._say = config, mix, say
        job = config["job"]
        self._choices_before = dispatch.choices()
        kinds = config["layer_types"]
        assert (config["hidden_act"] == "silu"
                and set(kinds) == {"full_attention"}
                and len(kinds) == config["num_hidden_layers"]
                and not config["use_sliding_window"]
                and config["sliding_window"] is None
                and config["rope_scaling"] is None
                and config["head_dim"] * config["num_attention_heads"]
                == config["hidden_size"]), "not the looped dense block"
        B, S = int(mix["batch"]), int(mix["seq"])
        assert S <= config["max_position_embeddings"]
        self.batch, self.seq = B, S
        self.tokens_per_step = B * S
        self.passes = int(config["total_ut_steps"])
        c = OuroConfig(seq_len=S, remat=job["remat"],
                       exit_entropy_coeff=job["exit_entropy_coeff"],
                       **{arg: config[key] for arg, key in HF_KEYS.items()})
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, S), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, S), dtype=np.int32),
            "state": ht.placeholder_op("state", (B, S, c.hidden_size)),
            "zs": [ht.placeholder_op(f"z{t}", (B * S,))
                   for t in range(self.passes)],
            "ces": [ht.placeholder_op(f"ce{t}", (B * S,))
                    for t in range(self.passes)]}
        calls = self.layer_calls()
        self.model = OuroForCausalLM(c)
        loss, terms = self.model.loss_terms(self.nodes["ids"],
                                            self.nodes["labels"])
        built = [int(n - before)
                 for n, before in zip(self.layer_calls(), calls)]
        # the registry counts nothing while telemetry is off (a test that
        # builds the program bare); the harness turns it on before it builds
        assert (not telemetry.enabled()
                or built == [c.num_layers] * self.passes), (
            f"hetu_loop_layer_calls_total says {built} layer applications "
            f"were built by pass, not {c.num_layers} each")
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        train = [loss, opt.minimize(loss), self.model.exit_shares]
        flat = ht.array_reshape_op(self.nodes["labels"], output_shape=(-1,))
        state = self.model.model.walk(self.nodes["state"])
        logits, ce, z = self.model.exit_terms(state, flat)
        self.ex = ht.Executor(
            {"train": train,
             "embed": [self.model.model._embed(self.nodes["ids"])],
             "pass": [state, z, ce, logits]},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]))
        # the gate's pre-activations and the cross-entropies are f32 in the
        # step; fed to the step's Executor they would be cast to its
        # compute type, so the exit block's program has an Executor of its
        # own (it reads no weight)
        loss, terms = self.model.exit_loss(self.nodes["zs"],
                                           self.nodes["ces"], flat)
        self.exit_ex = ht.Executor(
            {"validate": [loss] + [terms[t] for t in TERMS]
             + [self.model.exit_p, self.model.exit_shares]})
        self.params_m = sum(int(np.prod(v.shape))
                            for v in self.ex.params.values()) / 1e6
        self.shares = None
        self.steps_off = 0
        say(f"Ouro looped decoder: hidden {c.hidden_size}, {c.num_layers} "
            f"layers walked {self.passes} times on one set of weights "
            f"({built} layer applications built by pass), {c.num_heads} "
            f"heads of {c.hidden_size // c.num_heads}, rotary theta "
            f"{c.rope_theta:g}, four norms a layer, gated MLP "
            f"{c.intermediate_size} wide, an exit gate a token and a pass, "
            f"untied head over {c.vocab_size} rows {self.passes} times a "
            f"step; batch {B} x {S}, {self.params_m:.1f} M parameters, "
            f"{job['compute_dtype']} compute over f32 masters, "
            f"{job['optimizer']}, recomputed: "
            f"{'every layer application and head pass' if c.remat else 'nothing'}"
            f", loss = "
            f"E_p[ce] - {c.exit_entropy_coeff} H(p)")

    def layer_calls(self):
        """``hetu_loop_layer_calls_total`` by pass, as the registry has it."""
        return [counter("hetu_loop_layer_calls_total", **{"pass": str(t)})
                for t in range(int(self.config["total_ut_steps"]))]

    def step(self, feed):
        """One training step through the normal feed path; the exit shares
        it fetched go to the gauge.  A step whose shares do not add up to 1
        has not computed a distribution: its loss is returned as NaN, which
        the loop counts as a failed step and an incorrect run."""
        from hetu_tpu.models.ouro import record_exit_shares
        out = self.ex.run("train", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        self.shares = np.asarray(out[2], np.float64)
        record_exit_shares(self.shares)
        if abs(self.shares.sum() - 1.0) > SHARE_SUM_TOLERANCE:
            self.steps_off += 1
            return float("nan")
        return float(out[0])

    def close(self):
        self.exit_ex.close()
        if self.shares is not None:
            self._say("mean exit shares by pass, last step "
                      f"(hetu_loop_exit_share): "
                      f"{[round(float(s), 4) for s in self.shares]}, sum "
                      f"{self.shares.sum():.6f}; steps whose shares were off "
                      f"1 by more than {SHARE_SUM_TOLERANCE}, each reported "
                      f"with a loss that is not finite: {self.steps_off}")
        super().close()

    def pallas_ops(self):
        """Flash attention and the loss kernels (the MLPs are dense)."""
        return super().pallas_ops()[:2]

    @property
    def forward_passes(self):
        """The most forward passes of a layer application a step: two where
        whole layers are recomputed in the backward pass (a step that keeps
        the kernel's output through the recomputation runs one)."""
        return 2 if self.config["job"]["remat"] else 1

    def expected_kernel_shapes(self):
        """Flash attention's work (batch, heads, positions, head size);
        ``attention_passes``, the passes a step REQUIRES: one forward and one
        backward for each of the ``P x k`` layer applications;
        ``attention_layers``, the MOST forward calls a step may make: each
        application's twice where whole layers are recomputed; the rows of a
        loss kernel call."""
        c = self.config
        heads, hd = c["num_attention_heads"], c["head_dim"]
        return {"flash_dims": (self.batch, heads, self.seq, hd),
                "flash_elements": self.batch * heads * self.seq * hd,
                "flash_rows": self.batch * heads, "head_dim": hd,
                "attention_passes": self.model.attention_layers,
                "attention_layers": (self.model.attention_layers
                                     * self.forward_passes),
                "causal": True,
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.batch * self.seq}

    def eval_loss(self, feed):
        """The program's loss on ``feed``, ``{"loss", "ce", "entropy",
        "exit_gap", "logits_gap"}``: the embeddings, ``P`` runs of the
        ``pass`` program, each reading the state the run before handed on
        (it stays on the device), and the exit block's program on the fetched
        terms.  ``exit_gap`` is the largest absolute gap of the exit
        distribution over tokens and passes, ``logits_gap`` the relative L2
        distance of a pass's logits, the worst of the ``P`` passes, both
        against what ``reference_loss`` kept from the same batch (it runs
        first)."""
        n = self.nodes
        x, = self.ex.run("embed", feed_dict={n["ids"]: feed[n["ids"]]})
        zs, ces, gaps = [], [], []
        for _ in range(self.passes):    # a pass's logits a run: the process
            # never holds P of them beside the training state
            x, z, ce, logits = self.ex.run("pass", feed_dict={
                n["state"]: x, n["labels"]: feed[n["labels"]]})
            gaps.append(logits_gap(logits, self._ref_logits.pop(0)))
            del logits
            zs.append(z)
            ces.append(ce)
        out = self.exit_ex.run("validate", feed_dict={
            n["labels"]: feed[n["labels"]], **dict(zip(n["zs"], zs)),
            **dict(zip(n["ces"], ces))})
        got = dict(zip(("loss",) + TERMS, map(float, out[:3])))
        p = np.asarray(out[3], np.float64)
        got["exit_gap"] = float(np.abs(p - self._ref_p).max())
        got["logits_gap"] = max(gaps)
        self._say(f"exit distribution: the program's mean shares by pass "
                  f"{[round(float(s), 4) for s in out[4]]}, the "
                  f"reference's {[round(float(s), 4) for s in self._ref_shares]}"
                  f"; logits_gap by pass {[float(f'{g:.3e}') for g in gaps]}")
        del self._ref_logits, self._ref_p
        return got

    def reference_loss(self, feed, chunk):
        """The plain reference's loss, as ``eval_loss`` names it, on all of
        ``feed`` with this executor's present weights (its f32 masters, read
        in place), a layer application and a block of the head at a time
        (``reference_run``; ``chunk`` is not read)."""
        params = reference_params(self.model, self.ex.params)
        out, p, logits, shares = reference_run(
            params, self.config, feed[self.nodes["ids"]],
            feed[self.nodes["labels"]],
            self.config["job"]["exit_entropy_coeff"])
        self._ref_p, self._ref_logits, self._ref_shares = p, logits, shares
        out.update(exit_gap=0.0, logits_gap=0.0)
        return out


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
