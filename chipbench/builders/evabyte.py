"""Byte-level pretraining of an EvaByte decoder at one pipeline stage, built
the way ``examples/nlp/train_llama.py --model evabyte`` builds it:
``EvaByteForCausalLM`` from the configuration's published keys, ``loss`` and
``opt.minimize`` through ``ht.Executor``, a fresh numpy batch of byte ids and
the eight heads' shifted labels fed every step.  Knows nothing of cells: sizes
come from the configuration file, batch shape from the traffic file.

The family's files: ``configs/evabyte-6.5b-pretrain.json`` (the published
keys; ``num_hidden_layers`` there is the layers built, listed in ``reduced``;
``assumed`` what the published keys do not fix; ``job`` the optimizer and
what is recomputed), this builder, ``reference/evabyte.py`` (the plain
reference), ``reference/evabyte_controls.py`` (the readings behind the traffic
file's limits), ``flops_evabyte.py`` (operations and bytes) and the readers
``metrics/*.evabyte.py``.

EVERY attention layer is EVA: its nodes are ``ScaledDotProductAttentionOp`` of
kind ``eva`` (what ``expected_kernel_shapes`` states under the harness's keys:
``flash_dims``, ``attention_passes``, ``attention_layers``) and its kernels go
by ``hetu_eva_fwd`` / ``hetu_eva_bwd``; no ``hetu_flash_*`` or ``hetu_swa_*``
event runs.  ``loops.TrainLoop.trace_checks`` finds a step's attention passes
by the event names of ``flops.FLASH_PASSES`` and has no other way to be told
them, so WHILE THIS PROGRAM LIVES (``Program.__init__`` .. ``close``) those two
names are the EVA pair's (``pass_events``); the operations and bytes of that
table are flash's and are read by no reader of this family
(``README.evabyte.md``; PERF.md section 7 asks for the harness's own key).
"""

from __future__ import annotations

import math

import numpy as np

from .. import flops
from ..flops_evabyte import EVA_EVENTS
from .common import counter, jax_seed
from .granite_hybrid import logits_gap
from .llama import Program as LlamaProgram

#: published keys that are EvaByteConfig arguments under their own names
HF_KEYS = ("attention_bias", "attention_class", "chunk_size", "fp32_ln",
           "fp32_logits", "fp32_skip_add", "hidden_act", "hidden_size",
           "init_cutoff_factor", "init_fn", "init_std", "intermediate_size",
           "lazy_init", "max_position_embeddings", "max_seq_length",
           "mixedp_attn", "model_type", "norm_add_unit_offset",
           "num_attention_heads", "num_chunks", "num_hidden_layers",
           "num_key_value_heads", "num_pred_heads", "rms_norm_eps",
           "rope_scaling", "rope_theta", "tie_word_embeddings", "vocab_size",
           "window_size")

#: what ``eval_loss`` compares beside the loss, and the reference's name for
#: what each is a distance of
GAPS = ("logits_gap", "eva_gap", "eva_remote_gap", "summary_gap")


def reference_params(model, params):
    """The program's weights under the plain reference's names
    (``chipbench/reference/evabyte.py`` ``WEIGHTS``), found by walking the
    model object.  The values are ``params``' own arrays: nothing is copied;
    ``phi`` and ``mu`` are ``[H, d]`` in both."""
    out = {"embed": model.model.embed.weight, "norm": model.model.norm.scale,
           "lm_head": model.lm_head.weight}
    for i, layer in enumerate(model.model.layers):
        a, f = layer.attn, layer.mlp
        out.update({f"layers.{i}.{k}": v for k, v in (
            ("input_norm", layer.input_norm.scale),
            ("post_norm", layer.post_norm.scale),
            ("q", a.q_proj.weight), ("k", a.k_proj.weight),
            ("v", a.v_proj.weight), ("o", a.out_proj.weight),
            ("phi", a.phi), ("mu", a.mu), ("mlp_gate", f.gate.weight),
            ("mlp_up", f.up.weight), ("mlp_down", f.down.weight))})
    return {k: params[v.name] for k, v in out.items()}


def seed_parts(ex, model, seed):
    """Every layer's ``phi`` and ``mu`` ~ N(0, 1) and every norm's ``g`` ~
    N(0, 1/16) from ``seed``, written over the executor's masters: at their
    initial values (0.013 wide, and zeros) every summary is all but its
    chunk's mean and the unit offset adds nothing, so a program without
    ``phi``, ``mu`` or the offset would read as the reference does."""
    import jax.numpy as jnp
    rng = np.random.default_rng([int(seed), 13])
    norms = [model.model.norm]
    for layer in model.model.layers:
        for var in (layer.attn.phi, layer.attn.mu):
            ex.params[var.name] = jnp.asarray(
                rng.standard_normal(var.shape), jnp.float32)
        norms += [layer.input_norm, layer.post_norm]
    for norm in norms:
        ex.params[norm.scale.name] = jnp.asarray(
            rng.normal(0, 0.25, norm.scale.shape), jnp.float32)


def remote_gap(got, want, local, window):
    """How far the REMOTE part of a layer's attention output lies from the
    reference's, over the positions behind the first window: both outputs
    less the reference's output of the local set alone.  A program without
    summaries reads 1."""
    local = np.asarray(local, np.float32)[:, window:]
    return logits_gap(np.asarray(got, np.float32)[:, window:] - local,
                      np.asarray(want, np.float32)[:, window:] - local)


class pass_events:
    """While entered, the events of ``flops.FLASH_PASSES`` are the EVA pair's
    (module docstring); nested entries (two programs alive at once) count,
    and the last to leave puts flash's names back."""

    flash = {k: p["events"] for k, p in flops.FLASH_PASSES.items()}
    entered = 0

    def __enter__(self):
        pass_events.entered += 1
        for k, name in EVA_EVENTS.items():
            flops.FLASH_PASSES[k]["events"] = name
        return self

    def __exit__(self, *exc):
        pass_events.entered -= 1
        if not pass_events.entered:
            for k, name in self.flash.items():
                flops.FLASH_PASSES[k]["events"] = name
        return False


class Program(LlamaProgram):
    """One Executor with a ``train`` subgraph (loss, update) and, for the
    correctness check, a ``validate`` subgraph of the same loss, each head's
    cross-entropy, the logits and the probed layer's attention output and
    summaries.  ``retraces`` is the Llama builder's."""

    KERNELS = ("hetu_softmax_ce_fwd", "hetu_softmax_ce_bwd",
               "hetu_eva_fwd", "hetu_eva_bwd")

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu.models import EvaByteConfig, EvaByteForCausalLM
        from hetu_tpu.ops.pallas import dispatch

        self.config, self.mix, self._say = config, mix, say
        self.seed = seed
        job = config["job"]
        self._choices_before = dispatch.choices()
        self._nodes_before = counter("hetu_attn_layers_total", kind="eva")
        B, S = int(mix["batch"]), int(mix["seq"])
        self.batch, self.seq = B, S
        self.tokens_per_step = B * S
        c = EvaByteConfig(seq_len=S, remat=job["remat"],
                          **{key: config[key] for key in HF_KEYS})
        P = c.num_pred_heads
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, S), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, S, P), dtype=np.int32)}
        self.model = EvaByteForCausalLM(c)
        logits = self.model(self.nodes["ids"])
        loss, terms = self.model.loss_terms(
            self.nodes["ids"], self.nodes["labels"], logits=logits)
        #: the layer whose own attention output and summaries are compared:
        #: the last, behind every other layer's rounding
        self.probed = c.num_layers - 1
        attn = self.model.model.layers[self.probed].attn
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        self.ex = ht.Executor(
            {"train": [loss, opt.minimize(loss)],
             "validate": [loss, terms["ce_heads"], logits, attn.context,
                          *attn.summaries]},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]))
        seed_parts(self.ex, self.model, seed)
        self.params_m = sum(int(np.prod(v.shape))
                            for v in self.ex.params.values()) / 1e6
        self._events = pass_events().__enter__()
        say(f"EvaByte decoder: hidden {c.hidden_size}, {c.num_layers} "
            f"layer(s) of {config['deployment']['num_hidden_layers']}, "
            f"{c.num_heads} heads of {c.hidden_size // c.num_heads}, EVA "
            f"attention (window {c.window_size}, one summary a chunk of "
            f"{c.chunk_size}), rotary at {c.rope_theta:g}, SwiGLU "
            f"{c.intermediate_size} wide, RMSNorm with a unit offset: "
            f"{c.unit_offset}, {P} heads over {c.vocab_size} rows; batch "
            f"{B} x {S}, {self.params_m:.1f} M parameters "
            f"({self.params_m * 12e6 / 2 ** 30:.2f} GiB resident at 12 B), "
            f"{job['compute_dtype']} compute over f32 masters, "
            f"{job['optimizer']}, recomputed: {job['remat']}, loss = mean of "
            f"the heads' ce; phi, mu ~ N(0, 1) and the norms' g ~ N(0, 1/16) "
            f"from the seed")

    def close(self):
        pairs = {part: counter("hetu_eva_pairs_total", part=part)
                 for part in ("local", "remote")}
        self._say(f"hetu_eva_pairs_total (pairs a head, summed over the "
                  f"calls planned while the programs were traced): {pairs}")
        self._events.__exit__()
        super().close()

    def make_batches(self, seed, n):
        """``n`` feed dicts: ``seq + num_pred_heads`` byte ids a sequence,
        uniform over the vocabulary's rows from the seed; the first ``seq``
        are the input and head ``i``'s labels the ids shifted by ``1 + i``,
        so every position is labelled in every head and no seed changes the
        work."""
        assert self.mix["mask_fraction"] == 1.0
        P, S = self.config["num_pred_heads"], self.seq
        rng = np.random.default_rng([int(seed), 5])
        out = []
        for _ in range(n):
            tok = rng.integers(0, self.config["vocab_size"],
                               (self.batch, S + P))
            labels = np.stack([tok[:, 1 + i:1 + i + S] for i in range(P)],
                              axis=-1)
            out.append({self.nodes["ids"]: tok[:, :S],
                        self.nodes["labels"]: labels})
        return out

    def step(self, feed):
        return float(self.ex.run("train", feed_dict=feed,
                                 convert_to_numpy_ret_vals=True)[0])

    def uniform_loss(self):
        return math.log(self.config["vocab_size"])

    def narrow_loss(self):
        """Why the loss kernel is not taken, where it is not: the rows of a
        toy are too few for 320 classes (``ops/pallas/softmax_ce.py``)."""
        from hetu_tpu.ops.pallas import softmax_ce
        rows = self.tokens_per_step * self.config["num_pred_heads"]
        return softmax_ce.unsupported(np.empty(
            (rows, self.config["vocab_size"]), np.float32))

    def kernel_choices(self):
        """The Llama builder's, with what this model explains: off a TPU the
        EVA plan's ``jax.numpy`` form, and at a toy's rows the loss's."""
        from hetu_tpu.ops.pallas import dispatch
        taken, fallbacks = super().kernel_choices()
        allowed = set()
        if not dispatch.mosaic():
            allowed.add(("eva", "jnp", f"platform:{dispatch.platform()}"))
        if self.narrow_loss():
            allowed.add(("softmax_ce", "jnp", self.narrow_loss()))
        return taken, [k for k in fallbacks if k not in allowed]

    def pallas_ops(self):
        from hetu_tpu.ops.pallas import dispatch
        if not dispatch.mosaic():
            return ()
        return ("eva",) + (() if self.narrow_loss() else ("softmax_ce",))

    @property
    def forward_passes(self):
        """The most forward passes of a layer a step: two where whole layers
        are recomputed in the backward pass (a step that keeps the kernel's
        output through the recomputation runs one)."""
        return 2 if self.config["job"]["remat"] == "layer" else 1

    def expected_kernel_shapes(self):
        """The attention passes' work under the harness's keys: batch x heads
        x positions x head size, one pass a layer REQUIRED and the most
        forward calls a step may make.  ``eva_dims``, ``window`` and ``chunk``
        are what this family's readers count pairs and bytes from."""
        c = self.config
        heads = c["num_attention_heads"]
        d = c["hidden_size"] // heads
        layers = c["num_hidden_layers"]
        dims = (self.batch, heads, self.seq, d)
        return {"flash_dims": dims, "flash_elements": int(np.prod(dims)),
                "flash_rows": self.batch * heads, "head_dim": d,
                "attention_passes": layers,
                "attention_layers": layers * self.forward_passes,
                "causal": True, "eva_dims": dims,
                "window": c["window_size"], "chunk": c["chunk_size"],
                "eva_layers": layers,
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.tokens_per_step * c["num_pred_heads"],
                "ce_itemsize": 4}

    def eval_loss(self, feed):
        """The program's loss on ``feed``, ``{"loss", "ce", "ce_head<i>",
        "logits_gap", "eva_gap", "eva_remote_gap", "summary_gap", "nodes"}``,
        through the executor's ``validate`` subgraph.  The gaps are relative
        L2 distances from what ``reference_loss`` kept from the same batch (it
        runs first): of all eight heads' logits, of the probed layer's
        attention output before ``W_o``, of that output's remote part
        (``remote_gap``) and of its summaries ``[k^ | v^]`` (of every window but
        the last: the rest are never read); ``nodes`` the EVA
        attention nodes built for this program."""
        loss, heads, logits, context, ks, vs = self.ex.run(
            "validate", feed_dict=feed, convert_to_numpy_ret_vals=True)
        got = {"loss": float(loss), "ce": float(loss)}
        got.update({f"ce_head{i}": float(h) for i, h in enumerate(heads)})
        kept = self.kept
        got["logits_gap"] = logits_gap(logits, kept["logits"])
        got["eva_gap"] = logits_gap(context, kept["eva"])
        got["eva_remote_gap"] = remote_gap(
            context, kept["eva"], kept["local"],
            self.config["window_size"])
        # the program summarises the windows that are read: all but the last
        got["summary_gap"] = logits_gap(
            np.concatenate([ks, vs], -1),
            kept["summaries"][:, :ks.shape[1]])
        got["nodes"] = (counter("hetu_attn_layers_total", kind="eva")
                        - self._nodes_before)
        return got

    def reference_loss(self, feed, chunk, **lower):
        """The plain reference's loss, as ``eval_loss`` names it, on all of
        ``feed`` with this executor's present weights (its f32 masters, read
        in place), ``chunk`` sequences at a time.  ``lower``: the reference's
        ``matmul_inputs`` or ``without`` (``reference/evabyte_controls.py``).
        What the reference kept is left on ``self.kept``."""
        import jax
        from ..reference import evabyte as ref
        c = self.config
        params = reference_params(self.model, self.ex.params)
        sums = jax.jit(lambda p, i, l: ref.loss_sums(
            p, c, i, l, keep_logits=True, keep_layer=self.probed, **lower))
        ids = np.asarray(feed[self.nodes["ids"]])
        labels = np.asarray(feed[self.nodes["labels"]])
        tot, kept = None, {}
        with jax.default_matmul_precision("highest"):
            for lo in range(0, self.batch, chunk):
                part = jax.device_get(sums(params, ids[lo:lo + chunk],
                                           labels[lo:lo + chunk]))
                for k in set(part) - {"ce", "n"}:
                    kept.setdefault(k, []).append(part.pop(k))
                tot = part if tot is None else {k: tot[k] + v
                                                for k, v in part.items()}
        self.kept = {k: np.concatenate(v, axis=0) for k, v in kept.items()}
        out = {k: float(v) for k, v in ref.loss_from_sums(tot).items()}
        out.update({gap: 0.0 for gap in GAPS},
                   nodes=float(c["num_hidden_layers"]))
        return out


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
