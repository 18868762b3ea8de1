"""Causal-LM pretraining of a Llama-family decoder, built the way
``examples/nlp/train_llama.py`` builds it: ``LlamaForCausalLM`` from the
configuration's published keys, ``loss`` and ``opt.minimize`` through
``ht.Executor``, a fresh numpy batch of ids and next-token labels fed every
step, an MoE model's per-expert load fetched beside the loss and counted by
``hetu_tpu.layers.moe.record_moe_load``.  Knows nothing of cells: sizes
come from the configuration file, batch shape from the traffic file.

The configuration file holds a Hugging Face ``config.json`` (``model_type``
``olmoe`` today); ``HF_KEYS`` says which ``LlamaConfig`` argument each
published key sets.  What ``config.json`` does not hold (the loss weights,
dropless routing) is under the file's ``job``.
"""

from __future__ import annotations

import math

import numpy as np

from .common import counter, jax_seed

#: LlamaConfig argument <- published key
HF_KEYS = {"vocab_size": "vocab_size", "hidden_size": "hidden_size",
           "num_layers": "num_hidden_layers",
           "num_heads": "num_attention_heads",
           "num_kv_heads": "num_key_value_heads",
           "intermediate_size": "intermediate_size",
           "rope_theta": "rope_theta", "rms_eps": "rms_norm_eps",
           "tie_embeddings": "tie_word_embeddings",
           "num_experts": "num_experts", "moe_k": "num_experts_per_tok",
           "moe_renorm_topk": "norm_topk_prob"}

TERMS = ("ce", "lbl", "z")


def reference_params(model, params):
    """The program's weights under the plain reference's names
    (``chipbench/reference/olmoe.py`` ``WEIGHTS``), found by walking the
    model object, not by parsing variable names."""
    out = {"embed": model.model.embed.weight, "norm": model.model.norm.scale,
           "lm_head": model.lm_head.weight}
    for i, layer in enumerate(model.model.layers):
        a, m = layer.attn, layer.mlp
        out.update({f"layers.{i}.{k}": v for k, v in (
            ("input_norm", layer.input_norm.scale),
            ("post_norm", layer.post_norm.scale),
            ("q", a.q_proj.weight), ("k", a.k_proj.weight),
            ("v", a.v_proj.weight), ("o", a.out_proj.weight),
            ("q_norm", a.q_norm.scale), ("k_norm", a.k_norm.scale),
            ("router", m.gate.wg), ("w_gate", m.w1), ("w_up", m.w3),
            ("w_down", m.w2))})
    return {k: params[v.name] for k, v in out.items()}


class Program:
    """One Executor with a ``train`` subgraph (loss, update, per-layer
    expert load) and, for the correctness check, a ``validate`` subgraph of
    the same loss, its terms and the same load."""

    #: the Mosaic kernels of a train step that are held by name; flash
    #: attention is held by its passes and its work (``loops.trace_checks``)
    KERNELS = ("hetu_softmax_ce_fwd", "hetu_softmax_ce_bwd",
               "hetu_moe_gmm_fwd", "hetu_moe_gmm_dx", "hetu_moe_gmm_dw")

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu.models import LlamaConfig, LlamaForCausalLM

        from hetu_tpu.ops.pallas import dispatch
        self.config, self.mix = config, mix
        job = config["job"]
        # the registry's counts are the process's: this program's choices
        # are those made from here on
        self._choices_before = dispatch.choices()
        assert config["clip_qkv"] is None and not config["attention_bias"], (
            "the Llama block has no QKV clipping and no attention bias")
        assert config["hidden_act"] == "silu"
        B, S = int(mix["batch"]), int(mix["seq"])
        assert S <= config["max_position_embeddings"]
        self.batch, self.seq = B, S
        self.tokens_per_step = B * S
        c = LlamaConfig(
            seq_len=S, qk_norm=job["qk_norm"],
            moe_capacity_factor=job["moe_capacity_factor"],
            moe_aux_coeff=job["lbl_weight"], moe_z_coeff=job["z_weight"],
            **{arg: config[key] for arg, key in HF_KEYS.items()})
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, S), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, S), dtype=np.int32)}
        self.model = LlamaForCausalLM(c)
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
        say(f"Llama-family decoder ({config['model_type']}): hidden "
            f"{c.hidden_size}, {c.num_layers} layer(s), {c.num_heads} heads "
            f"of {c.hidden_size // c.num_heads}, QK-norm {c.qk_norm}, "
            f"{c.num_experts} experts of width {c.intermediate_size}, "
            f"{c.moe_k} a token (renormalised: {c.moe_renorm_topk}; "
            f"capacity factor {c.moe_capacity_factor}: None is dropless), "
            f"vocabulary {c.vocab_size}, batch {B} x {S}, "
            f"{self.params_m:.1f} M parameters, {job['compute_dtype']} "
            f"compute over f32 masters, {job['optimizer']}, loss = ce + "
            f"{c.moe_aux_coeff} lbl + {c.moe_z_coeff} z")

    @property
    def devices(self):
        import jax
        return jax.devices()[:1]

    def make_batches(self, seed, n):
        """``n`` feed dicts: ``seq + 1`` ids a sequence, uniform over the
        vocabulary from the seed; the first ``seq`` are the input and the
        last ``seq`` the labels, so every position is labelled and no seed
        changes the work."""
        assert self.mix["mask_fraction"] == 1.0
        rng = np.random.default_rng([int(seed), 5])
        out = []
        for _ in range(n):
            tok = rng.integers(0, self.config["vocab_size"],
                               (self.batch, self.seq + 1))
            out.append({self.nodes["ids"]: tok[:, :-1],
                        self.nodes["labels"]: tok[:, 1:]})
        return out

    def step(self, feed):
        """One training step through the normal feed path; returns the
        loss, which is on the host only when the step has ended."""
        from hetu_tpu.layers.moe import record_moe_load
        out = self.ex.run("train", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        for i, load in enumerate(out[2:]):
            record_moe_load(f"layer{i}", load)
        return float(out[0])

    def retraces(self):
        return counter("hetu_executor_retraces_total", subgraph="train")

    def uniform_loss(self):
        return math.log(self.config["vocab_size"])

    def kernel_choices(self):
        """``(taken, fallbacks)``: the kernels whose Pallas form was chosen
        while the step was traced, and the jnp forms taken that the
        platform does not explain (the cpu platform has no Mosaic)."""
        from hetu_tpu.ops.pallas import dispatch
        allowed = set()
        if not dispatch.mosaic():
            why = f"platform:{dispatch.platform()}"
            allowed = {("flash_attention", "jnp", why),
                       ("moe_gmm", "jnp", why)}
        choices = {k for k, n in dispatch.choices().items()
                   if n > self._choices_before.get(k, 0)}
        taken = sorted({k[0] for k in choices if k[1] == "pallas"})
        fallbacks = sorted(k for k in choices
                           if k[1] == "jnp" and k not in allowed)
        return taken, fallbacks

    def pallas_ops(self):
        from hetu_tpu.ops.pallas import dispatch
        return (("flash_attention", "softmax_ce", "moe_gmm")
                if dispatch.mosaic() else ())

    def expected_kernel_shapes(self):
        """Flash attention's work (batch, heads, positions, head size;
        layers a step; the type computed in), the rows of the loss kernel
        and the sizes of the grouped products."""
        c = self.config
        heads = c["num_attention_heads"]
        hd = c["hidden_size"] // heads
        return {"flash_dims": (self.batch, heads, self.seq, hd),
                "flash_elements": self.batch * heads * self.seq * hd,
                "flash_rows": self.batch * heads, "head_dim": hd,
                "attention_layers": c["num_hidden_layers"],
                "causal": True,
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.batch * self.seq,
                "moe_pairs": self.tokens_per_step * c["num_experts_per_tok"]}

    def eval_loss(self, feed):
        """The program's loss on ``feed`` and its terms, ``{"loss", "ce",
        "lbl", "z", "dropped", "routing_mismatch"}``, through the
        executor's ``validate`` subgraph: same graph, kernels and compute
        type as the train step's forward pass.  ``dropped`` is the share of
        (token, choice) pairs the program did not compute;
        ``routing_mismatch`` the share of the reference's pairs (a token
        and one of its experts) that the program did not choose, against
        the routing ``reference_loss`` kept from the same batch."""
        out = self.ex.run("validate", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        n = self.n_layers
        got = dict(zip(("loss",) + TERMS, map(float, out[:4])))
        loads = np.asarray(out[4:4 + n], np.float64)     # [layers, 2, E]
        got["dropped"] = float(1.0 - loads[:, 1].sum() / loads[:, 0].sum())
        want = self._ref_chosen            # reference_loss runs first
        E = self.config["num_experts"]
        shared = 0
        for mine, theirs in zip(out[4 + n:], want):
            hot = np.zeros((len(theirs), E), bool)
            np.put_along_axis(hot, np.asarray(theirs), True, axis=1)
            shared += np.take_along_axis(hot, np.asarray(mine), 1).sum()
        got["routing_mismatch"] = float(1.0 - shared / want.size)
        return got

    def reference_loss(self, feed, chunk):
        """The plain reference's loss and terms, as ``eval_loss`` names
        them, on all of ``feed`` with this executor's present weights,
        ``chunk`` sequences at a time (the sums add; the balance loss is
        formed from the whole batch's)."""
        import jax
        from ..reference import olmoe as ref
        job = self.config["job"]
        params = reference_params(self.model, self.ex.params)
        sums = jax.jit(lambda p, i, l: ref.loss_sums(p, self.config, i, l))
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
            tot, self.config, job["lbl_weight"], job["z_weight"]).items()}
        out.update(dropped=0.0, routing_mismatch=0.0)
        return out

    def close(self):
        self.ex.close()


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
