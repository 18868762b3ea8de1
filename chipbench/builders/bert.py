"""BERT pretraining (MLM + NSP) built the way ``examples/nlp/train_bert.py``
and ``chip_smoke.py`` build it, through ``ht.Executor`` and, across chips, a
strategy of ``parallel/strategies.py``.  Knows nothing of cells: sizes come
from the configuration file, batch shape and strategy from the traffic
file."""

from __future__ import annotations

import math

import numpy as np

from .common import counter, jax_seed

CONFIG_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
               "num_attention_heads", "intermediate_size",
               "max_position_embeddings", "type_vocab_size",
               "hidden_dropout_prob", "attention_probs_dropout_prob")


class Program:
    """One Executor with a ``train`` subgraph and, for the correctness
    check, a ``validate`` subgraph of the same loss and its MLM and NSP
    terms (dropout off), and the batches to feed them."""

    #: the Mosaic kernels of a BERT train step that are held by name; flash
    #: attention is held by its passes and its work (``loops.trace_checks``)
    KERNELS = ("hetu_softmax_ce_fwd", "hetu_softmax_ce_bwd")

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu import parallel
        from hetu_tpu.models import BertConfig, BertForPreTraining

        self.config, self.mix = config, mix
        job = config["job"]
        B, S = int(mix["batch"]), int(mix["seq"])
        self.batch, self.seq = B, S
        self.tokens_per_step = B * S
        c = BertConfig(seq_len=S, **{k: config[k] for k in CONFIG_KEYS})
        self.strategy = None
        if mix.get("strategy"):
            self.strategy = getattr(parallel, mix["strategy"]["name"])(
                **mix["strategy"].get("kwargs", {}))
        self.nodes = {
            "input_ids": ht.placeholder_op("input_ids", (B, S),
                                           dtype=np.int32),
            "token_type_ids": ht.placeholder_op("token_type_ids", (B, S),
                                                dtype=np.int32),
            "attention_mask": ht.placeholder_op("attention_mask", (B, S)),
            "mlm_labels": ht.placeholder_op("mlm_labels", (B * S,),
                                            dtype=np.int32),
            "nsp_labels": ht.placeholder_op("nsp_labels", (B,),
                                            dtype=np.int32)}
        n = self.nodes
        self.model = BertForPreTraining(c)
        loss = self.model.loss(n["input_ids"], n["token_type_ids"],
                               n["attention_mask"], n["mlm_labels"],
                               n["nsp_labels"])
        # the loss node is the sum of its two terms; they are read beside
        # it so that each is held to the reference at its own tolerance
        mlm_term, nsp_term = loss.inputs
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        self.ex = ht.Executor(
            {"train": [loss, opt.minimize(loss)],
             "validate": [loss, mlm_term, nsp_term]},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]),
            rng_impl=job["rng_impl"], dist_strategy=self.strategy)
        self.params_m = sum(int(np.prod(v.shape))
                            for v in self.ex.params.values()) / 1e6
        say(f"BERT hidden {c.hidden_size}, {c.num_hidden_layers} layers, "
            f"{c.num_attention_heads} heads, vocabulary {c.vocab_size}, "
            f"batch {B} x {S}, {self.params_m:.1f} M parameters, "
            f"{job['compute_dtype']} compute over f32 masters, "
            f"{job['optimizer']}, dropout {c.hidden_dropout_prob} "
            f"({job['rng_impl']}), strategy "
            f"{mix['strategy']['name'] if self.strategy else 'none'}")

    @property
    def devices(self):
        import jax
        return (list(self.strategy.mesh.devices.flat)
                if self.strategy is not None else jax.devices()[:1])

    def make_batches(self, seed, n):
        """``n`` feed dicts from the traffic generator's batches."""
        from .. import traffic
        return [{self.nodes[k]: v for k, v in b.items()}
                for b in traffic.mlm_batches(self.mix, seed, n,
                                             self.config["vocab_size"])]

    def step(self, feed):
        """One training step through the normal feed path; returns the
        loss, which is on the host only when the step has ended."""
        out = self.ex.run("train", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        return float(out[0])

    def retraces(self):
        return counter("hetu_executor_retraces_total", subgraph="train")

    def uniform_loss(self):
        return math.log(self.config["vocab_size"]) + math.log(2)

    def kernel_choices(self):
        """``(taken, fallbacks)``: the kernels whose Pallas form was chosen
        while the step was traced, and the jnp forms taken that the model
        does not explain.  The 2-class NSP head is below the softmax-CE
        kernel's 1024-class floor by design; on the cpu platform flash
        attention has no Mosaic to run on."""
        from hetu_tpu.ops.pallas import dispatch
        allowed = {("softmax_ce", "jnp", "vocab<1024")}
        if not dispatch.mosaic():
            allowed.add(("flash_attention", "jnp", "platform:cpu"))
        choices = dispatch.choices()
        taken = sorted({k[0] for k in choices if k[1] == "pallas"})
        fallbacks = sorted(k for k in choices
                           if k[1] == "jnp" and k not in allowed)
        return taken, fallbacks

    def pallas_ops(self):
        """The ops that must take their Pallas form where there is a
        Mosaic to compile it."""
        from hetu_tpu.ops.pallas import dispatch
        return ("flash_attention", "softmax_ce") if dispatch.mosaic() else ()

    def expected_kernel_shapes(self):
        """Flash attention's work on the local shard (batch, heads,
        positions, head size; layers a step; the type computed in), and
        the rows the loss kernel must read whole."""
        axes = dict(self.strategy.mesh.shape) if self.strategy else {}
        dp, tp = axes.get("dp", 1), axes.get("tp", 1)
        c = self.config
        batch, heads = self.batch // dp, c["num_attention_heads"] // tp
        hd = c["hidden_size"] // c["num_attention_heads"]
        return {"flash_dims": (batch, heads, self.seq, hd),
                "flash_elements": batch * heads * self.seq * hd,
                "flash_rows": batch * heads, "head_dim": hd,
                "attention_layers": c["num_hidden_layers"],
                "causal": False,
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.ce_rows() // dp}

    def ce_rows(self):
        """Rows the MLM loss kernel sees: the model's static bucket of
        masked positions (``BertConfig.mlm_bucket_frac``)."""
        n = self.batch * self.seq
        frac = self.model.config.mlm_bucket_frac
        return n if frac is None else min(
            n, -(-int(n * frac) // 128) * 128)

    def eval_loss(self, feed):
        """The program's loss on ``feed`` with dropout off and its two
        terms, ``{"loss", "mlm", "nsp"}``, through the executor's
        ``validate`` subgraph: same graph, kernels and compute type as the
        train step's forward pass."""
        out = self.ex.run("validate", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        return dict(zip(("loss", "mlm", "nsp"), map(float, out)))

    def reference_loss(self, feed, chunk):
        """The plain reference's loss and its terms, as ``eval_loss``
        names them, on all of ``feed`` with this executor's present
        weights, ``chunk`` sequences at a time."""
        import jax
        from ..reference import bert as ref
        n, S = self.nodes, self.seq
        sums = jax.jit(lambda p, *a: ref.loss_sums(p, self.config, *a))
        cols = (np.asarray(feed[n["input_ids"]]),
                np.asarray(feed[n["token_type_ids"]]),
                np.asarray(feed[n["attention_mask"]]),
                np.asarray(feed[n["mlm_labels"]]).reshape(-1, S),
                np.asarray(feed[n["nsp_labels"]]))
        params = {k: v for k, v in self.ex.params.items()
                  if np.issubdtype(v.dtype, np.floating)}
        tot = np.zeros(3)
        for lo in range(0, self.batch, chunk):
            tot += [float(x) for x in sums(
                params, *(c[lo:lo + chunk] for c in cols))]
        mlm, nsp = tot[0] / max(tot[1], 1.0), tot[2] / self.batch
        return {"loss": mlm + nsp, "mlm": mlm, "nsp": nsp}

    def close(self):
        self.ex.close()


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
