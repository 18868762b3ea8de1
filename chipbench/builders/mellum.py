"""Causal-LM pretraining of a Mellum 2 decoder on the chips that share its
layers: ``MellumForCausalLM`` from the configuration's published keys under
``parallel.ExpertParallel`` (the traffic file names the strategy), ``loss`` and
``opt.minimize`` through ``ht.Executor(dist_strategy=)``, a fresh numpy batch
of ids and next-token labels fed every step, each expert layer's load (over
the host's counts) fetched beside the loss.  Knows nothing of cells: sizes
come from the configuration file, batch shape and strategy from the traffic
file.

No rank is absent: every sequence is on one of the chips, every expert on one
of them, and the experts' exchange runs (``ops/moe.py
dropless_moe_over_axis``).  So the plain reference
(``reference/mellum.py``) is the UNCUT model, all experts and the whole
vocabulary, a layer and a sequence at a time on one chip beside the program's
state, on the program's f32 masters gathered from the chips that hold them;
and the comparison looks at the sequences of ``compared_sequences`` (the
traffic file's: at least two, on different chips), so that rows returned to
the wrong chip fail.

The family's files: ``configs/mellum2-12b-a2.5b-pretrain.json``, this builder,
``reference/mellum.py``, ``reference/mellum_controls.py`` (the readings behind
the traffic file's limits), ``flops_mellum.py`` and the readers
``metrics/*.mellum.py``.  The FULL layers' kernels go by ``hetu_flash_*`` and
are what ``expected_kernel_shapes`` states for one chip's shard, the WINDOW
layers' by ``hetu_swa_*`` (``window_dims``), as the Laguna builder's.
"""

from __future__ import annotations

import numpy as np

from .common import counter, jax_seed
from .granite_hybrid import logits_gap
from .laguna import KINDS, edge_share, nodes_built
from .laguna import Program as LagunaProgram

#: published keys that are MellumConfig arguments under their own names
HF_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
           "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "head_dim", "max_position_embeddings", "attention_bias",
           "rms_norm_eps", "num_experts", "num_experts_per_tok",
           "moe_intermediate_size", "norm_topk_prob", "tie_word_embeddings",
           "sliding_window", "rope_parameters", "layer_types",
           "mlp_layer_types", "hidden_act", "use_sliding_window",
           "max_window_layers")


def reference_nodes(model):
    """The program's variables under the plain reference's names
    (``chipbench/reference/mellum.py``), found by walking the model object."""
    out = {"embed": model.model.embed.weight,
           "head.norm": model.model.norm.scale,
           "head.lm_head": model.lm_head.weight}
    for i, layer in enumerate(model.model.layers):
        a, f = layer.attn, layer.mlp
        out.update({f"layers.{i}.{k}": v for k, v in (
            ("input_norm", layer.input_norm.scale),
            ("post_norm", layer.post_norm.scale),
            ("q", a.q_proj.weight), ("k", a.k_proj.weight),
            ("v", a.v_proj.weight), ("o", a.out_proj.weight),
            ("router", f.gate.wg), ("w_gate", f.w1), ("w_up", f.w3),
            ("w_down", f.w2))})
    return out


class Program(LagunaProgram):
    """One Executor under the traffic file's strategy with a ``train``
    subgraph (loss, update, per-layer expert load) and, for the correctness
    check, a ``validate`` subgraph of the same loss, every ``logits_every``-th
    row of the logits under it, the
    attention sublayer's output of the first window layer and of the full
    layer, the first expert layer's routed sum, the same load and what each
    token chose in that layer.  ``make_batches``, ``retraces``,
    ``uniform_loss`` and ``kernel_choices`` are the Llama builder's (a
    ``jax.numpy`` form taken for the reason ``mesh`` is one the platform does
    not explain: the run is not ``correct``)."""

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu import parallel
        from hetu_tpu.models import MellumConfig, MellumForCausalLM
        from hetu_tpu.ops.pallas import dispatch

        self.config, self.mix, self._say = config, mix, say
        self.seed = seed
        self.held_peak, self.steps_dropping = 0.0, 0
        job, dep = config["job"], config["deployment"]
        self._choices_before = dispatch.choices()
        self._nodes_before = nodes_built()
        B, S = int(mix["batch"]), int(mix["seq"])
        self.batch, self.seq = B, S
        self.tokens_per_step = B * S
        self.strategy = getattr(parallel, mix["strategy"]["name"])(
            **mix["strategy"].get("kwargs", {}))
        self.axis = self.strategy.axis
        self.ranks = int(self.strategy.mesh.shape[self.axis])
        assert self.ranks == dep["chips_sharing_a_layer"], (
            "the strategy's axis is the chips that share a layer")
        assert B % self.ranks == 0, (B, self.ranks)
        self.compared = tuple(mix["compared_sequences"])
        assert len({b * self.ranks // B for b in self.compared}) >= 2, (
            "the comparison looks at the sequences of at least two chips")
        c = MellumConfig(seq_len=S, expert_axis=self.axis,
                         remat=job["remat"],
                         **{key: config[key] for key in HF_KEYS})
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, S), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, S), dtype=np.int32)}
        self.model = MellumForCausalLM(c)
        logits = self.model(self.nodes["ids"])
        loss, _ = self.model.loss_terms(
            self.nodes["ids"], self.nodes["labels"], logits=logits)
        loads = self.model.moe_loads()
        self.n_layers = len(loads)
        layers = self.model.model.layers
        kinds = list(c.layer_types)
        self.probed = (kinds.index(KINDS["window"]),
                       len(kinds) - 1 - kinds[::-1].index(KINDS["full"]))
        first_moe = self.model.moe_layers()[0]
        # every so many-th row of the logits: a program's fetches come back
        # whole on every chip, and [32,768 x 98,304] do not fit beside the
        # state
        self.every = int(mix["logits_every"])
        assert S % self.every == 0, (S, self.every)
        shown = ht.slice_op(
            ht.array_reshape_op(logits, output_shape=(
                B * S // self.every, self.every, c.vocab_size)),
            begin_pos=(0, 0, 0),
            output_shape=(B * S // self.every, 1, c.vocab_size))
        probes = [layers[i].attn_out for i in self.probed]
        probes.append(first_moe.last_op)
        chosen = first_moe.chosen()
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        self.ex = ht.Executor(
            {"train": [loss, opt.minimize(loss)] + loads,
             "validate": [loss, shown] + probes + loads + [chosen]},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]),
            dist_strategy=self.strategy)
        self.params_m = sum(int(np.prod(v.shape))
                            for v in self.ex.params.values()
                            ) / 1e6 - self.untrained_m()
        a_chip = sum(int(np.prod(v.addressable_shards[0].data.shape))
                     for k, v in self.ex.params.items()
                     if not k.endswith(("_bias", "_load"))) / 1e6
        rope = config["rope_parameters"]
        self.say_memory("the executor's set-up")
        say(f"Mellum decoder: hidden {c.hidden_size}, layers "
            + " | ".join(k.split("_")[0] for k in kinds)
            + f", {c.heads_per_layer[0]}/{c.num_kv_heads} heads of "
            f"{c.head_dim}, window {c.sliding_window}; rotary full "
            f"{rope[KINDS['full']]['rope_type']} at "
            f"{c.rope[KINDS['full']]['rope_theta']:g}, window "
            f"{rope[KINDS['window']]['rope_type']} at "
            f"{c.rope[KINDS['window']]['rope_theta']:g}; every FFN sparse: "
            f"router {c.num_experts} wide (softmax, normalised over the "
            f"chosen), {c.moe_k} a token, experts of width "
            f"{c.intermediate_size}, {c.num_experts // self.ranks} a chip "
            f"over the {self.ranks} chips of {self.axis!r}, no shared "
            f"expert; vocabulary {c.vocab_size}, "
            f"{c.vocab_size // self.ranks} rows a chip; batch {B} x {S} "
            f"({B // self.ranks} a chip), {self.params_m:.1f} M parameters "
            f"on the host, {a_chip:.1f} M a chip, {job['compute_dtype']} "
            f"compute over f32 masters, {job['optimizer']}, recomputed: "
            f"{job['remat']}, loss = ce, strategy {mix['strategy']['name']}")

    @property
    def devices(self):
        return list(self.strategy.mesh.devices.flat)

    def pallas_ops(self):
        from hetu_tpu.ops.pallas import dispatch
        return (("flash_attention", "softmax_ce", "moe_gmm", "moe_rows",
                 "moe_select", "rotary") if dispatch.mosaic() else ())

    def step(self, feed):
        """One training step; a step in which any layer routed more pairs
        than the host computed has not done the configuration's work and
        its loss is returned as NaN (the Qwen3-Next builder's rule).  Counts
        the exchange's bytes of the step (``hetu_moe_exchange_bytes_total``:
        what the traced step's shapes say one chip receives in its
        all-gathers and reduce-scatters, a recomputed layer's second gather
        of its tokens among them)."""
        from hetu_tpu.layers.moe import record_moe_load
        from hetu_tpu.ops.moe import exchange_bytes_a_step
        out = self.ex.run("train", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        dropped = 0.0
        for i, (load, moe) in enumerate(zip(out[2:],
                                            self.model.moe_layers())):
            record_moe_load(f"layer{i}", load, exchange=exchange_bytes_a_step(
                moe.last_op.exchange, self.forward_passes))
            # the fullest chip's pairs: what one pass's rows are bound by
            by_chip = np.asarray(load[0]).reshape(self.ranks, -1).sum(1)
            self.held_peak = max(self.held_peak, float(by_chip.max()))
            dropped += float(load[0].sum() - load[1].sum())
        if dropped:
            self.steps_dropping += 1
            return float("nan")
        return float(out[0])

    def close(self):
        from hetu_tpu.ops.moe import held_rows
        c = self.config
        bound = held_rows(self.tokens_per_step * c["num_experts_per_tok"],
                          c["num_experts"], c["num_experts"] // self.ranks)
        self._say(f"pairs on one chip's experts, the fullest chip, layer and "
                  f"step: {self.held_peak:.0f} of the {bound} rows one pass "
                  f"lays out ({self.held_peak / bound:.2f}); pairs a further "
                  f"pass computed: "
                  f"{counter('hetu_moe_pairs_over_bound_total'):.0f}; steps "
                  f"that dropped a pair, each reported with a loss that is "
                  f"not finite: {self.steps_dropping}")
        self._say("exchange, bytes one chip received by the traced shapes, "
                  "all steps and layers: gather "
                  f"{counter('hetu_moe_exchange_bytes_total', direction='gather'):.0f}"
                  ", scatter "
                  f"{counter('hetu_moe_exchange_bytes_total', direction='scatter'):.0f}"
                  f"; expert axis of "
                  f"{counter('hetu_moe_expert_axis_size'):.0f}")
        self._say("hetu_attn_window_block_share (tile area the window "
                  "kernel's key loop visits over the causal plan's): "
                  f"{counter('hetu_attn_window_block_share'):.4f}")
        self.ex.close()

    def expected_kernel_shapes(self):
        """One chip's shard: its sequences x the query heads x positions x
        head size for the FULL layer's flash passes; the window layers'
        kernels stated beside them (``window_dims``); the loss kernel's rows
        and the pairs ONE chip routes a step."""
        c = self.config
        heads, d = c["num_attention_heads"], c["head_dim"]
        batch = self.batch // self.ranks
        full = self.model.layers_of(KINDS["full"])
        return {"flash_dims": (batch, heads, self.seq, d),
                "flash_elements": batch * heads * self.seq * d,
                "flash_rows": batch * heads, "head_dim": d,
                "attention_passes": full,
                "attention_layers": full * self.forward_passes,
                "causal": True,
                "window_dims": (batch, heads, self.seq, d),
                "window": min(c["sliding_window"], self.seq),
                "window_layers": self.model.layers_of(KINDS["window"]),
                "key_heads": c["num_key_value_heads"],
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": batch * self.seq,
                "moe_pairs": (self.tokens_per_step // self.ranks
                              * c["num_experts_per_tok"])}

    def say_memory(self, after):
        """The allocator's marks on the fullest chip so far (the harness's
        peak is the sum of two marks that this program reaches at different
        times: set-up's programs and the step's)."""
        stats = [d.memory_stats() or {} for d in self.devices]
        if not any(stats):
            return
        top = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
        self._say(f"memory after {after}, the chip with the highest mark: "
                  + ", ".join(f"{k} {top.get(k, 0) / 2 ** 30:.2f} GiB"
                              for k in ("bytes_in_use", "peak_bytes_in_use",
                                        "bytes_reserved",
                                        "peak_bytes_reserved")))

    def rows_of(self, array, per_sequence):
        """The rows of the compared sequences of ``array``, whose leading
        dim holds ``per_sequence`` rows a sequence of the batch."""
        a = np.asarray(array)
        a = a.reshape((self.batch, per_sequence) + a.shape[1:])
        return a[list(self.compared)].reshape((-1,) + a.shape[2:])

    def eval_loss(self, feed):
        """The program's loss on ``feed`` (all sequences), through the
        executor's ``validate`` subgraph under the strategy, and its gaps
        from what ``reference_loss`` kept of the compared sequences (it runs
        first): the Laguna builder's terms."""
        out = self.ex.run("validate", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        loss, logits, window, full, routed, *loads, chosen = out
        got = {"loss": float(loss), "ce": float(loss)}
        kept, S = self.kept, self.seq
        got["logits_gap"] = logits_gap(
            self.rows_of(logits[:, 0], S // self.every), kept.pop("logits"))
        del logits, out
        window = self.rows_of(window, 1)
        got["window_gap"] = logits_gap(window, kept["window"])
        got["window_edge"] = edge_share(window, kept["window"], kept["edges"])
        got["full_gap"] = logits_gap(self.rows_of(full, 1), kept["full"])
        got["routed_gap"] = logits_gap(
            self.rows_of(np.asarray(routed).reshape(-1, routed.shape[-1]), S),
            kept["routed"])
        loads = np.asarray(loads, np.float64)           # [layers, 4, E]
        got["dropped"] = float(1.0 - loads[:, 1].sum() / loads[:, 0].sum())
        theirs = np.asarray(kept["chosen"][0])          # [B S, k]
        hot = np.zeros((len(theirs), self.config["num_experts"]), bool)
        np.put_along_axis(hot, theirs, True, axis=1)
        got["routing_share"] = float(np.take_along_axis(
            hot, np.asarray(chosen), 1).sum() / theirs.size)
        built = nodes_built()
        got.update({f"{kind}_nodes": built[kind] - self._nodes_before[kind]
                    for kind in KINDS})
        self.say_memory("the validate program")
        return got

    def weights(self, prefix):
        """One group of the program's f32 masters under the reference's
        names, gathered to the host from the chips that hold them."""
        if not hasattr(self, "_ref_nodes"):
            self._ref_nodes = reference_nodes(self.model)
        if prefix in self._ref_nodes:
            return np.asarray(self.ex.params[self._ref_nodes[prefix].name])
        return {k[len(prefix):]: np.asarray(self.ex.params[v.name])
                for k, v in self._ref_nodes.items() if k.startswith(prefix)}

    def reference_loss(self, feed, chunk, **lower):
        """The plain reference's loss, as ``eval_loss`` names it, on all of
        ``feed`` with this executor's present weights: the uncut model on
        one chip, a layer and a sequence at a time.  ``lower``: the
        reference's ``matmul_inputs`` or ``without``
        (``reference/mellum_controls.py``)."""
        from ..reference import mellum as ref
        c = self.config
        window, full = self.probed
        sums, kept = ref.walk(
            self.weights, c, np.asarray(feed[self.nodes["ids"]]),
            np.asarray(feed[self.nodes["labels"]]), ranks=self.ranks,
            keep=self.compared, keep_attention=(window, full),
            # the windows one key off are the baseline's to compute
            edges_of=None if lower else window, logits_every=self.every,
            **lower)
        self.kept = {"logits": kept["logits"], "routed": kept["routed"],
                     "chosen": kept["chosen"],
                     "window": kept["attention"][window],
                     "full": kept["attention"][full]}
        if "edges" in kept:
            self.kept["edges"] = kept["edges"]
        self.say_memory("the reference")
        out = {k: float(v) for k, v in ref.loss_from_sums(sums).items()}
        kinds = c["layer_types"]
        out.update(logits_gap=0.0, window_gap=0.0, window_edge=0.0,
                   full_gap=0.0, routed_gap=0.0, routing_share=1.0,
                   dropped=0.0,
                   **{f"{kind}_nodes": float(kinds.count(name))
                      for kind, name in KINDS.items()})
        return out


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
