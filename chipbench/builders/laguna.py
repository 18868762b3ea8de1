"""Causal-LM pretraining of a Laguna decoder at one chip's share of an
expert-parallel job: ``LagunaForCausalLM`` from the configuration's published
keys, ``loss`` and ``opt.minimize`` through ``ht.Executor``, a fresh numpy
batch of ids and next-token labels fed every step, each expert layer's load
fetched beside the loss and counted by
``hetu_tpu.layers.moe.record_moe_load``.  Knows nothing of cells: sizes come
from the configuration file, batch shape from the traffic file.

The family's files: ``configs/laguna-xs.2-pretrain.json`` (the published keys;
``num_experts`` there is the experts HELD on this chip and ``vocab_size`` the
slice, both listed in ``reduced``; the ``deployment`` group holds the published
counts; ``job`` the optimizer and what is recomputed), this builder,
``reference/laguna.py`` (the plain reference, given the same held experts and
the same slice), ``reference/laguna_controls.py`` (the readings behind the
traffic file's limits), ``flops_laguna.py`` (operations and bytes) and the
readers ``metrics/*.laguna.py``, ``metrics/window_attn_*.py``.

Two kinds of attention layer, two head counts: the FULL layers' kernels go by
``hetu_flash_*`` and are what ``expected_kernel_shapes`` states
(``flash_dims``, ``attention_passes``, ``attention_layers``: the harness holds
every flash event to one shape, and the forward calls of a step between the
required passes and a whole recomputation's); the WINDOW layers' go by
``hetu_swa_*``, are held by name in ``KERNELS`` and stated under
``window_dims`` for their own reader.  Both kernel pairs read the key heads in
place on the projections' ``[B, S, H d]`` (since PR 52: nothing is repeated
before a kernel).
"""

from __future__ import annotations

import numpy as np

from .common import counter, jax_seed
from .granite_hybrid import logits_gap
from .llama import Program as LlamaProgram
from .qwen3_next import Program as Qwen3NextProgram

#: published keys that are LagunaConfig arguments under their own names
HF_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
           "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "head_dim", "max_position_embeddings", "attention_bias",
           "rms_norm_eps", "num_experts_per_tok", "moe_intermediate_size",
           "shared_expert_intermediate_size", "tie_word_embeddings", "gating",
           "sliding_window", "rope_parameters", "layer_types",
           "moe_apply_router_weight_on_input", "partial_rotary_factor",
           "mlp_layer_types", "moe_routed_scaling_factor",
           "num_attention_heads_per_layer")

KINDS = {"full": "full_attention", "window": "sliding_attention"}


def reference_params(model, params):
    """The program's weights under the plain reference's names
    (``chipbench/reference/laguna.py`` ``WEIGHTS``), found by walking the
    model object, not by parsing variable names.  The values are ``params``'
    own arrays: nothing is copied."""
    out = {"embed": model.model.embed.weight, "norm": model.model.norm.scale,
           "lm_head": model.lm_head.weight}
    for i, layer in enumerate(model.model.layers):
        a, f = layer.attn, layer.mlp
        named = [("input_norm", layer.input_norm.scale),
                 ("post_norm", layer.post_norm.scale),
                 ("q", a.q_proj.weight), ("k", a.k_proj.weight),
                 ("v", a.v_proj.weight), ("o", a.out_proj.weight),
                 ("gate", a.gate_proj.weight)]
        if layer.dense:
            named += [("mlp_gate", f.gate.weight), ("mlp_up", f.up.weight),
                      ("mlp_down", f.down.weight)]
        else:
            named += [("router", f.gate.wg), ("w_gate", f.w1), ("w_up", f.w3),
                      ("w_down", f.w2)]
            named += zip(("shared_gate", "shared_up", "shared_down"),
                         f.shared)
        out.update({f"layers.{i}.{k}": v for k, v in named})
    return {k: params[v.name] for k, v in out.items()}


def edge_share(got, want, edges):
    """How far a window layer's output ``got`` lies from the reference's
    ``want`` on the way to the nearer of ``edges`` (the reference's with one
    key fewer and one key more in the window): its distance from ``want``
    over that plus its distance from the nearer edge.  0 at the reference,
    0.5 as near to another window as to the right one, 1 at a window one key
    off; the plain gap cannot tell, because one key of 512 moves the output
    by about as much as bf16 does."""
    here = logits_gap(got, want)
    there = min(logits_gap(got, edge) for edge in edges)
    return here / max(here + there, 1e-30)


def nodes_built():
    """``{"full": n, "window": n}``: the attention nodes the process has built
    so far, by kind (``hetu_attn_layers_total``)."""
    return {kind: counter("hetu_attn_layers_total", kind=kind)
            for kind in KINDS}


class Program(Qwen3NextProgram):
    """One Executor with a ``train`` subgraph (loss, update, per-layer expert
    load) and, for the correctness check, a ``validate`` subgraph of the same
    loss, the logits under it, the attention sublayer's output of one window
    layer and of the last full layer, the first expert layer's routed sum
    before its shared expert, the same load and what each token chose in that
    layer.  ``make_batches``, ``retraces``,
    ``uniform_loss`` and ``kernel_choices`` are the Llama builder's, ``step``
    (a step that left a routed pair without a row reports a loss that is not
    finite) and ``close`` the Qwen3-Next builder's."""

    KERNELS = LlamaProgram.KERNELS + ("hetu_swa_fwd", "hetu_swa_bwd")

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu.models import LagunaConfig, LagunaForCausalLM
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
        self.held = tuple(dep["experts_held"])
        assert self.held[1] == config["num_experts"], (
            "num_experts in the configuration file is the experts held here")
        c = LagunaConfig(seq_len=S, num_experts=dep["num_experts"],
                         experts_held=self.held, remat=job["remat"],
                         **{key: config[key] for key in HF_KEYS})
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, S), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, S), dtype=np.int32)}
        self.model = LagunaForCausalLM(c)
        logits = self.model(self.nodes["ids"])
        loss, _ = self.model.loss_terms(
            self.nodes["ids"], self.nodes["labels"], logits=logits)
        loads = self.model.moe_loads()
        self.n_layers = len(loads)
        layers = self.model.model.layers
        # a window layer's and the LAST full layer's own output beside the
        # logits: one sublayer of five moves the logits by less than its own
        # output moves
        kinds = list(c.layer_types)
        self.probed = (kinds.index(KINDS["window"]),
                       len(kinds) - 1 - kinds[::-1].index(KINDS["full"]))
        first_moe = self.model.moe_layers()[0]
        # the routed sum alone: beside the shared expert's output (the same
        # initialiser on a matrix, not on a stack of them) it is too small
        # for any other term to see what the router's weights are
        probes = [layers[i].attn_out for i in self.probed]
        probes.append(first_moe.last_op)
        chosen = first_moe.chosen()
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        self.ex = ht.Executor(
            {"train": [loss, opt.minimize(loss)] + loads,
             "validate": [loss, logits] + probes + loads + [chosen]},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]))
        self.params_m = sum(int(np.prod(v.shape))
                            for v in self.ex.params.values()
                            ) / 1e6 - self.untrained_m()
        rope = config["rope_parameters"]
        say(f"Laguna decoder: hidden {c.hidden_size}, layers "
            + " | ".join(f"{k.split('_')[0]} {h}/{c.num_kv_heads}"
                         for k, h in zip(kinds, c.heads_per_layer))
            + f" heads of {c.head_dim}, window {c.sliding_window}, a gate a "
            f"head: {c.gating}; rotary full "
            f"{rope[KINDS['full']]['rope_type']} on "
            f"{c.rope[KINDS['full']]['rotary_dim'] or c.head_dim} "
            f"dimensions at {c.rope[KINDS['full']]['rope_theta']:g}, window "
            f"{rope[KINDS['window']]['rope_type']} on "
            f"{c.rope[KINDS['window']]['rotary_dim'] or c.head_dim} at "
            f"{c.rope[KINDS['window']]['rope_theta']:g}; FFNs "
            f"{list(c.mlp_layer_types)}: dense {c.dense_intermediate_size} "
            f"wide, router {c.num_experts} wide (sigmoid, x "
            f"{c.routed_scaling_factor}), {c.moe_k} a token, experts "
            f"{self.held[0]}..{self.held[0] + self.held[1] - 1} held (width "
            f"{c.intermediate_size}), shared expert {c.shared_width}; "
            f"vocabulary slice {c.vocab_size} of {dep['vocab_size']}; batch "
            f"{B} x {S}, {self.params_m:.1f} M parameters, "
            f"{job['compute_dtype']} compute over f32 masters, "
            f"{job['optimizer']}, recomputed: {job['remat']}, loss = ce")

    def untrained_m(self):
        """Millions of numbers among the executor's variables that are not
        parameters: the routers' (unused) selection bias and the load."""
        return sum(int(np.prod(v.shape)) for k, v in self.ex.params.items()
                   if k.endswith(("_bias", "_load"))) / 1e6

    def close(self):
        self._say("hetu_attn_window_block_share (tile area the window "
                  "kernel's key loop visits over the causal plan's): "
                  f"{counter('hetu_attn_window_block_share'):.4f}")
        super().close()

    def pallas_ops(self):
        from hetu_tpu.ops.pallas import dispatch
        return (("flash_attention", "softmax_ce", "moe_gmm", "moe_rows")
                if dispatch.mosaic() else ())

    @property
    def forward_passes(self):
        """The most forward passes of a full layer a step: two where whole
        layers are recomputed in the backward pass (a step that keeps the
        kernel's output through the recomputation runs one)."""
        return 2 if self.config["job"]["remat"] == "layer" else 1

    @property
    def window_forward_passes(self):
        """The most forward passes of a window layer's attention a step."""
        return 2 if self.config["job"]["remat"] in ("layer", "window") else 1

    def heads_of(self, kind):
        """The query heads of the layers of ``kind`` ("full" / "window")."""
        c = self.config
        return {h for h, k in zip(c["num_attention_heads_per_layer"],
                                  c["layer_types"]) if k == KINDS[kind]}

    def expected_kernel_shapes(self):
        """Flash attention's work is the FULL layers': batch x their query
        heads (the kernel reads the key heads in place, one for each group of
        query heads) x positions x head size; ``attention_passes`` the passes
        a step REQUIRES (one forward and one backward a full layer),
        ``attention_layers`` the MOST forward calls a step may make (a
        recomputed layer's twice).  The window layers' kernels go by another
        name and are stated beside them: ``window_dims``, ``window``,
        ``window_layers`` (layers, so the required passes; not calls)."""
        c = self.config
        (heads,), (w_heads,) = self.heads_of("full"), self.heads_of("window")
        d = c["head_dim"]
        return {"flash_dims": (self.batch, heads, self.seq, d),
                "flash_elements": self.batch * heads * self.seq * d,
                "flash_rows": self.batch * heads, "head_dim": d,
                "attention_passes": self.model.layers_of(KINDS["full"]),
                "attention_layers": (self.model.layers_of(KINDS["full"])
                                     * self.forward_passes),
                "causal": True,
                "window_dims": (self.batch, w_heads, self.seq, d),
                "window": min(c["sliding_window"], self.seq),
                "window_layers": self.model.layers_of(KINDS["window"]),
                "key_heads": c["num_key_value_heads"],
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.batch * self.seq,
                "moe_pairs": self.tokens_per_step * c["num_experts_per_tok"]}

    def eval_loss(self, feed):
        """The program's loss on ``feed``, ``{"loss", "ce", "logits_gap",
        "window_gap", "window_edge", "full_gap", "routed_gap",
        "routing_share", "dropped", "full_nodes", "window_nodes"}``, through
        the executor's ``validate`` subgraph.  The gaps are relative L2
        distances from what ``reference_loss`` kept from the same batch (it
        runs first): of the logits, of the first window layer's attention
        sublayer's output, of the last full layer's and of the first expert
        layer's routed sum; ``window_edge`` says which window the window
        layer's output lies nearest (``edge_share``);
        ``routing_share`` the share of the reference's (token, expert) pairs
        of the first expert layer that the program chose too; ``dropped`` the
        share of the pairs routed to held experts that got no row;
        ``*_nodes`` the attention nodes built for this program, by kind."""
        # to the host: the [B S, V] logits stay on the device no longer than
        # the fetch
        out = self.ex.run("validate", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        loss, logits, window, full, routed, *loads, chosen = out
        got = {"loss": float(loss), "ce": float(loss)}
        kept = self.kept
        got["logits_gap"] = logits_gap(logits, kept.pop("logits"))
        got["window_gap"] = logits_gap(window, kept["window"])
        got["window_edge"] = edge_share(window, kept["window"],
                                        kept["edges"])
        got["full_gap"] = logits_gap(full, kept["full"])
        got["routed_gap"] = logits_gap(routed.reshape(kept["routed"].shape),
                                       kept["routed"])
        loads = np.asarray(loads, np.float64)           # [layers, 4, held]
        got["dropped"] = float(1.0 - loads[:, 1].sum() / loads[:, 0].sum())
        theirs = np.asarray(kept["chosen"][0])          # [T, k]
        hot = np.zeros((len(theirs), self.config["deployment"]["num_experts"]),
                       bool)
        np.put_along_axis(hot, theirs, True, axis=1)
        got["routing_share"] = float(np.take_along_axis(
            hot, np.asarray(chosen), 1).sum() / theirs.size)
        built = nodes_built()
        got.update({f"{kind}_nodes": built[kind] - self._nodes_before[kind]
                    for kind in KINDS})
        return got

    def reference_loss(self, feed, chunk, **lower):
        """The plain reference's loss, as ``eval_loss`` names it, on all of
        ``feed`` with this executor's present weights (its f32 masters, read
        in place), the same held experts and the same vocabulary slice,
        ``chunk`` sequences at a time.  ``lower``: the reference's
        ``matmul_inputs`` or ``without`` (the readings of a lower precision
        and of a changed piece, ``reference/laguna_controls.py``)."""
        import jax
        from ..reference import laguna as ref
        c = self.config
        params = lower.pop("params", None) or reference_params(
            self.model, self.ex.params)
        held = lower.pop("held", self.held)
        window, full = self.probed
        sums = jax.jit(lambda p, i, l: ref.loss_sums(
            p, c, i, l, held=held, keep_logits=True, keep_attention=(full,),
            **lower))
        # a second look at the window layer, so that what it keeps does not
        # lie on the device beside the logits; the windows one key off are
        # the baseline's to compute (a control is held to the baseline's)
        look = jax.jit(lambda p, i: ref.window_layer(
            p, c, i, window, held=held, edges=not lower, **lower))
        ids = np.asarray(feed[self.nodes["ids"]])
        labels = np.asarray(feed[self.nodes["labels"]])
        #: what is kept of a chunk, and the axis its sequences lie along:
        #: chosen [layers, T, k], logits [B S, V] f32, routed [T, hidden],
        #: full (the layer's index in front) and edges [., B, S, hidden],
        #: window [B, S, hidden]
        axes = {"chosen": 1, "logits": 0, "attention": 1, "window": 0,
                "routed": 0, "edges": 1}
        tot, kept = None, {}
        for lo in range(0, self.batch, chunk):
            part = jax.device_get(sums(params, ids[lo:lo + chunk],
                                       labels[lo:lo + chunk]))
            part.update(jax.device_get(look(params, ids[lo:lo + chunk])))
            for k in axes.keys() & part.keys():
                kept.setdefault(k, []).append(part.pop(k))
            tot = part if tot is None else {k: tot[k] + v
                                            for k, v in part.items()}
        self.kept = {k: np.concatenate(v, axis=axes[k])
                     for k, v in kept.items()}
        self.kept["full"] = self.kept.pop("attention")[0]
        out = {k: float(v) for k, v in ref.loss_from_sums(tot).items()}
        kinds = c["layer_types"]
        out.update(logits_gap=0.0, window_gap=0.0, window_edge=0.0,
                   full_gap=0.0, routed_gap=0.0, routing_share=1.0,
                   dropped=0.0,
                   **{f"{kind}_nodes": float(kinds.count(name))
                      for kind, name in KINDS.items()})
        return out


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
