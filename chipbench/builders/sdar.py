"""Block-diffusion training of an SDAR decoder at one chip's share of an
expert-parallel job: ``SdarMoeForCausalLM`` from the configuration's published
keys, ``loss`` and ``opt.minimize`` through ``ht.Executor``, a fresh numpy
batch made by ``hetu_tpu.dataloader.block_diffusion_noise`` (``input_ids [B,
2L]``, the clean copy and then the noised one; ``labels [B, L]``; ``weights [B,
L]``) fed every step, the loss and each layer's load fetched as ONE value and
the loads counted by ``hetu_tpu.layers.moe.record_moe_load``: the loop of
``examples/nlp/train_llama.py --model sdar-30b-a3b-chat``.  Knows nothing of
cells: sizes come from the configuration file, batch shape from the traffic
file.

The family's files: ``configs/sdar-30b-a3b-chat-train.json`` (the published
keys; ``num_experts`` there is the experts HELD on this chip and ``vocab_size``
the slice, both listed in ``reduced``; ``deployment`` holds the published
counts; ``assumed`` the block length, the schedule and the mask token;
``job`` the optimizer and what is recomputed), this builder,
``reference/sdar.py`` (the plain reference, given the same held experts and
slice), ``reference/sdar_controls.py``, ``flops_sdar.py`` and the readers
``metrics/*.sdar.py`` and ``metrics/diffusion_masked_share.py``.

ONE flash call a layer over all ``2L`` positions (``flash_dims`` ``B x heads x
2L x head_dim``; ``Program.seq`` is the POSITIONS of a pass, which the harness
holds every forward event to, ``tokens_per_step`` the data's tokens): the
kernels go by ``hetu_flash_fwd_bd`` / ``hetu_flash_bwd_bd``, names that hold
the flash passes', and walk the tiles that hold a visible pair alone.
"""

from __future__ import annotations

import numpy as np

from .common import counter, jax_seed
from .granite_hybrid import logits_gap
from .llama import Program as LlamaProgram

#: published keys that are SdarMoeConfig arguments under their own names
HF_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
           "num_attention_heads", "num_key_value_heads", "head_dim",
           "rope_theta", "rms_norm_eps", "num_experts_per_tok",
           "moe_intermediate_size", "norm_topk_prob", "tie_word_embeddings",
           "max_position_embeddings", "attention_bias")


def reference_params(model, params):
    """The program's weights under the plain reference's names
    (``chipbench/reference/sdar.py`` ``WEIGHTS``), found by walking the model
    object.  The values are ``params``' own arrays: nothing is copied."""
    out = {"embed": model.model.embed.weight, "norm": model.model.norm.scale,
           "lm_head": model.lm_head.weight}
    for i, layer in enumerate(model.model.layers):
        a, f = layer.attn, layer.mlp
        out.update({f"layers.{i}.{k}": v for k, v in (
            ("input_norm", layer.input_norm.scale),
            ("post_norm", layer.post_norm.scale),
            ("q", a.q_proj.weight), ("k", a.k_proj.weight),
            ("v", a.v_proj.weight), ("o", a.out_proj.weight),
            ("q_norm", a.q_norm.scale), ("k_norm", a.k_norm.scale),
            ("router", f.gate.wg), ("w_gate", f.w1), ("w_up", f.w3),
            ("w_down", f.w2))})
    return {k: params[v.name] for k, v in out.items()}


def unit_embedding(ex, model):
    """The embedding's rows at an RMS of 1 in place of the repo's N(0, 0.02^2)
    start, written over the executor's master (the reference reads the same
    array).  A trained model's stream at a layer's input is dominated by the
    token's own embedding; at 0.02 it is dominated, from layer 1 on, by the
    attention's average over the same values at every position, so all 16,384
    positions of a pass route alike (the fullest expert took 15,245 of 16,384
    positions in layer 2 at step 0, the pairs on the 16 held experts were
    4.5-43.7 k a layer by the seed's luck, and the routers collapsed within
    ten steps: my chip run, PR 62, call 62.5) and the timed step's expert
    work is a seed's, 527-570 ms a step over twelve seeds (call 62.3).  With
    the rows at 1 a position routes by its token, the uniform ids balance the
    loads, and what is left of the concentration is block diffusion's own: the
    masked positions share ONE embedding."""
    import jax.numpy as jnp
    name = model.model.embed.weight.name
    w = ex.params[name]
    ex.params[name] = w * jnp.reciprocal(jnp.sqrt(jnp.mean(jnp.square(w))))


def mask_row(config):
    """The mask token's row in this chip's slice of the vocabulary."""
    first, end = config["deployment"]["vocab_rows"]
    row = config["assumed"]["mask_token_id"] - first
    assert end - first == config["vocab_size"] and 0 <= row < end - first, (
        "the slice is the one that holds the mask token's row")
    return row


class Program(LlamaProgram):
    """One Executor with a ``train`` subgraph (loss, update, per-layer load)
    and, for the correctness check, a ``validate`` subgraph of the same loss,
    the unweighted mean over the masked positions, the logits of the noised
    half, the first layer's attention output behind ``W_o`` (both halves), the
    same load and what each position chose.  ``retraces``, ``uniform_loss``
    and ``kernel_choices`` are the Llama builder's."""

    KERNELS = LlamaProgram.KERNELS + ("hetu_moe_rows_sum",)
    #: the layer whose attention output the comparison looks at
    PROBED = 0

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu.graph.node import scope
        from hetu_tpu.models import SdarMoeConfig, SdarMoeForCausalLM
        from hetu_tpu.ops.pallas import dispatch

        self.config, self.mix, self._say = config, mix, say
        self.seed = seed
        self.held_peak, self.steps_dropping = 0.0, 0
        job, dep, assumed = (config[k] for k in ("job", "deployment",
                                                 "assumed"))
        self._choices_before = dispatch.choices()
        assert config["hidden_act"] == "silu" and not config["mlp_only_layers"]
        assert config["decoder_sparse_step"] == 1
        assert not config["use_sliding_window"] and not config["rope_scaling"]
        B, L = int(mix["batch"]), int(mix["seq"])
        assert int(mix["block"]) == assumed["block_length"]
        self.batch, self.tokens = B, L
        #: the positions of a pass: what the flash kernels walk
        self.seq = 2 * L
        self.tokens_per_step = B * L
        self.held = tuple(dep["experts_held"])
        assert self.held[1] == config["num_experts"], (
            "num_experts in the configuration file is the experts held here")
        self.mask_id = mask_row(config)
        c = SdarMoeConfig(
            seq_len=L, num_experts=dep["num_experts"],
            experts_held=self.held, remat=job["remat"] or None,
            block_length=assumed["block_length"], mask_token_id=self.mask_id,
            **{key: config[key] for key in HF_KEYS})
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, 2 * L), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, L), dtype=np.int32),
            "weights": ht.placeholder_op("weights", (B, L),
                                         dtype=np.float32)}
        self.model = SdarMoeForCausalLM(c)
        logits = self.model(self.nodes["ids"])
        loss, terms = self.model.loss_terms(
            self.nodes["ids"], self.nodes["labels"], self.nodes["weights"],
            logits=logits)
        loads = self.model.moe_loads()
        chosen = [m.chosen() for m in self.model.moe_layers()]
        self.n_layers = len(loads)
        # a fetched value is a copy of its own with the device idle, so the
        # train step hands out ONE: the loss, then the layers' loads
        self.load_shape = tuple(loads[0].var.shape)
        with scope("hetu_moe_other"):
            stats = ht.concatenate_op([
                ht.array_reshape_op(n, output_shape=(-1,))
                for n in [loss] + loads])
        probed = self.model.model.layers[self.PROBED]
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        self.ex = ht.Executor(
            {"train": [stats, opt.minimize(loss)],
             "validate": ([loss, terms["ce_masked"], logits, probed.attn_out]
                          + loads + chosen)},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]))
        unit_embedding(self.ex, self.model)
        self.params_m = sum(int(np.prod(v.shape))
                            for k, v in self.ex.params.items()
                            if not k.endswith("_load")) / 1e6
        say(f"SDAR decoder: hidden {c.hidden_size}, {c.num_layers} layers; "
            f"attention {c.num_heads}/{c.num_kv_heads} heads of {c.head_dim}, "
            f"a norm a head, rotary at {c.rope_theta:g} on positions 0.."
            f"{L - 1} twice, block-diffusion mask over {2 * L} positions in "
            f"blocks of {c.block_length}; router {c.num_experts} wide "
            f"(softmax, renormalised: {c.moe_renorm_topk}), {c.moe_k} a "
            f"token, experts {self.held[0]}.."
            f"{self.held[0] + self.held[1] - 1} held (width "
            f"{c.intermediate_size}); vocabulary slice {c.vocab_size} of "
            f"{dep['vocab_size']} (mask token at row {self.mask_id}); batch "
            f"{B} x {L} tokens, {self.params_m:.1f} M parameters, "
            f"{job['compute_dtype']} compute over f32 masters, "
            f"{job['optimizer']}, recomputed: {job['remat']}, loss = the "
            f"1/t-weighted ce over masked positions")

    def make_batches(self, seed, n):
        """``n`` feed dicts: ``L`` ids a sequence, uniform over the rows of
        the slice that are not the mask token's, from the seed; then the data
        path's noising step (one level a block, uniform on ``[eps, 1]``), so
        about half the positions are masked and which ones is the seed's."""
        from hetu_tpu.dataloader import block_diffusion_noise
        rng = np.random.default_rng([int(seed), 5])
        assumed = self.config["assumed"]
        out = []
        for _ in range(n):
            tok = rng.integers(0, self.config["vocab_size"] - 1,
                               (self.batch, self.tokens))
            tok = tok + (tok >= self.mask_id)       # never the mask token
            ids, labels, weights = block_diffusion_noise(
                tok, assumed["block_length"], self.mask_id, rng,
                eps=assumed["noise_eps"])
            out.append({self.nodes["ids"]: ids, self.nodes["labels"]: labels,
                        self.nodes["weights"]: weights})
        return out

    def step(self, feed):
        """One training step on the ONE value it hands out (the loss, then
        every layer's load): a step in which any layer routed more pairs to
        its held experts than it computed reports NaN."""
        from hetu_tpu.layers.moe import record_moe_load
        stats = self.ex.run("train", feed_dict=feed,
                            convert_to_numpy_ret_vals=True)[0]
        loads = stats[1:].reshape((self.n_layers,) + self.load_shape)
        dropped = 0.0
        for i, load in enumerate(loads):
            record_moe_load(f"layer{i}", load)
            self.held_peak = max(self.held_peak, float(load[0].sum()))
            dropped += float(load[0].sum() - load[1].sum())
        if dropped:
            self.steps_dropping += 1
            return float("nan")
        return float(stats[0])

    def close(self):
        tiles = {f"{which} {kind}": counter(
            "hetu_flash_tiles", mask="block_diffusion", tiles=kind,
            **{"pass": which}) for which in ("forward", "backward")
            for kind in ("walked", "visible")}
        masked, kept = (counter("hetu_diffusion_positions_total", state=state)
                        for state in ("masked", "kept"))
        self._say(f"hetu_flash_tiles (a head's tiles under the "
                  f"block-diffusion mask): {tiles}; noised positions masked "
                  f"{masked:.0f}, kept {kept:.0f}")
        from hetu_tpu.ops.moe import held_rows
        want = self.expected_kernel_shapes()
        bound = held_rows(want["moe_pairs"],
                          self.config["deployment"]["num_experts"],
                          self.held[1])
        self._say(f"pairs on held experts, the fullest layer and step: "
                  f"{self.held_peak:.0f} of the {bound} rows the bound "
                  f"allows ({self.held_peak / bound:.2f}); steps that "
                  f"dropped a pair, each reported with a loss that is not "
                  f"finite: {self.steps_dropping}")
        super().close()

    def pallas_ops(self):
        from hetu_tpu.ops.pallas import dispatch
        return (("flash_attention", "softmax_ce", "moe_gmm", "moe_rows")
                if dispatch.mosaic() else ())

    @property
    def forward_passes(self):
        """The most forward calls of a layer's kernel a step: two where whole
        layers are recomputed (a step that keeps the kernel's output through
        the recomputation runs one)."""
        return 2 if self.config["job"]["remat"] == "layer" else 1

    def expected_kernel_shapes(self):
        """Flash attention's work: ONE call a layer over the ``2L`` positions
        of a pass, ``batch x query heads x 2L x head size`` (on ``[B, H, S,
        D]`` the key heads are repeated before the kernel); not causal: the
        mask is ``block_diffusion`` in blocks of ``block_length``, and the
        pairs it shows a head are ``flops_sdar.visible_pairs``.  ``ce_rows``
        are the noised positions, ``moe_pairs`` the pairs a step routes over
        all experts (both copies go through the experts)."""
        c = self.config
        heads, d = c["num_attention_heads"], c["head_dim"]
        layers = self.model.attention_layers
        return {"flash_dims": (self.batch, heads, self.seq, d),
                "flash_elements": self.batch * heads * self.seq * d,
                "flash_rows": self.batch * heads, "head_dim": d,
                "attention_passes": layers,
                "attention_layers": layers * self.forward_passes,
                "causal": False, "mask": "block_diffusion",
                "block_length": c["assumed"]["block_length"],
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.batch * self.tokens,
                "moe_pairs": (self.batch * self.seq
                              * c["num_experts_per_tok"])}

    def eval_loss(self, feed):
        """The program's loss on ``feed``, ``{"loss", "ce", "ce_masked",
        "logits_gap", "attention_gap", "routing_mismatch", "dropped"}``,
        through the executor's ``validate`` subgraph.  ``ce`` is the weighted
        loss, ``ce_masked`` the unweighted mean over the masked positions;
        the gaps are relative L2 distances from what ``reference_loss`` kept
        from the same batch (it runs first): of the noised half's logits and
        of the first layer's attention output behind ``W_o`` over both halves;
        ``routing_mismatch`` the share of the reference's (position, expert)
        pairs, over all experts and layers, that the program did not choose;
        ``dropped`` the share of the pairs routed to held experts that got no
        row."""
        out = self.ex.run("validate", feed_dict=feed,
                          convert_to_numpy_ret_vals=True)
        loss, ce_masked, logits, attended, *rest = out
        n, kept = self.n_layers, self.kept
        got = {"loss": float(loss), "ce": float(loss),
               "ce_masked": float(ce_masked)}
        got["logits_gap"] = logits_gap(logits, kept.pop("logits"))
        got["attention_gap"] = logits_gap(attended, kept["attention"])
        loads = np.asarray(rest[:n], np.float64)        # [layers, 4, held]
        got["dropped"] = float(1.0 - loads[:, 1].sum() / loads[:, 0].sum())
        E = self.config["deployment"]["num_experts"]
        shared = 0
        for mine, theirs in zip(rest[n:], kept["chosen"]):
            hot = np.zeros((len(theirs), E), bool)
            np.put_along_axis(hot, np.asarray(theirs), True, axis=1)
            shared += np.take_along_axis(hot, np.asarray(mine), 1).sum()
        got["routing_mismatch"] = float(1.0 - shared / kept["chosen"].size)
        return got

    def reference_loss(self, feed, chunk, **lower):
        """The plain reference's loss, as ``eval_loss`` names it, on all of
        ``feed`` with this executor's present weights (its f32 masters, read
        in place), the same held experts and the same vocabulary slice,
        ``chunk`` sequences at a time.  What the comparison needs beside the
        sums stays on ``self.kept``.  ``lower``: the reference's
        ``matmul_inputs`` or ``without`` (a control's reading)."""
        import jax
        from ..reference import sdar as ref
        params = reference_params(self.model, self.ex.params)
        sums = jax.jit(lambda p, i, l, w: ref.loss_sums(
            p, self.config, i, l, w, held=self.held, keep_logits=True,
            keep=self.PROBED, **lower))
        ids, labels, weights = (np.asarray(feed[self.nodes[k]])
                                for k in ("ids", "labels", "weights"))
        #: what is kept of a chunk, and the axis its sequences lie along
        axes = {"chosen": 1, "logits": 0, "attention": 0}
        tot, kept = None, {}
        for lo in range(0, self.batch, chunk):
            part = jax.device_get(sums(params, *(
                x[lo:lo + chunk] for x in (ids, labels, weights))))
            for k in axes:
                kept.setdefault(k, []).append(part.pop(k))
            tot = part if tot is None else {k: tot[k] + v
                                            for k, v in part.items()}
        self.kept = {k: np.concatenate(v, axis=axes[k])
                     for k, v in kept.items()}
        out = {k: float(v) for k, v in ref.loss_from_sums(tot).items()}
        out.update(logits_gap=0.0, attention_gap=0.0, routing_mismatch=0.0,
                   dropped=0.0)
        return out


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
