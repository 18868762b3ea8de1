"""Causal-LM pretraining of a Granite 4.0-H decoder at one pipeline stage of
one whole period, built the way ``examples/nlp/train_llama.py --model
granite-4.0-h-micro`` builds it: ``GraniteHybridForCausalLM`` from the
configuration's published keys, ``loss`` and ``opt.minimize`` through
``ht.Executor``, a fresh numpy batch of ids and next-token labels fed every
step.  Knows nothing of cells: sizes come from the configuration file, batch
shape from the traffic file.

The family's files: ``configs/granite-4.0-h-micro-pretrain.json`` (the
published ``config.json`` keys; ``num_hidden_layers`` and ``layer_types``
there are one pipeline stage, ``vocab_size`` the slice, all listed in
``reduced``; the ``deployment`` group holds the published values; ``job`` the
optimizer, the compute type, what is recomputed and the chunk the scan
runs at), this builder, ``reference/granite_hybrid.py`` (the plain
reference, given the same slice), ``flops_granitehybrid.py`` (operations and
bytes) and the readers ``metrics/*.granite_hybrid.py`` with
``metrics/_scopes.py`` and ``metrics/_blocks.py``.
"""

from __future__ import annotations

import numpy as np

from .common import jax_seed
from .llama import Program as LlamaProgram
from .nemotron_h import relative_gaps

#: published keys that are GraniteHybridConfig arguments under their own names
HF_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "layer_types",
           "num_attention_heads", "num_key_value_heads",
           "shared_intermediate_size", "mamba_n_heads", "mamba_d_head",
           "mamba_d_state", "mamba_n_groups", "mamba_d_conv", "mamba_expand",
           "mamba_chunk_size", "attention_multiplier",
           "embedding_multiplier", "residual_multiplier", "logits_scaling",
           "rms_norm_eps", "tie_word_embeddings")

TERMS = ("ce",)


def logits_gap(got, want):
    """The relative L2 distance of the program's logits from the
    reference's, summed in f64 on the host: two ``[B S, V]`` f32 arrays and
    their difference beside the training state would be the process's peak
    of device memory (96.6% of HBM for the step's own 88.3%; PERF.md,
    PR 37)."""
    want = np.asarray(want, np.float32)
    diff = np.asarray(got).astype(np.float32) - want
    return float(np.sqrt(np.sum(np.square(diff, out=diff), dtype=np.float64)
                         / np.sum(np.square(want), dtype=np.float64)))


#: the decay a position (``dt |A|``) of the first and the last head of the
#: long-memory probe (``ssd_state_gap``), log-spaced between over a whole
#: group's heads: a memory of about 10,000 down to 10 positions
PROBE_DECAY = (1e-4, 1e-1)


def reference_params(model, params):
    """The program's weights under the plain reference's names
    (``chipbench/reference/granite_hybrid.py`` ``WEIGHTS``), found by walking
    the model object, not by parsing variable names.  The values are
    ``params``' own arrays: nothing is copied."""
    out = {"embed": model.model.embed.weight, "norm": model.model.norm.scale}
    for i, layer in enumerate(model.model.layers):
        m = layer.mixer
        named = [("input_norm", layer.input_norm.scale),
                 ("post_norm", layer.post_norm.scale),
                 ("mlp_gate", layer.mlp.gate.weight),
                 ("mlp_up", layer.mlp.up.weight),
                 ("mlp_down", layer.mlp.down.weight)]
        if layer.kind == "mamba":
            named += [("in_proj", m.in_proj), ("conv", m.conv),
                      ("conv_bias", m.conv_bias), ("dt_bias", m.dt_bias),
                      ("a_log", m.a_log), ("d", m.d_skip),
                      ("ssm_norm", m.norm), ("out_proj", m.out_proj)]
        else:
            named += [("q", m.q_proj.weight), ("k", m.k_proj.weight),
                      ("v", m.v_proj.weight), ("o", m.out_proj.weight)]
        out.update({f"layers.{i}.{k}": v for k, v in named})
    return {k: params[v.name] for k, v in out.items()}


def probe_inputs(config, seq, seed):
    """Seeded inputs of the long-memory probe: ``x``, ``B``, ``C`` in the
    compute type, ``dt`` and ``A`` in f32 for ONE WHOLE GROUP of heads of the
    published size (``mamba_n_heads / mamba_n_groups``: 64, all reading one
    ``B`` and ``C``) over ``seq`` positions; head ``j`` forgets
    ``PROBE_DECAY`` (log-spaced) a position at ``dt = 1``, and ``dt`` is
    drawn about 1."""
    import jax.numpy as jnp
    p, n = config["mamba_d_head"], config["mamba_d_state"]
    heads = config["mamba_n_heads"] // config["mamba_n_groups"]
    rng = np.random.default_rng([int(seed), 7])
    ct = getattr(jnp, config["job"]["compute_dtype"])
    x = jnp.asarray(rng.standard_normal((1, seq, heads, p),
                                        dtype=np.float32), ct)
    B, C = (jnp.asarray(rng.standard_normal((1, seq, 1, n)) * n ** -0.5, ct)
            for _ in range(2))
    dt = jnp.asarray(np.logaddexp(0.0, rng.standard_normal(
        (1, seq, heads)) + 0.5), jnp.float32)
    A = jnp.asarray(-np.geomspace(*PROBE_DECAY, heads), jnp.float32)
    return x, dt, A, B, C


def ssd_state_gap(config, seq, seed, say, scan):
    """How far a state-space scan ends from the plain reference's where the
    state has to remember: the largest relative gap (L2, a head) between the
    last states of ``scan(x, dt, A, B, C) -> (y, last state)`` and of the
    reference's recurrence with its f32 state, both given ``probe_inputs``:
    every head of a group, so every block of heads the scan cuts the group
    into.  At its initial values the model's heads forget within tens to
    thousands of positions and the loss alone holds no type of the state; a
    state carried in bf16 shows here (the traffic file's
    ``reference_tolerance_why``)."""
    import jax
    import jax.numpy as jnp
    from ..reference import granite_hybrid as ref
    x, dt, A, B, C = probe_inputs(config, seq, seed)
    y, last = jax.jit(scan)(x, dt, A, B, C)
    with jax.default_matmul_precision("highest"):
        y_ref, last_ref = jax.jit(ref.ssm_recurrence)(
            x.astype(jnp.float32), dt, A, B.astype(jnp.float32),
            C.astype(jnp.float32))
    state, out = relative_gaps(last, last_ref, 1), relative_gaps(y, y_ref, 2)

    def shown(gaps):        # the slowest head, the quartiles, the fastest
        return [float(f"{g:.2e}") for g in (
            gaps[0], *np.quantile(gaps, (0.25, 0.5, 0.75)), gaps[-1])]
    say(f"state-space scan at long memory (one group of {x.shape[2]} heads "
        f"of {x.shape[-1]} x {B.shape[-1]} over {seq} positions, decays a "
        f"position {PROBE_DECAY[0]:.0e} .. {PROBE_DECAY[1]:.0e} x dt, "
        f"log-spaced): relative gap to the recurrence with an f32 state, "
        f"first head, quartiles over heads, last head: last state "
        f"{shown(state)} (largest {state.max():.2e} at head "
        f"{int(state.argmax())}), outputs {shown(out)}")
    return float(state.max())


class Program(LlamaProgram):
    """One Executor with a ``train`` subgraph (loss, update) and, for the
    correctness check, a ``validate`` subgraph of the same loss and the
    logits under it.
    ``make_batches``, ``step``, ``retraces``, ``uniform_loss`` and ``close``
    are the Llama builder's.  The f32 state-space state, which the loss does
    not hold, is held by ``ssd_state_gap``."""

    #: the Mosaic kernels of a train step that are held by name
    KERNELS = ("hetu_softmax_ce_fwd", "hetu_softmax_ce_bwd", "hetu_ssd_fwd",
               "hetu_ssd_bwd")

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu.models import (GraniteHybridConfig,
                                     GraniteHybridForCausalLM)
        from hetu_tpu.ops import ssd
        from hetu_tpu.ops.pallas import dispatch

        self.config, self.mix, self._say = config, mix, say
        self.seed = seed
        job, dep = config["job"], config["deployment"]
        self._choices_before = dispatch.choices()
        assert (config["hidden_act"] == "silu"
                and config["normalization_function"] == "rmsnorm"
                and config["position_embedding_type"] == "nope")
        assert config["mamba_conv_bias"] and not any(
            config[k] for k in ("attention_bias", "mamba_proj_bias",
                                "num_local_experts", "num_experts_per_tok"))
        assert job["scan_chunk"] == ssd.CHUNK, (
            "the chunk the readers credit is the one the program runs")
        B, S = int(mix["batch"]), int(mix["seq"])
        assert S <= config["max_position_embeddings"]
        self.batch, self.seq = B, S
        self.tokens_per_step = B * S
        c = GraniteHybridConfig(seq_len=S, remat=job["remat"],
                                **{key: config[key] for key in HF_KEYS})
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, S), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, S), dtype=np.int32)}
        self.model = GraniteHybridForCausalLM(c)
        # the logits beside the loss: at its initial weights the model's
        # logits are a tenth of a unit wide and the loss is within 0.01 of
        # the uniform guess whatever the layers compute (`logits_gap`)
        logits = self.model(self.nodes["ids"])
        loss, terms = self.model.loss_terms(
            self.nodes["ids"], self.nodes["labels"], logits=logits)
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        self.ex = ht.Executor(
            {"train": [loss, opt.minimize(loss)],
             "validate": [loss] + [terms[t] for t in TERMS] + [logits]},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]))
        self.params_m = sum(int(np.prod(v.shape))
                            for v in self.ex.params.values()) / 1e6
        kinds = "".join("*" if k == "attention" else "M"
                        for k in c.layer_types)
        say(f"Granite 4.0-H decoder: hidden {c.hidden_size}, layers {kinds} "
            f"(M Mamba-2: {c.mamba_num_heads} heads of {c.mamba_head_dim} in "
            f"{c.n_groups} group(s), state {c.ssm_state_size}, the scan at "
            f"chunks of {ssd.CHUNK}; * attention {c.num_heads}/"
            f"{c.num_kv_heads} heads of {c.hidden_size // c.num_heads}, no "
            f"position encoding, scores x {c.attention_multiplier}), each "
            f"followed by a gated MLP {c.intermediate_size} wide; residuals "
            f"x {c.residual_multiplier}, embeddings x "
            f"{c.embedding_multiplier}, logits / {c.logits_scaling}, tied "
            f"head; vocabulary slice {c.vocab_size} of {dep['vocab_size']}; "
            f"batch {B} x {S}, {self.params_m:.1f} M parameters, "
            f"{job['compute_dtype']} compute over f32 masters, "
            f"{job['optimizer']}, recomputed: {job['remat']}, loss = ce")

    def kernel_choices(self):
        """The Llama builder's, and a line that says how the scan cut its
        groups into programs (``hetu_ssd_entry_total``)."""
        from hetu_tpu.ops.pallas import ssd as kernels
        self._say("state-space scan calls traced, by (heads a group, heads "
                  f"a program): {kernels.entries()}")
        return super().kernel_choices()

    def pallas_ops(self):
        """Flash attention and the loss kernels (the MLPs are dense: no
        grouped product), and the scan where a layer is a Mamba-2 mixer."""
        ops = super().pallas_ops()[:2]
        scans = ops and "mamba" in self.config["layer_types"]
        return ops + ("ssd",) if scans else ops

    def expected_kernel_shapes(self):
        """Flash attention's work (batch, query heads, positions, head size:
        the KV heads are repeated before the kernel; one call an attention
        layer) and the rows of the loss kernel."""
        c = self.config
        heads = c["num_attention_heads"]
        hd = c["hidden_size"] // heads
        return {"flash_dims": (self.batch, heads, self.seq, hd),
                "flash_elements": self.batch * heads * self.seq * hd,
                "flash_rows": self.batch * heads, "head_dim": hd,
                "attention_layers": self.model.attention_layers,
                "causal": True,
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.batch * self.seq}

    def eval_loss(self, feed):
        """The program's loss on ``feed``, ``{"loss", "ce", "logits_gap",
        "ssd_state_gap"}``, through the executor's ``validate`` subgraph.
        ``logits_gap`` is the relative L2 distance of the program's logits
        from those ``reference_loss`` kept from the same batch (it runs
        first); ``ssd_state_gap`` is not of ``feed`` (``ssd_state_gap``)."""
        out = self.ex.run("validate", feed_dict=feed)
        got = dict(zip(("loss",) + TERMS, map(float, out)))
        got["logits_gap"] = logits_gap(out[-1], self._ref_logits)
        del self._ref_logits
        got["ssd_state_gap"] = self.ssd_state_gap()
        return got

    def ssd_state_gap(self):
        """``ssd_state_gap`` of the function the layers' ``hetu_ssm_scan``
        nodes call, at the cell's sequence length and the chunk the program
        runs, from the run's seed."""
        from hetu_tpu.ops import ssd
        return ssd_state_gap(self.config, self.seq, self.seed, self._say,
                             lambda *a: ssd.chunk_ssd(*a, chunk=ssd.CHUNK))

    def reference_loss(self, feed, chunk):
        """The plain reference's loss, as ``eval_loss`` names it, on all of
        ``feed`` with this executor's present weights (its f32 masters, read
        in place) and the same vocabulary slice, ``chunk`` sequences at a
        time."""
        import jax
        from ..reference import granite_hybrid as ref
        params = reference_params(self.model, self.ex.params)
        sums = jax.jit(lambda p, i, l: ref.loss_sums(p, self.config, i, l,
                                                     keep_logits=True))
        ids = np.asarray(feed[self.nodes["ids"]])
        labels = np.asarray(feed[self.nodes["labels"]])
        tot, logits = None, []
        for lo in range(0, self.batch, chunk):
            part = sums(params, ids[lo:lo + chunk], labels[lo:lo + chunk])
            part = jax.device_get(part)     # the logits to the host too
            logits.append(part.pop("logits"))
            tot = part if tot is None else {k: tot[k] + v
                                            for k, v in part.items()}
        self._ref_logits = np.concatenate(logits)        # [B S, V] f32
        out = {k: float(v) for k, v in ref.loss_from_sums(tot).items()}
        out.update(logits_gap=0.0, ssd_state_gap=0.0)
        return out


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
