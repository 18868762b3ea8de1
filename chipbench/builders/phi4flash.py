"""Supervised training of a SambaY decoder-hybrid-decoder
(Phi-4-mini-flash-reasoning) at one pipeline stage across the boundary of its
two decoders, built the way ``examples/nlp/train_llama.py --model
phi-4-mini-flash-reasoning`` builds it: ``Phi4FlashForCausalLM`` from the
configuration's published keys, ``loss`` and ``opt.minimize`` through
``ht.Executor``, a fresh numpy batch of ids and next-token labels fed every
step.  Knows nothing of cells: sizes come from the configuration file, batch
shape from the traffic file.

The family's files: ``configs/phi-4-mini-flash-reasoning-train.json`` (the
published keys; ``num_hidden_layers`` there is the run of layers built,
``vocab_size`` the slice, both listed in ``reduced``; ``first_layer_index``
the published index of the first; the ``deployment`` group holds the published
counts; ``assumed`` the Mamba sizes; ``job`` the optimizer and what is
recomputed), this builder, ``reference/phi4flash.py`` (the plain reference,
given the same run and slice), ``reference/phi4flash_controls.py`` (the
readings behind the traffic file's limits), ``flops_phi4flash.py`` (operations
and bytes) and the readers ``metrics/*.phi4flash.py``.

Three kinds of attention node, two kernel names: the FULL and the CROSS
layer's kernels go by ``hetu_flash_*`` and are what ``expected_kernel_shapes``
states (the halves of the query pairs are the heads the kernels see: 40 of 128
on 10 key heads of 128, ``layers/attention.py DifferentialAttention``); the
WINDOW layer's go by ``hetu_swa_*``, are held by name in ``KERNELS`` and
stated under ``window_dims`` for their own reader.
"""

from __future__ import annotations

import numpy as np

from .common import counter, jax_seed
from .granite_hybrid import logits_gap
from .laguna import edge_share
from .llama import Program as LlamaProgram
from .nemotron_h import relative_gaps

#: published keys that are Phi4FlashConfig arguments under their own names
HF_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
           "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
           "layer_norm_eps", "max_position_embeddings", "mb_per_layer",
           "sliding_window", "tie_word_embeddings", "hidden_act",
           "embd_pdrop", "resid_pdrop", "mlp_bias", "lm_head_bias",
           "model_type", "first_layer_index")
ASSUMED_KEYS = ("mamba_d_state", "mamba_d_conv", "mamba_expand",
                "mamba_dt_rank")

#: what the reference keeps of a pass and ``eval_loss`` compares, by the kind
#: of the layer whose mixer's own output it is (``memory``: layer 16's ``M``)
PROBES = ("memory", "window", "full", "gmu", "cross")
GAPS = {"memory": "scan_gap", "window": "window_gap",
        "full": "attention_gap", "gmu": "gmu_gap", "cross": "cross_gap"}

#: rows of logits the reference makes and hands to the host at a time, and
#: the channels of ``M`` that are compared (evenly spaced over all of them):
#: what the reference leaves on the device beside the training state counts in
#: ``peak_hbm_share``
LOGIT_ROWS, MEMORY_CHANNELS = 2048, 512

#: the decay a position (``|A|`` at ``delta = 1``) of the first and the last
#: channel of the scan's probe, log-spaced between: a memory of about 10,000
#: positions down to none
PROBE_DECAY, PROBE_CHANNELS = (1e-4, 5.0), 512


def reference_params(model, params):
    """The program's weights under the plain reference's names
    (``chipbench/reference/phi4flash.py`` ``WEIGHTS``), found by walking the
    model object, not by parsing variable names.  The values are ``params``'
    own arrays: nothing is copied."""
    out = {"embed": model.model.embed.weight, "norm": model.model.norm.scale,
           "norm_bias": model.model.norm.bias}
    for i, layer in enumerate(model.model.layers):
        m = layer.mixer
        named = [("input_norm", layer.input_norm.scale),
                 ("input_norm_bias", layer.input_norm.bias),
                 ("post_norm", layer.post_norm.scale),
                 ("post_norm_bias", layer.post_norm.bias),
                 ("mlp_gate", layer.mlp.gate.weight),
                 ("mlp_up", layer.mlp.up.weight),
                 ("mlp_down", layer.mlp.down.weight)]
        if layer.kind == "mamba":
            named += [("in_proj", m.in_proj), ("conv", m.conv),
                      ("conv_bias", m.conv_bias), ("x_proj", m.x_proj),
                      ("dt_proj", m.dt_proj), ("dt_bias", m.dt_bias),
                      ("a_log", m.a_log), ("d", m.d_skip),
                      ("out_proj", m.out_proj)]
        elif layer.kind == "gmu":
            named += [("in_proj", m.in_proj), ("out_proj", m.out_proj)]
        else:
            named += [("qkv", m.qkv_proj.weight),
                      ("qkv_bias", m.qkv_proj.bias),
                      ("o", m.out_proj.weight), ("o_bias", m.out_proj.bias),
                      ("subln", m.sub_norm)]
            named += zip(("lq1", "lk1", "lq2", "lk2"), m.lambdas)
        out.update({f"layers.{i}.{k}": v for k, v in named})
    return {k: params[v.name] for k, v in out.items()}


def probe_inputs(config, seq, seed):
    """Seeded inputs of the scan's probe: ``u`` in the compute type, ``delta``
    (about 1), ``A`` ``[PROBE_CHANNELS, N]``, ``B`` and ``C`` in f32 over
    ``seq`` positions; channel ``j`` forgets ``PROBE_DECAY`` (log-spaced) a
    position at ``delta = 1`` in its fastest state, half that in its
    slowest."""
    import jax.numpy as jnp
    n = config["assumed"]["mamba_d_state"]
    rng = np.random.default_rng([int(seed), 11])
    ct = getattr(jnp, config["job"]["compute_dtype"])
    u = jnp.asarray(rng.standard_normal((1, seq, PROBE_CHANNELS),
                                        dtype=np.float32), ct)
    B, C = (jnp.asarray(rng.standard_normal((1, seq, n)) * n ** -0.5,
                        jnp.float32) for _ in range(2))
    delta = jnp.asarray(np.exp(rng.uniform(np.log(0.5), np.log(1.5), (
        1, seq, PROBE_CHANNELS))), jnp.float32)
    A = -np.geomspace(*PROBE_DECAY, PROBE_CHANNELS)[:, None] * (
        0.5 + 0.5 * np.arange(1, n + 1) / n)
    return u, delta, jnp.asarray(A, jnp.float32), B, C


def scan_probe_gap(config, seq, seed, say, scan):
    """How far a selective scan lies from the plain recurrence with its f32
    state where the state has to remember: the largest relative gap (L2, a
    channel) between the outputs of ``scan(u, delta, A, B, C)`` and of the
    reference's recurrence, both given ``probe_inputs``.  At its initial
    values the model's channels forget within tens to a thousand positions;
    a state carried in bf16 shows here."""
    import jax
    import jax.numpy as jnp
    from ..reference import phi4flash as ref
    u, delta, A, B, C = probe_inputs(config, seq, seed)
    y = jax.jit(scan)(u, delta, A, B, C)
    with jax.default_matmul_precision("highest"):
        y_ref = jax.jit(ref.recurrence)(u.astype(jnp.float32), delta, A, B, C)
    gaps = relative_gaps(np.asarray(y, np.float32).swapaxes(1, 2),
                         np.asarray(y_ref).swapaxes(1, 2), 1)
    shown = [float(f"{g:.2e}") for g in (
        gaps[0], *np.quantile(gaps, (0.25, 0.5, 0.75)), gaps[-1])]
    say(f"selective scan at long memory ({PROBE_CHANNELS} channels x "
        f"{A.shape[1]} states over {seq} positions, decays a position "
        f"{PROBE_DECAY[0]:.0e} .. {PROBE_DECAY[1]:.0e} x delta, log-spaced): "
        f"relative gap of the outputs to the recurrence with an f32 state, "
        f"first channel, quartiles, last channel: "
        f"{shown}"
        f" (largest {gaps.max():.2e} at channel {int(gaps.argmax())})")
    return float(gaps.max())


def nodes_built():
    """Attention nodes the process has built so far, by kind
    (``hetu_attn_layers_total``)."""
    return {kind: counter("hetu_attn_layers_total", kind=kind)
            for kind in ("differential_full", "differential_window",
                         "differential_cross")}


class Program(LlamaProgram):
    """One Executor with a ``train`` subgraph (loss, update) and, for the
    correctness check, a ``validate`` subgraph of the same loss and the logits
    under it and a ``probes`` subgraph of the ``PROBES`` (two programs, so
    that the logits and the probes do not lie on the device together).
    ``make_batches``, ``step``, ``retraces``, ``uniform_loss`` and
    ``kernel_choices`` are the Llama builder's."""

    KERNELS = ("hetu_softmax_ce_fwd", "hetu_softmax_ce_bwd", "hetu_s6_fwd",
               "hetu_s6_bwd", "hetu_conv_fwd", "hetu_conv_bwd",
               "hetu_swa_fwd", "hetu_swa_bwd")

    def __init__(self, config, mix, seed, say):
        import jax.numpy as jnp
        import hetu_tpu as ht
        from hetu_tpu.models import Phi4FlashConfig, Phi4FlashForCausalLM
        from hetu_tpu.ops.pallas import dispatch

        self.config, self.mix, self._say = config, mix, say
        self.seed = seed
        job, dep = config["job"], config["deployment"]
        self._choices_before = dispatch.choices()
        self._nodes_before = nodes_built()
        B, S = int(mix["batch"]), int(mix["seq"])
        self.batch, self.seq = B, S
        self.tokens_per_step = B * S
        c = Phi4FlashConfig(
            seq_len=S, remat=job["remat"],
            published_layers=dep["num_hidden_layers"],
            **{key: config[key] for key in HF_KEYS},
            **{key: config["assumed"][key] for key in ASSUMED_KEYS})
        self.nodes = {
            "ids": ht.placeholder_op("ids", (B, S), dtype=np.int32),
            "labels": ht.placeholder_op("labels", (B, S), dtype=np.int32)}
        self.model = Phi4FlashForCausalLM(c)
        logits = self.model(self.nodes["ids"])
        loss, _ = self.model.loss_terms(
            self.nodes["ids"], self.nodes["labels"], logits=logits)
        self.kinds = c.kinds
        self.memory_stride = max(
            1, c.mamba_expand * c.hidden_size // MEMORY_CHANNELS)
        first = {kind: self.model.model.layers[self.kinds.index(kind)]
                 for kind in ("window", "full", "gmu", "cross")}
        probes = [self.model.model.shared["memory"]] + [
            first[kind].mixer_out for kind in PROBES[1:]]
        opt = getattr(ht, job["optimizer"])(**job["optimizer_kwargs"])
        self.ex = ht.Executor(
            {"train": [loss, opt.minimize(loss)],
             "validate": [loss, logits], "probes": probes},
            seed=jax_seed(seed),
            compute_dtype=getattr(jnp, job["compute_dtype"]))
        self.params_m = sum(int(np.prod(v.shape))
                            for v in self.ex.params.values()) / 1e6
        say(f"SambaY decoder: hidden {c.hidden_size}, layers "
            + " | ".join(f"{i} {k}" for i, k in zip(c.indices, self.kinds))
            + f" (published indices of {c.published_layers}); Mamba-1 "
            f"{c.mamba_expand * c.hidden_size} channels x {c.mamba_d_state} "
            f"states, step rank {c.mamba_dt_rank}, {c.mamba_d_conv} taps; "
            f"differential attention {c.num_heads}/{c.num_kv_heads} heads of "
            f"{c.head_dim} in pairs on one value of {2 * c.head_dim}, window "
            f"{c.sliding_window}, no position encoding; GMU on layer "
            f"{c.published_layers // 2}'s scan, cross-attention on layer "
            f"{c.published_layers // 2 + 1}'s K and V; each followed by a "
            f"gated MLP {c.intermediate_size} wide; LayerNorm; tied head; "
            f"vocabulary slice {c.vocab_size} of {dep['vocab_size']}; batch "
            f"{B} x {S}, {self.params_m:.1f} M parameters "
            f"({self.params_m * 12e6 / 2 ** 30:.2f} GiB resident at 12 B), "
            f"{job['compute_dtype']} compute over f32 masters, "
            f"{job['optimizer']}, recomputed: {job['remat']}, loss = ce")

    def close(self):
        from hetu_tpu.ops import selective_scan
        self._say(f"selective scans traced, by form: "
                  f"{selective_scan.entries()}; layers reading a kept value: "
                  + ", ".join(
                      f"{v} {counter('hetu_shared_value_readers', value=v):g}"
                      for v in ("scan", "kv")))
        self._say("hetu_diff_attn_lambda at the end of the run, by published "
                  f"layer: {self.model.record_lambdas(self.ex.params)}")
        super().close()

    def pallas_ops(self):
        from hetu_tpu.ops.pallas import dispatch
        return (("flash_attention", "softmax_ce", "causal_conv",
                 "selective_scan") if dispatch.mosaic() else ())

    @property
    def forward_passes(self):
        """The most forward passes of a layer a step: two where whole layers
        are recomputed in the backward pass."""
        return 2 if self.config["job"]["remat"] == "layer" else 1

    def expected_kernel_shapes(self):
        """Flash attention's work is the full and the cross layer's: batch x
        the HALVES of the query pairs (each a head of twice the published
        size whose other half is zero) x positions; ``attention_passes`` the
        passes a step REQUIRES, ``attention_layers`` the most forward calls
        it may make.  The window layer's kernels go by another name and are
        stated beside them."""
        c = self.config
        heads, d = c["num_attention_heads"], 2 * (
            c["hidden_size"] // c["num_attention_heads"])
        full = sum(k in ("full", "cross") for k in self.kinds)
        dims = (self.batch, heads, self.seq, d)
        return {"flash_dims": dims,
                "flash_elements": int(np.prod(dims)),
                "flash_rows": self.batch * heads, "head_dim": d,
                "attention_passes": full,
                "attention_layers": full * self.forward_passes,
                "causal": True, "window_dims": dims,
                "window": min(c["sliding_window"], self.seq),
                "window_layers": self.kinds.count("window"),
                "key_heads": c["num_key_value_heads"] // 2,
                "compute_dtype": c["job"]["compute_dtype"],
                "ce_rows": self.batch * self.seq}

    def scan_probe_gap(self, scan=None):
        from hetu_tpu.ops import selective_scan
        return scan_probe_gap(self.config, self.seq, self.seed, self._say,
                              scan or selective_scan.selective_scan)

    def eval_loss(self, feed):
        """The program's loss on ``feed``, ``{"loss", "ce", "logits_gap",
        "scan_gap", "scan_probe_gap", "window_gap", "window_edge",
        "attention_gap", "gmu_gap", "cross_gap", "nodes"}``, through the
        executor's ``validate`` subgraph.  The gaps are relative L2 distances
        from what ``reference_loss`` kept from the same batch (it runs
        first); ``window_edge`` says which window the window layer's output
        lies nearest (``builders/laguna.py edge_share``); ``scan_probe_gap``
        is not of ``feed``; ``nodes`` the differential attention nodes built
        for this program."""
        loss, logits = self.ex.run("validate", feed_dict=feed,
                                   convert_to_numpy_ret_vals=True)
        got = {"loss": float(loss), "ce": float(loss)}
        kept = self.kept
        got["logits_gap"] = logits_gap(logits, kept.pop("logits"))
        del logits
        probes = self.ex.run("probes", feed_dict=feed,
                             convert_to_numpy_ret_vals=True)
        probes[0] = probes[0][..., ::self.memory_stride]
        for name, value in zip(PROBES, probes):
            got[GAPS[name]] = logits_gap(value, kept[name])
        got["window_edge"] = edge_share(probes[PROBES.index("window")],
                                        kept["window"], kept["edges"])
        got["scan_probe_gap"] = self.scan_probe_gap()
        built = nodes_built()
        got["nodes"] = float(sum(built.values())
                             - sum(self._nodes_before.values()))
        return got

    def reference_loss(self, feed, chunk, **lower):
        """The plain reference's loss, as ``eval_loss`` names it, on all of
        ``feed`` with this executor's present weights (its f32 masters, read
        in place) and the same vocabulary slice, ``chunk`` sequences at a
        time.  ``lower``: the reference's ``matmul_inputs`` or ``without``
        (``reference/phi4flash_controls.py``)."""
        import jax
        from ..reference import phi4flash as ref
        c = self.config
        params = reference_params(self.model, self.ex.params)
        keep = tuple(p for p in PROBES if p != "window")
        stride = self.memory_stride

        def some(p, i, l):
            out = ref.loss_sums(p, c, i, l, keep_hidden=True, keep=keep,
                                **lower)
            return dict(out, memory=out["memory"][..., ::stride])
        sums = jax.jit(some)
        rows = jax.jit(lambda h, e: ref.logits_of(
            h, e, lower.get("matmul_inputs")))
        # a second look at the window layer, so that what it keeps does not
        # lie on the device beside the others; the windows one key off are
        # the baseline's to compute (a control is held to the baseline's)
        upto = dict(c, num_hidden_layers=self.kinds.index("window") + 1)
        look = jax.jit(lambda p, i: ref.forward(
            p, upto, i, keep=("window",), edges=not lower, **lower)[1])
        ids = np.asarray(feed[self.nodes["ids"]])
        labels = np.asarray(feed[self.nodes["labels"]])
        # what is kept of a chunk lies [B, S, .] but the logits [B S, V] and
        # the edges [2, B, S, hidden]
        tot, kept = None, {}
        for lo in range(0, self.batch, chunk):
            part = sums(params, ids[lo:lo + chunk], labels[lo:lo + chunk])
            hidden = part.pop("hidden")
            part = jax.device_get(part)
            # the logits a block of rows at a time, each to the host at once
            part["logits"] = np.concatenate([
                jax.device_get(rows(hidden[at:at + LOGIT_ROWS],
                                    params["embed"]))
                for at in range(0, hidden.shape[0], LOGIT_ROWS)])
            del hidden
            part.update(jax.device_get(look(params, ids[lo:lo + chunk])))
            for k in set(part) - {"ce", "n"}:
                kept.setdefault(k, []).append(part.pop(k))
            tot = part if tot is None else {k: tot[k] + v
                                            for k, v in part.items()}
        self.kept = {k: np.concatenate(v, axis=1 if k == "edges" else 0)
                     for k, v in kept.items()}
        out = {k: float(v) for k, v in ref.loss_from_sums(tot).items()}
        out.update({gap: 0.0 for gap in GAPS.values()}, logits_gap=0.0,
                   window_edge=0.0, scan_probe_gap=0.0,
                   nodes=float(sum(k in ("window", "full", "cross")
                                   for k in self.kinds)))
        return out


def build(config, mix, seed, say):
    return Program(config, mix, seed, say)
