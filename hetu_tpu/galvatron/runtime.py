"""Per-layer hybrid-parallel runtime on a factorized TPU mesh.

Reference: tools/Hetu-Galvatron/galvatron/core — the PyTorch runtime builds
per-layer TP×DP process groups (comm_groups.py:58-196), wraps each layer in
Megatron-TP modules + DDP/FSDP (parallel.py:166), inserts activation
redistribution between layers of different TP size (parallel.py:138,
redistribute.py), and drives GPipe/1F1B schedules (pipeline/pipeline.py:23).

TPU redesign — ONE SPMD program instead of process groups:

  * mesh = ("pp", "m0", ..., "m{k-1}") with k binary axes; a layer with
    tp=2^t takes t binary axes for tensor parallel and the rest for data
    parallel (config.tp_dp_axes).  Different layers → different
    PartitionSpecs, same program.
  * Megatron column/row-parallel matmuls need no hand-written collectives:
    weights carry shardings, activations carry with_sharding_constraint
    boundaries, and GSPMD inserts the all-reduce/all-gather — the manual
    f/g autograd functions of megatron mappings.py are the compiler's job.
  * DDP vs FSDP(zero-3) is purely a parameter-sharding choice: FSDP shards
    params over the dp axes too; XLA all-gathers at use and reduce-scatters
    gradients.
  * activation "redistribution" between adjacent layers of different tp
    = a sharding-constraint change.
  * checkpoint flag → jax.checkpoint on the layer body.
  * grad accumulation over `chunks` micro-batches via lax.scan; with
    pp_deg>1 and homogeneous stages the existing spmd pipeline
    (parallel/pipeline.py) provides the schedule.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .config import HybridParallelConfig, layer_mesh_axes, tp_dp_axes


def build_mesh(config: HybridParallelConfig, devices=None):
    if devices is None:
        devices = jax.devices()
    world = config.world or len(devices)
    k, maxes = layer_mesh_axes(world, config.pp_deg)
    names = ("pp",) + maxes
    sizes = (config.pp_deg,) + (2,) * k
    arr = np.array(devices[:world]).reshape(sizes)
    return Mesh(arr, names)


class LayerShardings:
    """PartitionSpecs for one layer under its searched strategy.

    ``mesh`` is the layer's execution mesh: the full (pp-less) mesh when
    pp_deg==1, or the layer's stage submesh (axes m0..mk-1) when the
    model is pipelined — per-layer TP×DP lives INSIDE a stage, exactly
    like the reference's per-layer groups within a pp rank range
    (comm_groups.py gen_tp_group_dist)."""

    def __init__(self, mesh, config, layer_idx):
        maxes = tuple(n for n in mesh.axis_names if n != "pp")
        k = len(maxes)
        tp = config.tp_sizes[layer_idx]
        consec = config.tp_consecutive[layer_idx]
        self.dp_axes, self.tp_axes = tp_dp_axes(k, maxes, tp, consec)
        self.fsdp = bool(config.dp_types[layer_idx])
        self.ckpt = bool(config.checkpoint_flags[layer_idx])
        # Megatron SP (reference transformer.py sequence_parallel): the
        # residual stream is seq-sharded over the tp axes; GSPMD turns the
        # entry to column-parallel matmuls into an all-gather and the exit
        # from row-parallel ones into a reduce-scatter (same ring bytes as
        # the plain-TP allreduce, 1/tp the LN/residual memory).
        self.sp = bool(config.sp_flags[layer_idx]) and bool(self.tp_axes)
        self.mesh = mesh

    def _axes(self, axes):
        return tuple(axes) if len(axes) != 1 else axes[0]

    def param_spec(self, tp_dim, ndim, fsdp_dim=None):
        """Spec for a parameter: shard ``tp_dim`` over the tp axes; under
        FSDP additionally shard ``fsdp_dim`` (default: first non-tp dim)
        over the dp axes."""
        spec = [None] * ndim
        if tp_dim is not None and self.tp_axes:
            spec[tp_dim] = self._axes(self.tp_axes)
        if self.fsdp and self.dp_axes:
            if fsdp_dim is None:
                fsdp_dim = next((d for d in range(ndim) if d != tp_dim), None)
            if fsdp_dim is not None and spec[fsdp_dim] is None:
                spec[fsdp_dim] = self._axes(self.dp_axes)
        return P(*spec)

    def act_spec(self, ndim, seq_shard=False):
        """Activations: batch over dp axes (+ optionally seq over tp axes =
        Megatron sequence parallelism for the LN/dropout segments)."""
        spec = [None] * ndim
        if self.dp_axes:
            spec[0] = self._axes(self.dp_axes)
        if seq_shard and self.tp_axes and ndim >= 2:
            spec[1] = self._axes(self.tp_axes)
        return P(*spec)

    def constrain(self, x, seq_shard=None):
        """Residual-stream constraint; seq_shard defaults to the layer's
        sequence-parallel flag."""
        if seq_shard is None:
            seq_shard = self.sp
        return lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.act_spec(x.ndim, seq_shard)))


def _layer_norm(x, g):
    """Shared LN (no bias): used by the transformer blocks AND the LM
    head so eps/dtype behavior can never drift between body and head."""
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.var(x, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g


def _rms_norm(x, g):
    """Shared RMSNorm (f32 accumulation, Llama convention)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), -1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + 1e-5)).astype(x.dtype) * g


class TransformerHPLayer:
    """A Megatron-parallel transformer layer as an HP layer spec.

    Column-parallel QKV/FFN-in (shard output dim), row-parallel proj/FFN-out
    (shard input dim); GSPMD materializes the g/f collectives.
    Reference: galvatron/core/tensor_parallel/transformer.py.
    """

    def __init__(self, hidden, heads, ffn=None, dtype=jnp.float32):
        self.hidden, self.heads = hidden, heads
        self.ffn = ffn or 4 * hidden
        self.dtype = dtype

    def init(self, key):
        h, f = self.hidden, self.ffn
        ks = jax.random.split(key, 4)
        s = 0.02
        return {
            "wqkv": jax.random.normal(ks[0], (h, 3 * h), self.dtype) * s,
            "wo": jax.random.normal(ks[1], (h, h), self.dtype) * s,
            "w1": jax.random.normal(ks[2], (h, f), self.dtype) * s,
            "w2": jax.random.normal(ks[3], (f, h), self.dtype) * s,
            "ln1": jnp.ones((h,), self.dtype),
            "ln2": jnp.ones((h,), self.dtype),
        }

    # param name -> (tp_dim, fsdp_dim)
    tp_dims = {"wqkv": (1, 0), "wo": (0, 1), "w1": (1, 0), "w2": (0, 1),
               "ln1": (None, None), "ln2": (None, None)}

    def param_specs(self, sh: LayerShardings):
        # (None, None) marks the 1-D norm scales; everything else is a
        # 2-D projection.  Shared by subclasses whose tp_dims follow the
        # same convention (LlamaHPLayer).
        out = {}
        for name, (tp_dim, fsdp_dim) in self.tp_dims.items():
            ndim = 1 if (tp_dim, fsdp_dim) == (None, None) else 2
            out[name] = sh.param_spec(tp_dim if ndim > 1 else None, ndim,
                                      fsdp_dim if ndim > 1 else None)
        return out

    def _ln(self, x, g):
        return _layer_norm(x, g)

    def _attend(self, q, k, v, sh: LayerShardings):
        """[b, nh, t, hd] heads tp-sharded, batch dp-sharded.

        Long sequences route through the Pallas flash kernel inside a
        shard_map over the layer mesh (pallas_call is not GSPMD-
        partitionable, but attention is local per head, so a head/batch-
        sharded shard_map is exact); short sequences keep the jnp path."""
        b, nh, t, hd = q.shape
        mesh = sh.mesh
        tp = int(np.prod([mesh.shape[a] for a in sh.tp_axes] or [1]))
        dp = int(np.prod([mesh.shape[a] for a in sh.dp_axes] or [1]))
        if (t >= 128 and hd <= 512 and nh % tp == 0 and b % dp == 0):
            from ..ops.pallas.flash_attention import \
                sharded_flash_attention
            return sharded_flash_attention(
                mesh, q, k, v, batch_axes=sh.dp_axes,
                head_axes=sh.tp_axes, causal=True)
        a = (q @ k.transpose(0, 1, 3, 2)) / np.sqrt(hd)
        mask = jnp.tril(jnp.ones((t, t), bool))
        a = jnp.where(mask, a, -1e9)
        a = jax.nn.softmax(a, axis=-1)
        return (a @ v).astype(v.dtype)

    def apply(self, params, x, sh: LayerShardings):
        b, t, h = x.shape
        nh = self.heads
        y = self._ln(x, params["ln1"])
        qkv = y @ params["wqkv"]                       # column-parallel
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, nh, h // nh).transpose(0, 2, 1, 3)
        k = k.reshape(b, t, nh, h // nh).transpose(0, 2, 1, 3)
        v = v.reshape(b, t, nh, h // nh).transpose(0, 2, 1, 3)
        o = self._attend(q, k, v, sh)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, h).astype(x.dtype)
        x = x + sh.constrain(o @ params["wo"])         # row-parallel + psum
        y = self._ln(x, params["ln2"])
        y = jax.nn.gelu(y @ params["w1"])              # column-parallel
        x = x + sh.constrain(y @ params["w2"])         # row-parallel + psum
        return sh.constrain(x)


class LlamaHPLayer(TransformerHPLayer):
    """A Llama decoder layer as an HP layer spec: RMSNorm, rotary q/k,
    optional GQA, SwiGLU FFN — the reference's Llama/Baichuan Galvatron
    tier (tools/Hetu-Galvatron/galvatron/models/llama/
    LlamaModel_tensor_parallel.py) rebuilt on shardings instead of
    Megatron process groups.  ``alibi=True`` gives the Baichuan-13B
    position scheme instead of RoPE (models/baichuan/)."""

    def __init__(self, hidden, heads, kv_heads=None, ffn=None,
                 rope_theta=10000.0, alibi=False, dtype=jnp.float32):
        self.hidden, self.heads = hidden, heads
        self.kv_heads = kv_heads or heads
        assert heads % self.kv_heads == 0
        self.ffn = ffn or int(hidden * 8 / 3)
        self.rope_theta = rope_theta
        self.alibi = alibi
        self.dtype = dtype

    def init(self, key):
        h, f = self.hidden, self.ffn
        kvd = self.kv_heads * (h // self.heads)
        ks = jax.random.split(key, 6)
        s = 0.02
        return {
            "wq": jax.random.normal(ks[0], (h, h), self.dtype) * s,
            "wkv": jax.random.normal(ks[1], (h, 2 * kvd), self.dtype) * s,
            "wo": jax.random.normal(ks[2], (h, h), self.dtype) * s,
            "wgate": jax.random.normal(ks[3], (h, f), self.dtype) * s,
            "wup": jax.random.normal(ks[4], (h, f), self.dtype) * s,
            "wdown": jax.random.normal(ks[5], (f, h), self.dtype) * s,
            "rms1": jnp.ones((h,), self.dtype),
            "rms2": jnp.ones((h,), self.dtype),
        }

    tp_dims = {"wq": (1, 0), "wkv": (1, 0), "wo": (0, 1),
               "wgate": (1, 0), "wup": (1, 0), "wdown": (0, 1),
               "rms1": (None, None), "rms2": (None, None)}

    def _rms(self, x, g):
        return _rms_norm(x, g)

    def apply(self, params, x, sh: LayerShardings):
        from ..ops.rotary import _rotary, _repeat_kv, _alibi_bias
        b, t, h = x.shape
        nh, kvh = self.heads, self.kv_heads
        hd = h // nh
        y = self._rms(x, params["rms1"])
        q = (y @ params["wq"]).reshape(b, t, nh, hd).transpose(0, 2, 1, 3)
        kv = y @ params["wkv"]                        # column-parallel
        k, v = jnp.split(kv, 2, axis=-1)
        k = k.reshape(b, t, kvh, hd).transpose(0, 2, 1, 3)
        v = v.reshape(b, t, kvh, hd).transpose(0, 2, 1, 3)
        if not self.alibi:
            q = _rotary(q, theta=self.rope_theta)
            k = _rotary(k, theta=self.rope_theta)
        if kvh != nh:
            k = _repeat_kv(k, n_rep=nh // kvh)
            v = _repeat_kv(v, n_rep=nh // kvh)
        if self.alibi:
            bias = _alibi_bias(q, num_heads=nh)
            a = (q @ k.transpose(0, 1, 3, 2)) / np.sqrt(hd) + bias
            mask = jnp.tril(jnp.ones((t, t), bool))
            a = jax.nn.softmax(jnp.where(mask, a, -1e9), axis=-1)
            o = (a @ v).astype(v.dtype)
        else:
            o = self._attend(q, k, v, sh)
        o = o.transpose(0, 2, 1, 3).reshape(b, t, h).astype(x.dtype)
        x = x + sh.constrain(o @ params["wo"])        # row-parallel + psum
        y = self._rms(x, params["rms2"])
        y = jax.nn.silu(y @ params["wgate"]) * (y @ params["wup"])
        x = x + sh.constrain(y @ params["wdown"])     # row-parallel + psum
        return sh.constrain(x)


class VocabEmbedHPSpec:
    """Token embedding as an HP 'layer': tokens [b, t] int32 → [b, t, h].

    tp shards the VOCAB dim of the table (Megatron VocabParallelEmbedding,
    reference site_package/megatron/core/tensor_parallel/layers.py — XLA's
    SPMD partitioner lowers the vocab-sharded gather to the same
    mask-local-rows + psum pattern Megatron hand-writes); an fsdp dp_type
    row — set from ``config.embed_sdp`` by ``lm_wrap_config`` — further
    shards it over the dp axes (the reference's embed_sdp flag,
    hybrid_parallel_config.py)."""

    def __init__(self, vocab, hidden, dtype=jnp.float32, init_scale=0.02):
        self.vocab, self.hidden = int(vocab), int(hidden)
        self.dtype, self.init_scale = dtype, init_scale

    def init(self, key):
        return {"wte": jax.random.normal(
            key, (self.vocab, self.hidden), self.dtype) * self.init_scale}

    def param_specs(self, sh: "LayerShardings"):
        return {"wte": sh.param_spec(0, 2, 1)}

    def apply(self, params, x, sh: "LayerShardings"):
        return sh.constrain(jnp.take(params["wte"], x, axis=0))


class LMHeadHPSpec:
    """Final norm + vocab-parallel LM head: [b, t, h] → logits [b, t, V]
    sharded over the tp axes on V (column-parallel; the CE loss reduces
    over the sharded vocab dim, GSPMD inserting the psum — logits are
    never unsharded, the point of Megatron's vocab-parallel CE).

    ``tied=True`` drops the head's own projection and reuses the
    embedding table (GPT-2/Megatron weight tying; the shared-table grad
    accumulates through the single vjp — no separate embedding-grad
    allreduce needed because pp_deg==1 keeps both on one submesh)."""

    def __init__(self, vocab, hidden, dtype=jnp.float32, norm="ln",
                 init_scale=0.02, tied=False):
        self.vocab, self.hidden = int(vocab), int(hidden)
        self.dtype, self.norm, self.init_scale = dtype, norm, init_scale
        self.tied = bool(tied)

    def init(self, key):
        p = {"gnorm": jnp.ones((self.hidden,), self.dtype)}
        if not self.tied:
            p["wlm"] = jax.random.normal(
                key, (self.hidden, self.vocab),
                self.dtype) * self.init_scale
        return p

    def param_specs(self, sh: "LayerShardings"):
        out = {"gnorm": sh.param_spec(None, 1)}
        if not self.tied:
            out["wlm"] = sh.param_spec(1, 2, 0)
        return out

    def apply(self, params, x, sh: "LayerShardings"):
        norm = _rms_norm if self.norm == "rms" else _layer_norm
        y = norm(x, params["gnorm"])
        if self.tied and "_tied_wte" not in params:
            raise KeyError(
                "tied LMHeadHPSpec.apply needs the shared table under "
                "'_tied_wte' (injected by HybridParallelModel._apply_range"
                "; pass the embedding table yourself when calling apply "
                "directly)")
        wlm = params["wlm"] if not self.tied else params["_tied_wte"].T
        logits = y @ wlm
        spec = [None] * 3
        if sh.dp_axes:
            spec[0] = sh._axes(sh.dp_axes)
        if sh.tp_axes:
            spec[2] = sh._axes(sh.tp_axes)
        return lax.with_sharding_constraint(
            logits, NamedSharding(sh.mesh, P(*spec)))


def lm_cross_entropy(logits, tokens):
    """Mean next-token CE over [b, t, V] logits vs [b, t] int targets.
    Works with vocab-sharded logits: the logsumexp reduction over V
    becomes a psum over the tp axes under GSPMD."""
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(
        logits.astype(jnp.float32), tokens[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)


def lm_wrap_config(cfg: HybridParallelConfig, embed_sdp=None):
    """Extend a searched per-transformer-layer config with embedding and
    LM-head rows on the first/last pipeline stage (the reference wraps
    model layers with embed/cls modules there, hybrid_parallel_config.py);
    ``embed_sdp`` (default: cfg.embed_sdp) makes both rows FSDP."""
    e = int(cfg.embed_sdp if embed_sdp is None else embed_sdp)
    div = list(cfg.pp_division)
    div[0] += 1
    div[-1] += 1   # pp_deg==1: same stage gets both rows
    return HybridParallelConfig(
        pp_deg=cfg.pp_deg,
        tp_sizes=[cfg.tp_sizes[0]] + cfg.tp_sizes + [cfg.tp_sizes[-1]],
        dp_types=[e] + cfg.dp_types + [e],
        tp_consecutive=([cfg.tp_consecutive[0]] + cfg.tp_consecutive
                        + [cfg.tp_consecutive[-1]]),
        checkpoint_flags=[0] + cfg.checkpoint_flags + [0],
        sp_flags=[0] + cfg.sp_flags + [0],
        pp_division=div, global_bsz=cfg.global_bsz, chunks=cfg.chunks,
        pipeline_type=cfg.pipeline_type,
        default_dp_type=cfg.default_dp_type, embed_sdp=e, world=cfg.world)


def make_lm_hybrid_model(vocab, layer_specs, cfg, embed_sdp=None,
                         norm="ln", dtype=jnp.float32, devices=None,
                         tie_embeddings=False):
    """Full-LM hybrid-parallel model (tokens → CE loss): embedding + the
    given transformer HP layers + vocab-parallel head under the searched
    config, matching the reference's Galvatron models
    (models/gpt/GPTModel_hybrid_parallel.py: embed and cls wrapped onto
    the first/last stage, embed_sdp honored).  ``tie_embeddings`` shares
    the table with the head (GPT-2 semantics) — pp_deg must be 1 so both
    live on one submesh; refused otherwise rather than silently untied."""
    if tie_embeddings and cfg.pp_deg > 1:
        raise ValueError(
            "tie_embeddings requires pp_deg == 1 (embedding and head must "
            "share a stage submesh); got pp_deg="
            f"{cfg.pp_deg}")
    hidden = layer_specs[0].hidden
    specs = ([VocabEmbedHPSpec(vocab, hidden, dtype=dtype)]
             + list(layer_specs)
             + [LMHeadHPSpec(vocab, hidden, dtype=dtype, norm=norm,
                             tied=tie_embeddings)])
    full = lm_wrap_config(cfg, embed_sdp)
    return HybridParallelModel(specs, full, loss_fn=lm_cross_entropy,
                               devices=devices)


class HybridParallelModel:
    """Applies a searched HybridParallelConfig to a stack of HP layers.

    pp_deg==1: all layers run inside one jitted step; per-layer shardings
    do the work the reference does with per-layer process groups.

    pp_deg>1: the searched ``pp_division`` is HONORED — layers partition
    into stages, each stage compiles its own forward and rematerializing
    backward over its pp-slice submesh (per-layer TP×DP/FSDP shardings
    intact inside the stage), and a host scheduler drives the searched
    ``config.pipeline_type`` schedule (gpipe or pipedream_flush/1F1B) over
    ``chunks`` micro-batches, transferring boundary activations/cotangents
    between stage device sets (the reference's pipeline/pipeline.py:133/343
    batched-p2p schedules).  JAX async dispatch overlaps stage programs —
    chunk m can be in stage 1 while chunk m+1 runs stage 0.
    """

    def __init__(self, layer_specs, config: HybridParallelConfig,
                 loss_fn=None, devices=None):
        assert len(layer_specs) == config.n_layers
        self.specs = layer_specs
        self.config = config
        self.mesh = build_mesh(config, devices)
        self.pp = config.pp_deg
        if self.pp > 1:
            rest = self.mesh.axis_names[1:]
            self.stage_meshes = [Mesh(self.mesh.devices[s], rest)
                                 for s in range(self.pp)]
            ranks = config.pp_ranks()
            self.stage_layers = [[i for i, r in enumerate(ranks) if r == s]
                                 for s in range(self.pp)]
            for s, idxs in enumerate(self.stage_layers):
                if not idxs:
                    raise ValueError(
                        f"pp_division {config.pp_division} leaves stage "
                        f"{s} empty — config cannot be honored")
            layer_mesh = lambda i: self.stage_meshes[ranks[i]]
        else:
            self.stage_meshes = [self.mesh]
            self.stage_layers = [list(range(config.n_layers))]
            layer_mesh = lambda i: self.mesh
        self.shardings = [LayerShardings(layer_mesh(i), config, i)
                          for i in range(config.n_layers)]
        self.loss_fn = loss_fn or (lambda out, tgt: jnp.mean((out - tgt) ** 2))
        self._stage_fwd = None

    def init_params(self, key):
        keys = jax.random.split(key, len(self.specs))
        params = []
        for spec, sh, k in zip(self.specs, self.shardings, keys):
            p = spec.init(k)
            pspecs = spec.param_specs(sh)
            p = {n: jax.device_put(v, NamedSharding(sh.mesh, pspecs[n]))
                 for n, v in p.items()}
            params.append(p)
        return params

    def save(self, path, params, opt_state=None):
        """Checkpoint the hybrid-parallel state: params gather to host
        numpy (shardings are a placement property, not data), alongside
        the searched config for load-time validation.  Reference:
        Galvatron's save_checkpoint over Megatron state dicts."""
        import pickle
        state = {
            "config": self.config.to_json(),
            "params": jax.tree_util.tree_map(np.asarray, params),
            "opt_state": (None if opt_state is None else
                          jax.tree_util.tree_map(np.asarray, opt_state)),
        }
        with open(path, "wb") as f:
            pickle.dump(state, f)

    def _expected_param_shapes(self):
        # abstract init: shapes without spending FLOPs
        key = jax.random.PRNGKey(0)
        return [jax.eval_shape(spec.init, key) for spec in self.specs]

    def load(self, path):
        """Restore (params, opt_state); params re-place onto each layer's
        searched shardings (a checkpoint written under one parallel config
        reloads under another — the host copy is layout-free).

        Optimizer state is pipeline-layout-bound: under pp_deg>1 it is a
        per-STAGE list whose grouping follows the saving config, so when
        the pipeline layout differs the load refuses it (reload with
        opt_state discarded, or keep the same pp layout)."""
        import pickle
        with open(path, "rb") as f:
            state = pickle.load(f)
        saved_layers = len(state["params"])
        if saved_layers != len(self.specs):
            raise ValueError(
                f"checkpoint has {saved_layers} layers, model has "
                f"{len(self.specs)}")
        expect = self._expected_param_shapes()
        for i, (p, exp) in enumerate(zip(state["params"], expect)):
            for n, v in p.items():
                if n not in exp or tuple(np.shape(v)) != tuple(exp[n].shape):
                    raise ValueError(
                        f"checkpoint layer {i} param {n!r} has shape "
                        f"{np.shape(v)}, model expects "
                        f"{tuple(exp[n].shape) if n in exp else 'absent'} "
                        "— wrong model for this checkpoint")
        shard_specs = []
        params = []
        for spec, sh, p in zip(self.specs, self.shardings,
                               state["params"]):
            pspecs = spec.param_specs(sh)
            shards = {n: NamedSharding(sh.mesh, pspecs[n]) for n in p}
            shard_specs.append(shards)
            params.append({n: jax.device_put(jnp.asarray(v), shards[n])
                           for n, v in p.items()})
        opt_state = state["opt_state"]
        if opt_state is not None:
            saved_cfg = state.get("config", {})
            cur_cfg = self.config.to_json()
            same_pp = (saved_cfg.get("pp_deg") == cur_cfg["pp_deg"] and
                       saved_cfg.get("pp_division")
                       == cur_cfg["pp_division"])
            if not same_pp:
                raise ValueError(
                    "checkpoint optimizer state was written under pipeline "
                    f"layout pp_deg={saved_cfg.get('pp_deg')}, this model "
                    f"uses pp_deg={self.config.pp_deg}; per-stage state "
                    "does not remap — load params only (save with "
                    "opt_state=None) or keep the pipeline layout")
            if self.pp == 1:
                # place optimizer subtrees that mirror the params tree
                # (adam mu/nu etc.) onto the params' shardings, so FSDP's
                # zero-3 memory sharding holds for the moments too
                param_td = jax.tree_util.tree_structure(params)
                flat_shards = [shard_specs[i][n]
                               for i in range(len(params))
                               for n in sorted(params[i])]

                def place(sub):
                    try:
                        leaves, td = jax.tree_util.tree_flatten(sub)
                    except Exception:
                        return None
                    if td != param_td:
                        return None
                    return jax.tree_util.tree_unflatten(
                        td, [jax.device_put(jnp.asarray(l), s)
                             for l, s in zip(leaves, flat_shards)])

                def walk(node):
                    placed = place(node)
                    if placed is not None:
                        return placed
                    if isinstance(node, (list, tuple)):
                        out = [walk(c) for c in node]
                        return (type(node)(*out)
                                if hasattr(node, "_fields")
                                else type(node)(out))
                    return jax.tree_util.tree_map(jnp.asarray, node)

                opt_state = walk(opt_state)
            else:
                # same pipeline layout: per-stage programs re-place the
                # state onto their submeshes on the first update
                opt_state = jax.tree_util.tree_map(jnp.asarray, opt_state)
        return params, opt_state

    def _apply_range(self, idxs, stage_params, x):
        for j, i in enumerate(idxs):
            spec, sh = self.specs[i], self.shardings[i]
            p = stage_params[j]
            if getattr(spec, "tied", False):
                # weight-tied LM head: borrow the embedding table from
                # layer 0 (make_lm_hybrid_model guarantees it shares this
                # stage); the vjp accumulates both uses into one grad
                if 0 not in idxs or "wte" not in stage_params[idxs.index(0)]:
                    raise ValueError(
                        "tied LM head requires a vocab-embedding spec as "
                        "layer 0 on the SAME pipeline stage (pp_deg == 1; "
                        "build via make_lm_hybrid_model)")
                p = dict(p)
                p["_tied_wte"] = stage_params[idxs.index(0)]["wte"]
            body = lambda p_, x_, spec_=spec, sh_=sh: spec_.apply(p_, x_, sh_)
            if sh.ckpt:
                body = jax.checkpoint(body)
            x = body(p, x)
        return x

    def apply(self, params, x):
        if self.pp == 1:
            return self._apply_range(self.stage_layers[0], params, x)
        for s, idxs in enumerate(self.stage_layers):
            x = self._to_stage(x, s)
            x = self._apply_range(idxs, [params[i] for i in idxs], x)
        return x

    def loss(self, params, x, tgt):
        return self.loss_fn(self.apply(params, x), tgt)

    # -- pipelined execution (pp_deg > 1) ---------------------------------
    def _to_stage(self, x, s):
        sh = self.shardings[self.stage_layers[s][0]]
        return jax.device_put(x, NamedSharding(
            self.stage_meshes[s], sh.act_spec(x.ndim)))

    def _build_stage_programs(self):
        self._stage_fwd, self._stage_bwd, self._stage_last_bwd = [], [], []
        for s, idxs in enumerate(self.stage_layers):
            last = s == self.pp - 1

            def fwd(sp, x, idxs=idxs):
                return self._apply_range(idxs, sp, x)

            self._stage_fwd.append(jax.jit(fwd))

            def bwd(sp, x, ct, idxs=idxs):
                _, vjp_fn = jax.vjp(
                    lambda p_, x_: self._apply_range(idxs, p_, x_), sp, x)
                return vjp_fn(ct)

            self._stage_bwd.append(jax.jit(bwd))
            if last:
                def last_bwd(sp, x, tgt, scale, idxs=idxs):
                    def f(p_, x_):
                        return self.loss_fn(
                            self._apply_range(idxs, p_, x_), tgt)
                    loss, vjp_fn = jax.vjp(f, sp, x)
                    gp, gx = vjp_fn(scale.astype(loss.dtype))
                    return loss, gp, gx

                self._stage_last_bwd = jax.jit(last_bwd)

    def grads(self, params, x, tgt):
        """(loss, grads) with micro-batch accumulation over config.chunks;
        pipelined across stages when pp_deg > 1."""
        chunks = max(1, self.config.chunks)
        if self.pp == 1:
            return self._grads_unstaged(params, x, tgt, chunks)
        return self._grads_pipelined(params, x, tgt, chunks)

    def _grads_unstaged(self, params, x, tgt, chunks):
        if chunks == 1:
            return jax.value_and_grad(self.loss)(params, x, tgt)
        b = x.shape[0]
        assert b % chunks == 0, f"batch {b} not divisible by chunks {chunks}"
        xs = x.reshape(chunks, b // chunks, *x.shape[1:])
        ts = tgt.reshape(chunks, b // chunks, *tgt.shape[1:])
        zero = jax.tree_util.tree_map(jnp.zeros_like, params)

        def micro(acc, xt):
            l, g = jax.value_and_grad(self.loss)(params, *xt)
            acc_l, acc_g = acc
            return (acc_l + l,
                    jax.tree_util.tree_map(jnp.add, acc_g, g)), None

        (tl, tg), _ = lax.scan(micro, (0.0, zero), (xs, ts))
        inv = 1.0 / chunks
        return tl * inv, jax.tree_util.tree_map(lambda g: g * inv, tg)

    def _grads_pipelined(self, params, x, tgt, chunks):
        """GPipe or pipedream-flush (1F1B) over ``chunks`` micro-batches,
        selected by ``config.pipeline_type`` (the searched schedule,
        reference pipeline/pipeline.py:133 pipedream_flush_forward_backward
        vs :343 gpipe_forward_backward).

        Both stash only boundary activations (stage inputs; intra-stage
        activations recompute in the vjp backward).  GPipe keeps all
        ``chunks`` of them live through the flush; pipedream-flush issues
        each chunk's full backward chain as soon as its forward leaves the
        last stage and frees that chunk's stash — at most ``pp`` chunks
        live, which is exactly what search.py's memory model
        (min(chunks, pp) live micro-batches) scores."""
        if self._stage_fwd is None:
            self._build_stage_programs()
        b = x.shape[0]
        assert b % chunks == 0, f"batch {b} not divisible by chunks {chunks}"
        schedule = self.config.pipeline_type
        mb = b // chunks
        xs = [x[m * mb:(m + 1) * mb] for m in range(chunks)]
        ts = [tgt[m * mb:(m + 1) * mb] for m in range(chunks)]
        sparams = [[params[i] for i in idxs] for idxs in self.stage_layers]

        stage_in = [[None] * self.pp for _ in range(chunks)]
        # d(mean over chunks)/dloss seed; losses stay device-resident —
        # a float() per chunk would sync the host mid-pipeline.  f32 here
        # (x may be int tokens for the LM tier); last_bwd casts it to the
        # loss dtype before seeding the vjp
        scale = jnp.asarray(1.0 / chunks, jnp.float32)
        grad_acc = [None] * self.pp
        losses = []
        self._live_chunks_hwm = 0

        def note_live():
            live = sum(any(a is not None for a in sl) for sl in stage_in)
            self._live_chunks_hwm = max(self._live_chunks_hwm, live)

        def backward(m):
            tgt_m = self._to_stage(ts[m], self.pp - 1) \
                if ts[m].ndim else ts[m]
            loss_m, gp, ct = self._stage_last_bwd(
                sparams[-1], stage_in[m][self.pp - 1], tgt_m, scale)
            losses.append(loss_m)
            grad_acc[-1] = gp if grad_acc[-1] is None else \
                jax.tree_util.tree_map(jnp.add, grad_acc[-1], gp)
            for s in reversed(range(self.pp - 1)):
                ct = self._to_stage(ct, s)
                gp, ct = self._stage_bwd[s](sparams[s], stage_in[m][s], ct)
                grad_acc[s] = gp if grad_acc[s] is None else \
                    jax.tree_util.tree_map(jnp.add, grad_acc[s], gp)
            stage_in[m] = [None] * self.pp   # chunk m's stash is consumed

        # forward wavefront: (chunk+stage) diagonal issue order; JAX async
        # dispatch overlaps stage programs across their device sets
        order = sorted(((m, s) for m in range(chunks)
                        for s in range(self.pp)),
                       key=lambda t: (t[0] + t[1], t[1]))
        for m, s in order:
            src = xs[m] if s == 0 else stage_in[m][s]
            xin = self._to_stage(src, s)   # ICI transfer between stages
            stage_in[m][s] = xin
            if s < self.pp - 1:
                stage_in[m][s + 1] = self._stage_fwd[s](sparams[s], xin)
                note_live()
            elif schedule == "pipedream_flush":
                backward(m)
                note_live()
            else:
                note_live()
        if schedule == "gpipe":
            for m in reversed(range(chunks)):
                backward(m)

        loss = losses[0]
        for l in losses[1:]:
            loss = loss + l
        grads = [None] * self.config.n_layers
        for s, idxs in enumerate(self.stage_layers):
            for j, i in enumerate(idxs):
                grads[i] = grad_acc[s][j]
        return loss * scale.astype(loss.dtype), grads

    def make_train_step(self, optimizer=None, lr=1e-3):
        """Returns (step_fn, opt_state_init).

        pp_deg==1: step_fn is one jitted program.  pp_deg>1: step_fn is a
        host-orchestrated pipeline step (per-stage programs overlap via
        async dispatch); updates apply per stage on its submesh."""
        if optimizer is None:
            def apply_updates(params, opt_state, g):
                new = jax.tree_util.tree_map(lambda p, gg: p - lr * gg,
                                             params, g)
                return new, opt_state
            init = lambda params: ()
        else:
            import optax

            def apply_updates(params, opt_state, g):
                updates, opt_state = optimizer.update(g, opt_state, params)
                return optax.apply_updates(params, updates), opt_state
            init = optimizer.init

        if self.pp == 1:
            def step(params, opt_state, x, tgt):
                loss, g = self.grads(params, x, tgt)
                params, opt_state = apply_updates(params, opt_state, g)
                return params, opt_state, loss
            return jax.jit(step, donate_argnums=(0, 1)), init

        # pipelined: per-stage jitted update keeps each stage's params on
        # its own submesh (grads already live there); donate params AND
        # slots so old/new optimizer state never coexist in HBM
        stage_update = jax.jit(apply_updates, donate_argnums=(0, 1))

        def step(params, opt_state, x, tgt):
            loss, g = self.grads(params, x, tgt)
            new_params = list(params)
            new_opt = list(opt_state) if isinstance(opt_state, list) \
                else [opt_state] * self.pp
            for s, idxs in enumerate(self.stage_layers):
                sp = [params[i] for i in idxs]
                sg = [g[i] for i in idxs]
                np_, no_ = stage_update(sp, new_opt[s], sg)
                for j, i in enumerate(idxs):
                    new_params[i] = np_[j]
                new_opt[s] = no_
            return new_params, new_opt, loss

        def init_pp(params):
            return [init([params[i] for i in idxs])
                    for idxs in self.stage_layers]

        return step, init_pp
