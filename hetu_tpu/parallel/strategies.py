"""Parallelization strategies: annotate a graph with shardings.

Reference: /root/reference/python/hetu/distributed_strategies/simple.py —
`DataParallel` (:6), `ModelParallel4CNN` (:46), `ModelParallel4LM` (:113),
`OneWeirdTrick4CNN` (:119), `MegatronLM` (:174); each assigns raw_ctx +
NodeStatus to every node.  Here a Strategy assigns `dist_state` (mesh-axis
layouts) to placeholders/variables; the executor turns them into jit
in_shardings and GSPMD propagates through the program — replacing the
reference's fixed-point NodeStatus inference (context.py:1008-1468) with the
compiler's propagation pass.
"""

from __future__ import annotations

import re

from ..graph.node import PlaceholderOp, VariableOp, find_topo_sort
from .mesh import DistState, make_mesh


class Strategy:
    """Base (reference distributed_strategies/base.py:13)."""

    mesh = None

    def annotate(self, eval_nodes):
        raise NotImplementedError

    # reference API name
    def set_raw_ctxs_n_states(self, eval_nodes):
        return self.annotate(eval_nodes)

    # -- config persistence (reference Strategy.save_json base.py:183) ----
    def config(self):
        """JSON-able constructor config (mesh stored as axis sizes).

        Raises for strategies carrying non-scalar state (e.g. a searched
        per-node assignment) — those need their own serializers rather
        than silent data loss.
        """
        out = {"strategy": type(self).__name__}
        for k, v in vars(self).items():
            if k == "mesh":
                out["mesh_axes"] = (dict(v.shape) if v is not None
                                    else None)
            elif isinstance(v, (int, float, str, bool, type(None))):
                out[k] = v
            else:
                raise TypeError(
                    f"{type(self).__name__}.{k} ({type(v).__name__}) is "
                    f"not JSON-persistable; this strategy needs a custom "
                    f"serializer")
        return out

    def save_json(self, path):
        import json
        with open(path, "w") as f:
            json.dump(self.config(), f, indent=2)

    @staticmethod
    def load_json(path):
        """Rebuild a strategy from a saved config (simple strategies)."""
        import json
        from . import strategies as S
        with open(path) as f:
            cfg = json.load(f)
        name = cfg.pop("strategy")
        cls = getattr(S, name, None)
        if cls is None or not (isinstance(cls, type)
                               and issubclass(cls, Strategy)):
            raise ValueError(f"{name!r} is not a Strategy in "
                             f"parallel.strategies")
        mesh_axes = cfg.pop("mesh_axes", None)
        if mesh_axes:
            cfg["mesh"] = make_mesh(mesh_axes)
        return cls(**cfg)


class DataParallel(Strategy):
    """Batch-dim sharding over a 'dp' axis (reference simple.py:6).

    Gradient all-reduce is implicit: batch-sharded loss + replicated params
    make XLA insert the reduction the reference expressed as
    AllReduceCommunicateOp on every grad edge (executor.py:278-283).
    """

    def __init__(self, mesh=None, ndev=None, axis="dp",
                 shard_batch_dim=0):
        self.mesh = mesh if mesh is not None else make_mesh(
            {axis: ndev or _ndev()})
        self.axis = axis
        self.shard_batch_dim = shard_batch_dim

    def annotate(self, eval_nodes):
        for n in find_topo_sort(eval_nodes):
            if isinstance(n, PlaceholderOp):
                n.dist_state = DistState({self.shard_batch_dim: self.axis})
        return self.mesh


class FSDP(Strategy):
    """ZeRO-3-style parameter sharding along the dp axis (Galvatron's
    dp_type='fsdp', tools/Hetu-Galvatron/galvatron/core/parallel.py:166).
    Params/optimizer state shard on dim 0; XLA all-gathers at use and
    reduce-scatters grads."""

    def __init__(self, mesh=None, ndev=None, axis="dp", min_size=1024):
        self.mesh = mesh if mesh is not None else make_mesh(
            {axis: ndev or _ndev()})
        self.axis = axis
        self.min_size = min_size

    def annotate(self, eval_nodes):
        import numpy as np
        size = self.mesh.shape[self.axis]
        for n in find_topo_sort(eval_nodes):
            if isinstance(n, PlaceholderOp):
                n.dist_state = DistState({0: self.axis})
            elif isinstance(n, VariableOp) and n.trainable:
                if (int(np.prod(n.shape)) >= self.min_size
                        and n.shape and n.shape[0] % size == 0):
                    n.dist_state = DistState({0: self.axis})
        return self.mesh


class ExpertParallel(Strategy):
    """One mesh axis that is data-parallel for everything but the experts
    and the vocabulary: the placeholders' batch on the axis; the ``[E, H,
    F]`` expert stacks' dim 0 on it (``MoELayer(ep_axis=)``, which says so
    itself: a device holds ``E / n`` experts with their optimizer state, and
    the dropless op exchanges tokens under ``shard_map``:
    ``ops/moe.py dropless_moe_over_axis``); the embedding table's and an
    untied head's vocabulary dim on it where it divides (FSDP's way:
    a device keeps ``V / n`` rows of the f32 master and of the moments, the
    compute type's copy is gathered where it is used and the gradient
    reduce-scattered, so the loss kernel sees whole rows of logits for the
    device's own tokens); everything else replicated, its gradients
    all-reduced by GSPMD as ``DataParallel``'s are.

    The axis goes by ``dp`` because that is the name the per-shard kernel
    plans split a batch over (``ops/pallas/dispatch.py shard_axes``)."""

    #: embedding tables (``layers/common.py Embedding``) and a causal LM's
    #: untied head (``models/llama.py``): name -> the vocabulary's dim
    VOCAB = ((re.compile(r"_table$"), 0), (re.compile(r"_lm_head_weight$"), 1))

    def __init__(self, mesh=None, ndev=None, axis="dp"):
        self.mesh = mesh if mesh is not None else make_mesh(
            {axis: ndev or _ndev()})
        self.axis = axis

    def annotate(self, eval_nodes):
        size = self.mesh.shape[self.axis]
        experts = 0
        for n in find_topo_sort(eval_nodes):
            if isinstance(n, PlaceholderOp):
                n.dist_state = DistState({0: self.axis})
            elif isinstance(n, VariableOp):
                if n.dist_state is not None:
                    experts += n.dist_state.splits.get(0) == self.axis
                    continue
                for pattern, dim in self.VOCAB:
                    if pattern.search(n.name) and n.shape[dim] % size == 0:
                        n.dist_state = DistState({dim: self.axis})
        if size > 1 and not experts:
            import warnings
            warnings.warn(
                f"ExpertParallel.annotate: no variable has its dim 0 on "
                f"{self.axis!r}; build the expert layers with "
                f"MoELayer(ep_axis={self.axis!r})", stacklevel=2)
        return self.mesh


class MegatronLM(Strategy):
    """2D dp×tp for transformer stacks (reference simple.py:174).

    Column-parallel: QKV projections and FFN up-projection (output dim on
    'tp'); row-parallel: attention output and FFN down-projection (input dim
    on 'tp').  Name patterns follow the layer library's naming contract
    (layers/attention.py, layers/transformer.py).  GSPMD inserts the psum
    pairs the reference placed as AllReduce after row-parallel matmuls.
    """

    COL_W = re.compile(r"(_q|_k|_v|_in|_gate|_up)_weight$")
    COL_B = re.compile(r"(_q|_k|_v|_in|_gate|_up)_bias$")
    ROW_W = re.compile(r"_out_weight$")
    # embedding tables (layers/common.py Embedding -> '<name>_table'):
    # vocab-parallel dim-0 sharding; a table also used as a tied LM head
    # (h @ table^T) then yields vocab-sharded logits, and the sparse CE's
    # reductions stay sharded under GSPMD.  Reference: megatron
    # VocabParallelEmbedding + sharded LM head
    # (core/tensor_parallel/transformer.py).
    EMB_W = re.compile(r"_table$")

    def __init__(self, mesh=None, dp=1, tp=None, dp_axis="dp",
                 tp_axis="tp", shard_embeddings=True):
        if mesh is None:
            tp = tp or (_ndev() // dp)
            mesh = make_mesh({dp_axis: dp, tp_axis: tp})
        self.mesh = mesh
        self.dp_axis, self.tp_axis = dp_axis, tp_axis
        self.shard_embeddings = shard_embeddings

    def annotate(self, eval_nodes):
        tp_size = self.mesh.shape[self.tp_axis]
        matched = 0
        skipped = []
        for n in find_topo_sort(eval_nodes):
            if isinstance(n, PlaceholderOp):
                n.dist_state = DistState({0: self.dp_axis})
            elif isinstance(n, VariableOp):
                if self.COL_W.search(n.name) and n.shape[1] % tp_size == 0:
                    n.dist_state = DistState({1: self.tp_axis})
                elif self.COL_B.search(n.name) and n.shape[0] % tp_size == 0:
                    n.dist_state = DistState({0: self.tp_axis})
                elif self.ROW_W.search(n.name) and n.shape[0] % tp_size == 0:
                    n.dist_state = DistState({0: self.tp_axis})
                elif (self.shard_embeddings and self.EMB_W.search(n.name)
                      and n.shape[0] % tp_size == 0):
                    n.dist_state = DistState({0: self.tp_axis})
                else:
                    if (self.COL_W.search(n.name)
                            or self.COL_B.search(n.name)
                            or self.ROW_W.search(n.name)
                            or (self.shard_embeddings
                                and self.EMB_W.search(n.name))):
                        skipped.append(n.name)  # matched name, bad divisor
                    continue
                matched += 1
        if tp_size > 1 and matched == 0:
            # the naming contract silently matching NOTHING means every
            # parameter stays replicated — plain DP at tp memory cost
            import warnings
            warnings.warn(
                "MegatronLM.annotate: no variable matched the naming "
                "contract (_q/_k/_v/_in/_out weights, *_table embeddings)"
                + (f"; name-matched but not divisible by tp={tp_size}: "
                   f"{skipped}" if skipped else "")
                + " — all parameters remain replicated. Check layer "
                "names or pass shard rules explicitly.",
                stacklevel=2)
        self.matched_variables = matched
        return self.mesh


class ModelParallel4CNN(Strategy):
    """TP for the classifier head of CNNs (reference simple.py:46/119 —
    'one weird trick': conv layers data-parallel, FC layers model-parallel)."""

    def __init__(self, mesh=None, dp=1, tp=None):
        if mesh is None:
            tp = tp or (_ndev() // dp)
            mesh = make_mesh({"dp": dp, "tp": tp})
        self.mesh = mesh

    def annotate(self, eval_nodes):
        tp_size = self.mesh.shape["tp"]
        for n in find_topo_sort(eval_nodes):
            if isinstance(n, PlaceholderOp):
                n.dist_state = DistState({0: "dp"})
            elif isinstance(n, VariableOp):
                if (n.name.endswith("_fc_weight")
                        and n.shape[1] % tp_size == 0):
                    n.dist_state = DistState({1: "tp"})
        return self.mesh


class PlannedParallel(Strategy):
    """A planner-emitted plan artifact as a graph annotation.

    The auto-parallel planner (``hetu_tpu/planner``) emits a searched
    ``hetu_train_plan`` dict; this strategy lowers it onto a flat node
    graph by delegating to the simple strategy the plan's per-layer
    assignment implies: searched tp > 1 -> :class:`MegatronLM` on a
    dp×tp mesh, fsdp-majority dp_types -> :class:`FSDP`, else
    :class:`DataParallel`.  (Pipeline stages are a runtime-level
    concept — ``galvatron/runtime.HybridParallelModel`` executes them —
    so a node-graph annotation uses the plan's intra-stage layout.)

    ``config()``/``save_json`` persist the full plan dict, so a saved
    strategy round-trips through :meth:`Strategy.load_json`."""

    def __init__(self, plan, mesh_shape=None, devices=None):
        cfg = plan["config"] if "config" in plan else plan
        from ..galvatron.config import HybridParallelConfig
        hp = (HybridParallelConfig.from_json(cfg)
              if isinstance(cfg, dict) else cfg)
        self.plan = dict(plan)
        self.mesh_shape = dict(mesh_shape) if mesh_shape else None
        # devices: the concrete device pool to build the mesh over —
        # the elastic trainer's surviving set after a chip loss.
        # Default (None) is jax.devices(), the full fleet.
        self._devices = list(devices) if devices is not None else None
        tp = max(int(t) for t in hp.tp_sizes)
        world = int(hp.world or hp.pp_deg * tp)
        dp = max(1, world // (int(hp.pp_deg) * tp))
        fsdp = sum(int(t) for t in hp.dp_types) * 2 > len(hp.dp_types)
        self.tp, self.dp = tp, dp
        mesh = (make_mesh(self.mesh_shape, devices=self._devices)
                if self.mesh_shape else None)
        if tp > 1:
            self._inner = MegatronLM(
                mesh=mesh if mesh is not None
                else make_mesh({"dp": dp, "tp": tp},
                               devices=self._devices))
        elif fsdp and dp > 1:
            self._inner = FSDP(
                mesh=mesh if mesh is not None
                else make_mesh({"dp": dp}, devices=self._devices))
        else:
            self._inner = DataParallel(
                mesh=mesh if mesh is not None
                else make_mesh({"dp": dp}, devices=self._devices))
        self.lowered = type(self._inner).__name__

    def annotate(self, eval_nodes):
        self.mesh = self._inner.annotate(eval_nodes)
        return self.mesh

    def config(self):
        return {"strategy": type(self).__name__,
                "plan": self.plan,
                "mesh_shape": self.mesh_shape}


def _ndev():
    import jax
    return len(jax.devices())
