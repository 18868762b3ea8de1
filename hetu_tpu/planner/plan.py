"""Training plan emission: calibrated profile -> executed parallel plan.

:func:`emit_plan` runs :class:`~hetu_tpu.galvatron.GalvatronSearch`
over calibrated :class:`LayerProfile`s and packages the winner as a
versioned JSON **plan artifact** carrying everything the runtime and
the perf gate need:

- the winning ``HybridParallelConfig`` (the executable part),
- the PREDICTED iteration time and per-stage memory — recomputed from
  the cost model over the winning assignment, so the artifact's number
  is exactly the quantity a measured run is compared with,
- provenance: which DP core ran, the profile's calibration meta, the
  ICI bandwidth the comm terms were priced with.

Plan JSON is canonical (sorted keys, fixed rounding): the same profile
artifact always emits byte-identical plan bytes — plans are
reproducible build outputs, not snowflakes.

Lowering helpers turn the artifact into each consumer's native shape:
:func:`plan_mesh` / :func:`plan_shardings` for the sharded executor
(``galvatron/runtime.py``), :func:`serving_tp` for
``serving/sharding.py`` meshes, :func:`plan_strategy` for
``parallel/strategies.py`` annotation of a node graph.
"""

from __future__ import annotations

import json
import os

from ..galvatron.config import HybridParallelConfig
from ..galvatron.search import (CostModel, GalvatronSearch, Strategy,
                                load_profile_doc, LayerProfile)

PLAN_SCHEMA = "hetu_train_plan"
PLAN_VERSION = 1


class PlanError(ValueError):
    """No feasible plan, or a plan artifact failed validation."""


def predict(cfg, layers, ici_gbps=100.0):
    """Predicted per-step cost of a CONCRETE config over calibrated
    layers — the same arithmetic the search's DP minimized, recomputed
    over ``cfg``'s per-layer assignment so any config (searched or
    hand-picked baseline) gets a comparable prediction.

    Returns ``{"iter_ms", "stage_ms", "stage_mem_bytes",
    "max_stage_mem_bytes"}``; iteration time is ``chunks x slowest
    stage + fill/drain`` (the flush-schedule model)."""
    pp = int(cfg.pp_deg)
    world = int(cfg.world or pp)
    per_stage = world // pp
    chunks = max(1, int(cfg.chunks or 1))
    global_bsz = int(cfg.global_bsz or chunks)
    micro_bsz = global_bsz // chunks
    if micro_bsz < 1:
        raise PlanError(
            f"global_bsz={global_bsz} not divisible into chunks={chunks}")
    model = CostModel(layers, per_stage, micro_bsz, chunks=chunks,
                      ici_gbps=float(ici_gbps))
    n_layers = len(layers)
    division = list(cfg.pp_division) if cfg.pp_division else None
    if division is None:
        avg = n_layers // pp
        division = [avg] * (pp - 1) + [n_layers - avg * (pp - 1)]
    ckpt = cfg.checkpoint_flags or [0] * n_layers
    sp = cfg.sp_flags or [0] * n_layers
    sts = [Strategy(int(cfg.tp_sizes[i]), int(cfg.dp_types[i]),
                    int(ckpt[i]), int(sp[i])) for i in range(n_layers)]
    n_live = min(chunks, pp) if pp > 1 else 1
    stage_ms, stage_mem = [], []
    lo = 0
    for stage_len in division:
        hi = lo + stage_len
        ms = mem = 0.0
        for i in range(lo, hi):
            ms += model.intra_ms(i, sts[i])
            if i > lo:
                ms += model.inter_ms(i, sts[i - 1], sts[i])
            mem += model.mem_bytes(i, sts[i], n_live)
        stage_ms.append(ms)
        stage_mem.append(mem)
        lo = hi
    slowest = max(stage_ms)
    total = chunks * slowest + (sum(stage_ms) - slowest)
    return {"iter_ms": round(total, 6),
            "stage_ms": [round(s, 6) for s in stage_ms],
            "stage_mem_bytes": [int(round(m)) for m in stage_mem],
            "max_stage_mem_bytes": int(round(max(stage_mem)))}


def _capacity_constraint(world, devices, mesh_shape, pp_candidates):
    """Resolve the ``devices=`` / ``mesh_shape=`` capacity constraint
    (elastic re-planning: the search answers "best plan on what's
    LEFT", not on the original fleet).  Returns ``(world, n_dev,
    mesh_shape, pp_candidates)``."""
    n_dev = None
    if devices is not None:
        n_dev = int(devices) if isinstance(devices, int) else len(devices)
        world = n_dev if world is None else min(int(world), n_dev)
    ms = None
    if mesh_shape is not None:
        ms = {str(k): int(v) for k, v in dict(mesh_shape).items()}
        forced = 1
        for v in ms.values():
            forced *= v
        if n_dev is not None and forced > n_dev:
            raise PlanError(
                f"mesh_shape {ms} needs {forced} devices, "
                f"constraint allows {n_dev}")
        world = forced
        if pp_candidates is None:
            pp_candidates = (ms.get("pp", 1),)
    if world is None:
        raise PlanError(
            "plan emission needs world=, devices=, or mesh_shape=")
    return int(world), n_dev, ms, pp_candidates


def emit_plan(layers, world=None, mem_budget_bytes=None, ici_gbps=100.0,
              micro_bsz=1, global_bsz=None, mem_units=64,
              pp_candidates=None, chunks_candidates=(1, 2, 4, 8),
              use_native=True, profile_meta=None, devices=None,
              mesh_shape=None):
    """Search the calibrated profile and emit the plan artifact dict.

    Raises :class:`PlanError` when no config fits the per-device
    memory budget (the search's infeasible verdict is an answer, not a
    crash with a half-written artifact).

    ``devices`` (a device list or count) clamps the searched world to
    the surviving capacity; ``mesh_shape`` ({axis: size}) pins it to a
    concrete mesh (and its ``pp`` size, unless ``pp_candidates`` says
    otherwise) — the elastic trainer's re-plan-after-chip-loss hook."""
    world, n_dev, mesh_shape, pp_candidates = _capacity_constraint(
        world, devices, mesh_shape, pp_candidates)
    if mem_budget_bytes is None:
        raise PlanError("emit_plan needs mem_budget_bytes")
    search = GalvatronSearch(world, mem_budget_bytes,
                             micro_bsz=micro_bsz, ici_gbps=ici_gbps,
                             mem_units=mem_units, use_native=use_native,
                             pp_candidates=pp_candidates,
                             chunks_candidates=chunks_candidates)
    cfg = search.search(layers, global_bsz=global_bsz)
    if cfg is None:
        raise PlanError(
            f"no feasible parallel config: world={world}, "
            f"mem_budget={mem_budget_bytes} bytes, "
            f"{len(layers)} layers")
    pred = predict(cfg, layers, ici_gbps=ici_gbps)
    plan = {"schema": PLAN_SCHEMA, "version": PLAN_VERSION,
            "world": int(world),
            "mem_budget_bytes": int(mem_budget_bytes),
            "mem_units": int(mem_units),
            "ici_gbps": round(float(ici_gbps), 6),
            "core": search.core_used,
            "n_layers": len(layers),
            "config": cfg.to_json(),
            "predicted": pred}
    if n_dev is not None:
        plan["devices"] = n_dev
    if mesh_shape is not None:
        plan["mesh_shape"] = mesh_shape
    if profile_meta:
        plan["profile_meta"] = dict(profile_meta)
    return plan


def emit_fallback_plan(world=None, n_layers=1, global_bsz=None,
                       devices=None, mesh_shape=None):
    """Degraded hand plan for when no calibrated profile exists (the
    elastic trainer must still re-plan after losing a chip it never
    profiled for): pure data parallelism over the surviving devices
    (tp=1, pp=1) — the one layout that is always executable.  Same
    artifact schema as :func:`emit_plan`; ``core`` says
    ``"hand_fallback"`` and ``predicted.iter_ms`` is ``None`` (nothing
    was measured, so nothing is predicted and the perf gate has
    nothing to hold it to)."""
    world, n_dev, mesh_shape, _pp = _capacity_constraint(
        world, devices, mesh_shape, None)
    n = max(1, int(n_layers))
    cfg = HybridParallelConfig(pp_deg=1, tp_sizes=[1] * n,
                               dp_types=[0] * n, world=world,
                               chunks=1, global_bsz=global_bsz)
    plan = {"schema": PLAN_SCHEMA, "version": PLAN_VERSION,
            "world": world, "core": "hand_fallback", "n_layers": n,
            "config": cfg.to_json(),
            "predicted": {"iter_ms": None}}
    if n_dev is not None:
        plan["devices"] = n_dev
    if mesh_shape is not None:
        plan["mesh_shape"] = mesh_shape
    return plan


def emit_plan_from_profile(path, world, mem_budget_bytes, **kw):
    """Emit a plan straight from a saved profile artifact (validated
    load; the artifact's measured ICI bandwidth prices the comm
    terms)."""
    doc = load_profile_doc(path)
    layers = [LayerProfile.from_json(l) for l in doc["layers"]]
    kw.setdefault("ici_gbps", doc.get("ici_gbps", 100.0))
    kw.setdefault("profile_meta", doc.get("meta"))
    return emit_plan(layers, world, mem_budget_bytes, **kw)


def plan_dumps(plan):
    """Canonical plan bytes: sorted keys, fixed separators, trailing
    newline.  Same profile artifact -> byte-identical plan JSON."""
    return json.dumps(plan, indent=2, sort_keys=True) + "\n"


def save_plan(path, plan):
    """Atomic plan write (tmp + ``os.replace``, the artifact
    convention)."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        fh.write(plan_dumps(plan))
    os.replace(tmp, path)
    return path


def load_plan(path):
    """Validated plan artifact dict, or :class:`PlanError`."""
    try:
        with open(path) as fh:
            d = json.load(fh)
    except (OSError, ValueError) as e:
        raise PlanError(f"unreadable plan artifact {path}: {e}")
    if not isinstance(d, dict) or d.get("schema") != PLAN_SCHEMA:
        raise PlanError(
            f"plan artifact {path}: schema "
            f"{d.get('schema') if isinstance(d, dict) else type(d)!r} "
            f"!= {PLAN_SCHEMA!r}")
    if d.get("version") != PLAN_VERSION:
        raise PlanError(f"plan artifact {path}: version "
                        f"{d.get('version')!r} != {PLAN_VERSION}")
    for key in ("config", "predicted", "world"):
        if key not in d:
            raise PlanError(f"plan artifact {path}: missing {key!r}")
    return d


# -- lowering: the consumers' native shapes --------------------------------

def plan_config(plan):
    """The executable :class:`HybridParallelConfig` of a plan dict."""
    return HybridParallelConfig.from_json(plan["config"])


def plan_mesh(plan, devices=None):
    """The plan's device mesh (``("pp", "m0", ...)`` axes) for the
    sharded executor."""
    from ..galvatron.runtime import build_mesh
    return build_mesh(plan_config(plan), devices)


def plan_shardings(plan, devices=None):
    """``(mesh, [LayerShardings ...])`` — per-layer NamedSharding/
    PartitionSpec sources for every layer of the plan, in layer order.
    ``LayerShardings.param_spec``/``act_spec`` feed ``NamedSharding``
    construction for the executor's placed params and activation
    constraints."""
    from ..galvatron.runtime import LayerShardings
    cfg = plan_config(plan)
    mesh = plan_mesh(plan, devices)
    return mesh, [LayerShardings(mesh, cfg, i)
                  for i in range(len(cfg.tp_sizes))]


def serving_tp(plan):
    """The serving tensor-parallel degree a training plan implies: the
    widest per-layer tp the search chose (decode weights sharded on the
    output dim want the same axis count ``serving/sharding.py`` builds
    meshes for)."""
    cfg = plan_config(plan)
    return max(int(t) for t in cfg.tp_sizes)


def plan_strategy(plan, mesh_shape=None):
    """The ``parallel.strategies`` annotation for a node graph, chosen
    from the plan (searched tp > 1 -> Megatron tp sharding, fsdp
    majority -> FSDP, else DataParallel)."""
    from ..parallel.strategies import PlannedParallel
    return PlannedParallel(plan, mesh_shape=mesh_shape)
