"""Auto-parallel planner: measured telemetry in, executed plans out.

ROADMAP direction 1 (the Galvatron papers' thesis — see PAPER.md):
parallel layout is a DERIVED artifact of a cost-model search over
measured evidence, not a hand annotation.  The pieces this package
glues together already exist:

- ``telemetry/profiling.py`` measures per-layer flops/bytes attribution
  and observed step windows; ``galvatron/search.py`` measures per-layer
  compute + activation memory (XLA temp-bytes slope) and ICI bandwidth.
- ``galvatron.GalvatronSearch`` turns per-layer ``LayerProfile``s into
  a winning ``HybridParallelConfig`` (native DP core).
- ``galvatron/runtime.py`` executes a config (mesh + shardings +
  pipelined train step); ``serving/sharding.py`` + ``EngineFleet``
  execute a serving shape (tp sub-meshes × replicas × KV page pools).

The planner closes the loop, end to end:

- :mod:`.calibrate` — measured ``LayerProfile``s (live evidence, not
  hand numbers), serialized as the versioned galvatron profile artifact.
- :mod:`.plan` — run the search over a calibrated profile and lower the
  winner into the concrete things the runtime consumes: a mesh +
  per-layer shardings, a ``parallel.strategies`` annotation, a serving
  tp size, and a JSON plan artifact carrying the predicted iteration
  time + per-stage memory (``tests/test_planner.py`` executes an
  emitted plan end to end).
- :mod:`.fleet_plan` — search tp_size × replica_count × page-pool
  geometry under a fleet HBM budget and a declared ``SLO`` from
  measured serving costs; ``FleetController.replan()`` adopts the
  result live via migrate-then-drain.
"""

from .calibrate import (calibrate_and_save, calibrate_from_profiler,
                        calibrate_hp_layers)
from .plan import (PlanError, emit_plan, emit_plan_from_profile,
                   load_plan, plan_config, plan_dumps, plan_mesh,
                   plan_shardings, plan_strategy, predict, save_plan,
                   serving_tp)
from .fleet_plan import (FleetPlanError, fleet_plan_dumps,
                         fleet_plan_from_controller, load_fleet_plan,
                         plan_fleet, save_fleet_plan)

__all__ = [
    "calibrate_and_save", "calibrate_from_profiler", "calibrate_hp_layers",
    "PlanError", "emit_plan", "emit_plan_from_profile", "load_plan",
    "plan_config", "plan_dumps",
    "plan_mesh", "plan_shardings", "plan_strategy", "predict",
    "save_plan", "serving_tp",
    "FleetPlanError", "fleet_plan_dumps", "fleet_plan_from_controller",
    "load_fleet_plan", "plan_fleet", "save_fleet_plan",
]
