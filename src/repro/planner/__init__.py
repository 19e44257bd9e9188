"""Mapping-plan database: amortize exact GOMA solves across models.

The solver in ``core.solver`` proves a globally optimal mapping for one
(GEMM, accelerator) pair; a real model emits hundreds of distinct GEMM
shapes across prefill sequence sweeps and decode steps, and a serving
fleet re-plans the same shapes forever.  This subsystem turns the solver
from a library function into a service-shaped component:

  * ``store``     — content-addressed, versioned on-disk plan store
                    (JSON ``Mapping`` + ``Certificate``, keyed by a stable
                    hash of (Gemm, AcceleratorSpec, solver version,
                    objective, walk restrictions)),
  * ``batch``     — whole-model GEMM extraction + deduplicated parallel
                    batch solving with near-neighbor warm starts,
  * ``manifest``  — the ``ModelMappingManifest`` build artifact,
  * ``cli``       — ``python -m repro.plan`` prebuild/inspect/verify.

Read-through consumers: ``core.tpu_mapping.plan_gemm_tiling`` and
``plan_fused_mlp`` (hence ``kernels.ops.gemm`` / ``fused_mlp``), through
the store a ``serving.Engine`` installs.  See DESIGN.md §Planner.
"""
from .batch import (BatchPlanner, BatchReport, cached_solve,
                    cached_solve_chain, cached_solve_pareto,
                    prewarm_fused_plans, prewarm_tpu_plans,
                    tile_plan_from_store)
from .manifest import ManifestEntry, ModelMappingManifest
from .store import (ChainKey, FusedPlanEntry, ParetoKey, ParetoPlanEntry,
                    PlanEntry, PlanKey, PlanStore, chain_plan_key,
                    pareto_plan_key, plan_key, resolve_default_store)

__all__ = [
    "BatchPlanner", "BatchReport", "ChainKey", "FusedPlanEntry",
    "ManifestEntry", "ModelMappingManifest", "ParetoKey", "ParetoPlanEntry",
    "PlanEntry", "PlanKey", "PlanStore",
    "cached_solve", "cached_solve_chain", "cached_solve_pareto",
    "chain_plan_key", "pareto_plan_key", "plan_key",
    "prewarm_fused_plans", "prewarm_tpu_plans",
    "resolve_default_store", "tile_plan_from_store",
]
