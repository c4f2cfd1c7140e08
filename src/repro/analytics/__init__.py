"""Batched homomorphic analytics: automatic stage planning + vmap execution.

The paper's Table I says *which* decompression stage each analytical
operation can run at; its §V timings say stage choice is where the speedups
live.  This package turns that into an engine:

* :mod:`repro.analytics.planner` — the feasibility matrix as data (derived
  from the declarative op registry in :mod:`repro.core.oplib`), plus a cost
  model (optionally calibrated from measured CSV rows) that picks
  the cheapest feasible stage automatically — jointly over an op *set* via
  ``plan_stages`` (one shared stage minimizing total cost);
* :mod:`repro.analytics.engine` — stacks same-layout compressed fields into
  a leading batch axis (``repro.core.batch_stack``) and runs the homomorphic
  op set once, ``vmap``-ed and ``jit``-ed, with a compilation cache keyed on
  ``(scheme, block, shape, frozen op-set, stage, region)``;
* :mod:`repro.analytics.query` — ``query(exprs=[...], store=...)``: the
  expression front-end.  Roots are ``repro.core.expr`` DAGs (cross-field
  derived operators — vorticity from u and v, ensemble deltas); the whole
  batch compiles to one program with exactly one stage-reconstruction
  prelude per distinct leaf, planned jointly per connected component
  (``plan_expr``).  With ``store=`` (a :class:`repro.store.FieldStore`)
  leaves may be string ids, planning is cache-aware (resident stages drop
  their reconstruction term), and the compiled program is seeded from
  resident materialized stages.  The flat op-set spelling
  ``query(fields, op_or_ops)`` remains as a deprecated bit-identical shim.
"""
from .planner import (CostModel, ExprPlan, FEASIBILITY, MULTIVARIATE, OPS,
                      RefreshPlan, StageSetPlan, TEMPORAL, as_stage,
                      check_feasible, feasible_stages, is_feasible,
                      plan_expr, plan_refresh, plan_stage, plan_stages)
from .engine import BatchedAnalytics, batch_key
from .query import QueryResult, query

__all__ = [
    "OPS", "TEMPORAL", "MULTIVARIATE", "FEASIBILITY", "as_stage",
    "feasible_stages", "is_feasible", "check_feasible", "plan_stage",
    "plan_stages", "StageSetPlan", "plan_expr", "ExprPlan", "plan_refresh",
    "RefreshPlan", "CostModel", "BatchedAnalytics", "batch_key",
    "QueryResult", "query",
]
