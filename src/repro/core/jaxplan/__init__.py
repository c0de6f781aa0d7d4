"""``repro.core.jaxplan`` — the jit-compiled "jax" planner engine.

Importing this package registers ``engine="jax"`` with
``repro.core.arrays``' engine registry; the existing dispatch
(``set_engine`` / ``engine_scope`` / per-call ``engine=`` kwargs /
``REPRO_PLANNER_ENGINE=jax``) then routes the planner entry points
here.  The import is lazy and optional: ``arrays`` only probes this
package when someone asks for an engine it does not know, so a repo
checkout without jax keeps working untouched (requesting ``"jax"``
there raises a ValueError naming the missing backend).

Layout:

* ``kernels``  — jitted ``(L, K)`` sweeps (``lax.while_loop`` rounds,
  every candidate level advancing together) + scoring/selection.
* ``backend``  — the engine entry points (``stacking``,
  ``equal_steps``, ``offset_plan``) that the vec/scalar dispatch
  sites call through ``arrays.engine_impl("jax")``.
* ``batched``  — ``plan_many``: the whole T* search vmapped over
  ~10^3 stacked scenarios in one jitted call.
* ``sharded``  — ``plan_many_sharded``: the scenario axis split across
  devices with ``jax.shard_map`` (``plan_many(..., devices=...)``
  routes here).
* ``optimal``  — the exact DP as a jitted breadth-first sweep.

Equivalence contract: objectives match the NumPy reference within the
tolerance documented in docs/PERFORMANCE.md ("jax engine"), never bit
for bit — XLA may reassociate reductions.  Returned ``BatchPlan``s are
always materialized by the exact NumPy single-level passes, so they
satisfy the paper's constraints regardless of engine.
"""

from __future__ import annotations

import types

import jax as _jax  # noqa: F401 — fail fast (ImportError) when absent

from repro.core import arrays as _arrays
from repro.core.jaxplan import backend, batched, kernels, optimal, sharded
from repro.core.jaxplan.backend import equal_steps, offset_plan, stacking
from repro.core.jaxplan.batched import (PlanManyResult, plan_many,
                                        replan_many)
from repro.core.jaxplan.optimal import optimal_mean_fid, optimal_plan
from repro.core.jaxplan.sharded import (plan_many_sharded,
                                        replan_many_sharded,
                                        resolve_devices)

#: what ``arrays.engine_impl("jax")`` hands to the dispatch sites
IMPL = types.SimpleNamespace(
    name="jax",
    stacking=stacking,
    equal_steps=equal_steps,
    offset_plan=offset_plan,
    optimal_plan=optimal_plan,
    optimal_mean_fid=optimal_mean_fid,
    plan_many=plan_many,
    plan_many_sharded=plan_many_sharded,
    replan_many=replan_many,
    replan_many_sharded=replan_many_sharded,
)

_arrays.register_engine("jax", IMPL)

__all__ = [
    "IMPL",
    "PlanManyResult",
    "backend",
    "batched",
    "equal_steps",
    "kernels",
    "offset_plan",
    "optimal",
    "optimal_mean_fid",
    "optimal_plan",
    "plan_many",
    "plan_many_sharded",
    "replan_many",
    "replan_many_sharded",
    "resolve_devices",
    "sharded",
    "stacking",
]
