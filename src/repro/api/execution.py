"""Closed-loop plan execution behind the EXECUTORS registry.

The seventh registry maps executor names to *session factories*: a
session is a stepwise execution handle (``run_batch`` / ``retarget`` /
``finish`` — see ``repro.core.execution``) that
``repro.core.execution.ExecutionLoop`` drives batch by batch, measuring
wall-clock, refitting the delay model and replanning on drift.

Built-in entries:

  * ``"diffusion"``  — ``BatchDenoisingExecutor`` sessions (the DDIM
                       U-Net with the Pallas kernels)
  * ``"llm_decode"`` — ``ServingEngine`` decode sessions
  * ``"simulated"``  — synthetic wall-clock from a hidden true
                       ``DelayModel`` (fast deterministic tests /
                       what-if drift studies); takes ``true_delay=``,
                       ``noise=``, ``seed=`` via ``executor_kwargs``

Entry points:

  * ``execute_plan``   — run a (scenario, plan, allocation) on a
                         workload's executor, open or closed loop
  * ``execute_report`` — the same, resolving everything from a
                         ``ProvisionReport``
  * ``replay_result``  — re-run an online result's committed batch
                         sequence on a real executor (open loop)
"""

from __future__ import annotations

import functools
from typing import Optional

from repro import spans
from repro.api.protocols import WorkloadOutput
from repro.api.registry import (ALLOCATORS, EXECUTORS, SCHEDULERS,
                                WORKLOADS, register_executor)
from repro.core.delay_model import DelayModel
from repro.core.execution import (ExecutionLoop, ExecutionResult,
                                  SimulatedSession)
from repro.core.plan import BatchPlan


@register_executor("diffusion", aliases=("llm_decode",))
def _workload_session(workload, plan, key, **kw):
    return workload.open_session(plan, key, **kw)


@register_executor("simulated")
def _simulated_session(workload, plan, key, *, true_delay: DelayModel,
                       noise: float = 0.0, seed: int = 0):
    return SimulatedSession(plan, true_delay, noise=noise, seed=seed)


def make_session(workload, plan: BatchPlan, key=None, *,
                 executor=None, executor_kwargs: Optional[dict] = None):
    """Open a stepwise execution session.  ``executor`` is an EXECUTORS
    name or factory; ``None`` uses the workload's own name (so a
    ``DiffusionWorkload`` opens a ``DenoiseSession`` etc.)."""
    if executor is None:
        executor = getattr(workload, "name", None)
        if executor is None:
            raise ValueError(
                "no executor: attach a named workload or pass "
                f"executor= (registered: {EXECUTORS.names()})")
    factory = EXECUTORS.resolve(executor)
    return factory(workload, plan, key, **(executor_kwargs or {}))


def execute_plan(scenario, plan: BatchPlan, alloc, workload=None, *,
                 mode: str = "closed", key=None, scheduler="stacking",
                 allocator="inv_se", delay: Optional[DelayModel] = None,
                 quality=None, engine: Optional[str] = None,
                 validate: bool = True, executor=None,
                 executor_kwargs: Optional[dict] = None,
                 window: int = 32, drift_tol: float = 0.25,
                 min_batches: int = 3, max_replans: int = 8,
                 headroom: float = 1.0) -> ExecutionResult:
    """Execute a planned batch schedule on a real (or simulated)
    executor.  ``mode="open"`` runs the plan as given (telemetry +
    rolling refit only); ``mode="closed"`` replans mid-flight through
    the offset-aware path when measured delay drifts (``drift_tol``,
    ``min_batches``, ``max_replans``, ``headroom`` tune the loop)."""
    with spans.span(spans.EXECUTE):
        session = make_session(workload, plan, key, executor=executor,
                               executor_kwargs=executor_kwargs)
        loop = ExecutionLoop(
            scenario, plan, alloc, session, delay=delay, quality=quality,
            scheduler=SCHEDULERS.resolve(scheduler),
            allocator=ALLOCATORS.resolve(allocator),
            mode=mode, window=window, drift_tol=drift_tol,
            min_batches=min_batches, max_replans=max_replans,
            headroom=headroom, validate=validate, engine=engine)
        return loop.run()


def execute_report(report, workload=None, *, mode: str = "closed",
                   key=None, **kwargs) -> ExecutionResult:
    """``execute_plan`` with everything resolved from a
    ``ProvisionReport``: its scenario, allocation, plan, delay/quality
    models and component names.  ``workload`` is a WORKLOADS name or
    instance (``None`` works with ``executor="simulated"``); remaining
    keywords are ``execute_plan``'s."""
    wl = WORKLOADS.resolve(workload) if workload is not None else None
    if isinstance(wl, type):
        wl = wl()
    scheduler = kwargs.pop("scheduler", None)
    if scheduler is None:
        name = getattr(report, "scheduler_name", "")
        scheduler = name if name in SCHEDULERS else "stacking"
    allocator = kwargs.pop("allocator", None)
    if allocator is None:
        name = getattr(report, "allocator_name", "")
        allocator = name if name in ALLOCATORS else "inv_se"
    kwargs.setdefault("delay", report.delay)
    kwargs.setdefault("quality", report.quality)
    return execute_plan(report.scenario, report.plan, report.allocation,
                        wl, mode=mode, key=key, scheduler=scheduler,
                        allocator=allocator, **kwargs)


def replay_plan(executed_batches, steps_completed,
                delay: DelayModel) -> BatchPlan:
    """A ``BatchPlan`` replaying an online run's committed batch
    sequence (``OnlineResult.executed_batches``): same batches, same
    order, simulated start instants as start times."""
    counters: dict = {}
    batches, starts = [], []
    for t_start, ids in executed_batches:
        batch = []
        for k in ids:
            batch.append((k, counters.get(k, 0)))
            counters[k] = counters.get(k, 0) + 1
        batches.append(batch)
        starts.append(float(t_start))
    assert counters == {k: v for k, v in steps_completed.items() if v}, \
        "executed batch log disagrees with final step counts"
    return BatchPlan(batches=batches, start_times=starts,
                     steps_completed=dict(counters), delay=delay)


def replay_result(workload, result, delay: DelayModel, key=None, *,
                  executor=None,
                  executor_kwargs: Optional[dict] = None) \
        -> WorkloadOutput:
    """Re-run an ``OnlineResult``'s committed batch sequence on a real
    executor, open loop, with per-batch timing — the online facades'
    ``execute=True`` path."""
    if result.executed_batches is None:
        raise ValueError("this result carries no executed-batch log "
                         "(multi-server results interleave per cell)")
    steps = {o.id: o.steps for o in result.outcomes}
    plan = replay_plan(result.executed_batches, steps, delay)
    session = make_session(workload, plan, key, executor=executor,
                           executor_kwargs=executor_kwargs)
    timings = []
    for _, ids in result.executed_batches:
        timings.append((len(ids), session.run_batch(ids, timed=True)))
    return WorkloadOutput(content=session.finish(), timings=timings)


def with_kwargs(fn, kwargs: Optional[dict]):
    """Bind component kwargs (allocator seeds etc.) onto a protocol
    callable — shared by the facades."""
    return functools.partial(fn, **kwargs) if kwargs else fn
