"""The one-call end-to-end pipeline.

    from repro.api import Provisioner
    report = Provisioner(scenario, workload="diffusion",
                         scheduler="stacking", allocator="pso").run(key)

runs P1 (bandwidth allocation) -> P2 (batch-denoising plan) -> execution
on the workload's real model, and bundles everything a figure script or
serving loop needs into a ``ProvisionReport``.  Components are registry
names or protocol instances; omitting the workload gives the pure
analytic pipeline (allocation + plan + simulated timeline, no model).

For requests arriving *over time* instead of a static batch, the
event-driven sibling ``repro.api.online.OnlineProvisioner`` replays this
same allocate -> plan composition on every admitted arrival
(docs/SCENARIOS.md).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro import spans
from repro.api.base import BaseProvisioner, report_dict
from repro.api.protocols import WorkloadOutput
from repro.api.registry import (ALLOCATORS, SCHEDULERS, WORKLOADS,
                                display_name)
# importing the entry modules populates the registries
from repro.api import allocators as _allocators   # noqa: F401
from repro.api import schedulers as _schedulers   # noqa: F401
from repro.api import workloads as _workloads     # noqa: F401
from repro.core.bandwidth import make_plan
from repro.core.delay_model import DelayModel, fit
from repro.core.execution import ExecutionResult
from repro.core.plan import BatchPlan
from repro.core.quality_model import PowerLawFID, QualityModel
from repro.core.service import Scenario
from repro.core.simulator import SimResult, simulate


@dataclasses.dataclass
class ProvisionReport:
    """Everything one provisioning round produced."""
    scenario: Scenario
    allocation: np.ndarray                    # B_k (Hz), sums to budget
    tau_prime: Dict[int, float]               # generation budgets
    plan: BatchPlan                           # P2 solution
    sim: SimResult                            # analytic timeline + quality
    content: Optional[Dict[int, Any]] = None  # per-service artifacts
    timings: List[Tuple[int, float]] = dataclasses.field(
        default_factory=list)                 # measured (batch_size, s)
    delay: Optional[DelayModel] = None
    quality: Optional[QualityModel] = None
    scheduler_name: str = ""
    allocator_name: str = ""
    workload_name: str = ""
    execution: Optional[ExecutionResult] = None  # closed/open-loop run

    @property
    def mean_fid(self) -> float:
        return self.sim.mean_fid

    @property
    def outage_rate(self) -> float:
        return self.sim.outage_rate

    def refit_delay(self) -> DelayModel:
        """Fit g(X) = aX + b from this run's measured per-batch timings
        (requires a timed execution with >= 2 distinct batch sizes) —
        the calibrate->replan loop's measurement half."""
        sizes = [x for x, _ in self.timings]
        if len(set(sizes)) < 2:
            raise ValueError(
                "need timed batches of >= 2 distinct sizes to refit; "
                "run with timed=True on a plan with varied batch sizes")
        m = fit(sizes, [s for _, s in self.timings])
        # least squares can extrapolate a (slightly) negative slope or
        # intercept from noisy timings; delays are physically nonnegative
        # and the schedulers require g(X) > 0
        return DelayModel(a=max(m.a, 0.0), b=max(m.b, 1e-6))

    def summary(self) -> str:
        head = (f"[{self.workload_name or 'analytic'}] "
                f"scheduler={self.scheduler_name} "
                f"allocator={self.allocator_name} "
                f"batches={self.plan.num_batches}")
        body = head + "\n" + self.sim.summary()
        if self.execution is not None:
            body += "\n" + self.execution.summary()
        return body

    def to_dict(self) -> dict:
        """Common report protocol (see ``repro.api.base.report_dict``):
        JSON-serializable aggregates, no model artifacts."""
        d = report_dict(
            "provision", mean_fid=self.mean_fid,
            outage_rate=self.outage_rate, makespan=self.plan.makespan(),
            components={"scheduler": self.scheduler_name,
                        "allocator": self.allocator_name,
                        "workload": self.workload_name},
            telemetry={"batches": self.plan.num_batches,
                       "timings": [[int(x), float(s)]
                                   for x, s in self.timings]},
            n_services=self.scenario.K)
        if self.execution is not None:
            d["execution"] = self.execution.to_dict()
            # per-kernel attribution (ROADMAP follow-up from PR 9):
            # measured wall-clock grouped by padded batch-shape bucket,
            # so drift points at a groupnorm/attention shape regime
            d["telemetry"]["exec_engine"] = d["execution"]["exec_engine"]
            d["telemetry"]["per_bucket"] = \
                d["execution"]["telemetry"]["per_bucket"]
        return d


class Provisioner(BaseProvisioner):
    """Facade binding a scenario to one (workload, scheduler, allocator)
    choice.  ``scheduler``/``allocator``/``workload`` accept registry
    names or protocol instances; ``allocator_kwargs`` pass through to the
    underlying P1 solver (``num_particles``, ``iters``, ``seed``, ...).
    ``engine``/``devices``/``seed``/``execute`` are the unified facade
    kwargs (``repro.api.base``); ``execute_kwargs`` tunes the closed
    loop (``window``, ``drift_tol``, ``min_batches``, ``max_replans``,
    ``headroom``, ``executor``, ``executor_kwargs``)."""

    _LEGACY = ("workload", "scheduler", "allocator", "delay", "quality",
               "allocator_kwargs", "engine")
    _LEGACY_DEFAULTS = {"workload": None, "scheduler": "stacking",
                        "allocator": "pso", "delay": None,
                        "quality": None, "allocator_kwargs": None,
                        "engine": None}

    def __init__(self, scenario: Scenario, *args, workload=None,
                 scheduler="stacking", allocator="pso",
                 delay: Optional[DelayModel] = None,
                 quality: Optional[QualityModel] = None,
                 allocator_kwargs: Optional[dict] = None,
                 engine: Optional[str] = None, devices=None,
                 seed: Optional[int] = None, execute=None,
                 execute_kwargs: Optional[dict] = None):
        kw = self._legacy_positionals(args, dict(
            workload=workload, scheduler=scheduler, allocator=allocator,
            delay=delay, quality=quality,
            allocator_kwargs=allocator_kwargs, engine=engine))
        workload, scheduler = kw["workload"], kw["scheduler"]
        allocator, delay, quality = (kw["allocator"], kw["delay"],
                                     kw["quality"])
        allocator_kwargs, engine = kw["allocator_kwargs"], kw["engine"]
        super().__init__(scenario, engine=engine, devices=devices,
                         seed=seed, execute=execute,
                         execute_kwargs=execute_kwargs)
        self.scheduler_name = display_name(scheduler)
        self.allocator_name = display_name(allocator)
        self.scheduler = SCHEDULERS.resolve(scheduler)
        self.allocator = ALLOCATORS.resolve(allocator)
        wl = WORKLOADS.resolve(workload) if workload is not None else None
        if isinstance(wl, type):
            wl = wl()
        self.workload = wl
        self.workload_name = getattr(wl, "name", "") if wl else ""
        self.delay = delay if delay is not None else (
            wl.default_delay() if wl else DelayModel())
        self.quality = quality if quality is not None else (
            wl.default_quality() if wl else PowerLawFID())
        self.allocator_kwargs = self._seeded_kwargs(allocator,
                                                    allocator_kwargs)

    # -- pipeline stages ------------------------------------------------
    def allocate(self) -> np.ndarray:
        """P1: bandwidth allocation under the current delay/quality."""
        from repro.core import arrays
        with spans.span(spans.ALLOCATE), arrays.engine_scope(self.engine):
            return np.asarray(self.allocator(
                self.scenario, self.scheduler, self.delay, self.quality,
                **self.allocator_kwargs))

    def plan(self, alloc: np.ndarray) -> Tuple[Dict[int, float], BatchPlan]:
        """P2: generation budgets + batch plan under an allocation."""
        from repro.core import arrays
        with spans.span(spans.PLAN), arrays.engine_scope(self.engine):
            return make_plan(self.scenario, alloc, self.scheduler,
                             self.delay, self.quality)

    def calibrate(self, key=None, **kw) -> DelayModel:
        """Measure the workload's real g(X) and adopt it for planning."""
        if self.workload is None:
            raise ValueError("no workload to calibrate against")
        self.delay = self.workload.calibrate(key, **kw)
        return self.delay

    # -- one-call end-to-end --------------------------------------------
    @spans.provision()
    def run(self, key=None, *, execute=None, timed: bool = False,
            calibrate: bool = False, refit: bool = False,
            validate: bool = True) -> ProvisionReport:
        """Allocate -> plan -> (validate) -> simulate -> execute.

        execute: ``None`` falls back to the constructor's ``execute=``
            (default: legacy one-shot workload execution).  ``True``
            runs ``workload.execute`` open loop; ``"open"``/``"closed"``
            drive the plan through ``repro.core.execution.ExecutionLoop``
            (measured wall-clock, rolling delay refit; ``"closed"`` also
            replans mid-flight on drift) and attach the
            ``ExecutionResult`` as ``report.execution``.
        calibrate: measure the workload's delay curve first and plan with
            the fitted model (Fig.-1a loop).
        timed: record per-batch wall clock during execution.
        refit: refit ``self.delay`` in place from the measured timings so
            the *next* ``run`` replans with them (the calibrate->replan
            loop's update half); implies ``timed=True`` and requires an
            executing workload.
        """
        mode = self._resolve_execute(execute)
        if mode is None:
            mode = True                    # legacy default: execute
        key = self._resolve_key(key)
        if refit:
            if mode is False or self.workload is None:
                raise ValueError(
                    "refit=True needs measured timings: attach a workload "
                    "and keep execute=True")
            timed = True                   # refit is meaningless untimed
        if calibrate:
            self.calibrate(key)
        alloc = self.allocate()
        tp, plan = self.plan(alloc)
        if validate:
            with spans.span(spans.VALIDATE):
                plan.validate(gen_deadlines=tp)
        with spans.span(spans.SIMULATE):
            sim = simulate(self.scenario, alloc, plan, self.quality)
        out = WorkloadOutput(content=None)
        execution = None
        if mode is True and self.workload is not None:
            out = self.workload.execute(plan, key, timed=timed)
        elif mode in ("open", "closed"):
            from repro.api.execution import execute_plan, with_kwargs
            execution = execute_plan(
                self.scenario, plan, alloc, self.workload, mode=mode,
                key=key, scheduler=self.scheduler,
                allocator=with_kwargs(self.allocator,
                                      self.allocator_kwargs),
                delay=self.delay, quality=self.quality,
                engine=self.engine, validate=validate,
                **self.execute_kwargs)
            out = WorkloadOutput(content=execution.content,
                                 timings=execution.timings)
        report = ProvisionReport(
            scenario=self.scenario, allocation=alloc, tau_prime=tp,
            plan=plan, sim=sim, content=out.content, timings=out.timings,
            delay=self.delay, quality=self.quality,
            scheduler_name=self.scheduler_name,
            allocator_name=self.allocator_name,
            workload_name=self.workload_name, execution=execution)
        if refit:
            self.delay = report.refit_delay()
        return report
