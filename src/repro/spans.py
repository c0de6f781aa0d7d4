"""Named host spans on the profiler's clock, at the served path's layers.

A span is a ``jax.profiler.TraceAnnotation``: it costs about two
microseconds when no profiler runs, and is recorded, on the same clock as
the device's operations, while one does (``jax.profiler.trace(dir)``;
docs/PERFORMANCE.md, "Reading the program's spans").  Spans are per
round, per stage or per batch, never per service.  They nest so:

    repro.provision              Provisioner.run              stat run
      repro.allocate             P1, bandwidth allocation
      repro.plan                 P2, generation budgets and batch plan
        repro.plan.clustered     Algorithm 1's clustered sweep
          repro.plan.compact     finished levels leave the sweep  stats kept, of
        repro.plan.lockstep      offset water-filling sweep (replans)
        repro.plan.shared        shared-horizon sweep (replans)
          repro.plan.compact
        repro.plan.replay        the winner's batch list
      repro.validate             BatchPlan.validate
      repro.simulate             the analytic timeline
      repro.execute              execute_plan: open, then the loop
        repro.session.open       BatchDenoisingExecutor.open_session
        repro.batch              one run_batch of the loop     stat size
          repro.session.lanes    padded lane arrays on the host
          repro.session.dispatch program lookup and the call
          repro.session.wait     block_until_ready
        repro.replan             refit, allocate and plan the residual
          repro.plan.*
        repro.finish             the session's images to the host
    repro.compile                a step program compiled on first use
                                 (under whatever asked for it)  stats kind,
                                 arch, and attn for a DiT

Every span opened inside a ``repro.provision`` carries that round's
``run`` stat, one process-wide id per round.
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools

PROVISION = "repro.provision"
ALLOCATE = "repro.allocate"
PLAN = "repro.plan"
PLAN_CLUSTERED = "repro.plan.clustered"
PLAN_COMPACT = "repro.plan.compact"
PLAN_LOCKSTEP = "repro.plan.lockstep"
PLAN_SHARED = "repro.plan.shared"
PLAN_REPLAY = "repro.plan.replay"
VALIDATE = "repro.validate"
SIMULATE = "repro.simulate"
EXECUTE = "repro.execute"
SESSION_OPEN = "repro.session.open"
BATCH = "repro.batch"
SESSION_LANES = "repro.session.lanes"
SESSION_DISPATCH = "repro.session.dispatch"
SESSION_WAIT = "repro.session.wait"
REPLAN = "repro.replan"
FINISH = "repro.finish"
COMPILE = "repro.compile"

_runs = itertools.count(1)
_run = contextvars.ContextVar("repro_spans_run", default=None)
_annotation = None    # jax.profiler.TraceAnnotation, imported on first use


def span(name: str, **stats):
    """A host span called ``name`` with ``stats`` (ints or strings)."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation as _annotation
    run = _run.get()
    if run is not None:
        stats["run"] = run
    return _annotation(name, **stats)


@contextlib.contextmanager
def provision():
    """The ``repro.provision`` span of one round, under a new ``run``;
    ``@provision()`` opens one around each call of a function."""
    token = _run.set(next(_runs))
    try:
        with span(PROVISION):
            yield
    finally:
        _run.reset(token)
