"""Per-row reference for the served denoising session.

``ReferenceDenoiseSession`` keeps each service's latent in a Python dict
and steps every batch at its exact width: stack the batch's latents,
one ``("dstep", X)`` program, scatter the rows back.  It shares the
schedule bookkeeping and the noise draw with the served
``DenoiseSession`` (``repro.diffusion.executor.ServiceSchedules``) and
nothing else, so the two differ only in how a batch reaches the
denoiser.  Tests and the e2e suite build it directly; nothing on the
served path opens it.

Numerical contract: the served session's per-row results match this
reference within ``MATCH_TOL``.  XLA may fuse a padded-width batch
differently from the exact-width one, so bit identity across the two is
not promised; per-row math is identical up to float32 reassociation.
"""

from __future__ import annotations

import time
from typing import Dict, List

import jax.numpy as jnp
import numpy as np

from repro.core.plan import BatchPlan
from repro.diffusion.executor import BatchDenoisingExecutor, \
    ServiceSchedules

MATCH_TOL = {"atol": 1e-5, "rtol": 1e-5}


class ReferenceDenoiseSession(ServiceSchedules):
    """Per-service latents, exact-width batches: the per-row reference
    (same interface as ``DenoiseSession``)."""

    exec_engine = "reference"

    def __init__(self, executor: BatchDenoisingExecutor, plan: BatchPlan,
                 key):
        super().__init__(executor, plan)
        self.latents = dict(zip(self.ids, self._noise(key)))

    def run_batch(self, ks: List[int], timed: bool = False) -> float:
        """Advance ``ks`` one DDIM step in ONE batched call at their
        exact width.  Returns measured seconds when ``timed`` (0.0
        otherwise)."""
        ex = self.executor
        ts = [self._next_t(k) for k in ks]
        x = jnp.stack([self.latents[k] for k in ks])
        t_now = jnp.array([t for t, _ in ts], jnp.int32)
        t_next = jnp.array([t for _, t in ts], jnp.int32)
        prog = ex.program(("dstep", len(ks)), ex.step_fn,
                          (ex.params, x, t_now, t_next))
        dt = 0.0
        if timed:
            t0 = time.perf_counter()
            x = prog(ex.params, x, t_now, t_next)
            x.block_until_ready()
            dt = time.perf_counter() - t0
        else:
            x = prog(ex.params, x, t_now, t_next)
        for i, k in enumerate(ks):
            self.latents[k] = x[i]
        self._count(len(ks))
        self._advance(ks)
        return dt

    def run_plan(self, batches: List[List[int]]) -> None:
        for ks in batches:
            self.run_batch(ks)

    def telemetry(self) -> dict:
        return {**super().telemetry(),
                "dispatches": int(sum(self._dispatch.values())),
                "by_size": {str(b): int(n)
                            for b, n in sorted(self._dispatch.items())}}

    def finish(self) -> Dict[int, np.ndarray]:
        """Final images (zero-step services: their untouched latent)."""
        return {k: np.asarray(v) for k, v in self.latents.items()}


def reference_images(executor: BatchDenoisingExecutor, plan: BatchPlan,
                     key) -> Dict[int, np.ndarray]:
    """The plan's final images, every batch run by the reference."""
    sess = ReferenceDenoiseSession(executor, plan, key)
    sess.run_plan([[k for k, _ in batch] for batch in plan.batches])
    return sess.finish()
