"""Device-resident bucketed denoising engine (``exec_engine="bucketed"``).

The dict engine round-trips latents through a per-service Python dict on
every step: stack K slices host-side, dispatch, scatter K slices back.
This engine keeps all K latents in ONE device array for the whole
session and drives each batch with a single jitted
gather→DDIM-step→scatter program:

  * **Pool layout** — ``(K+1, H, W, C)``: row i holds service
    ``ids[i]``'s latent (seeded identically to the dict path), row K is
    a scratch row for padded lanes.
  * **Power-of-two buckets** — a batch of B services runs at padded
    width ``shape_bucket(B)`` (min 2), the same bucketing trick as
    ``jaxplan/kernels.py``.  Padded lanes gather the scratch row with
    ``t_now = -1``; ``ddim_step``'s inactive-passthrough returns them
    unchanged, and the duplicate scatter indices all write that same
    unchanged value, so padding is deterministic and invisible.  Any
    plan over K services compiles at most ⌈log2 K⌉ step programs.
  * **Donated buffers** — the pool is donated into every program, so
    steps update latents in place instead of allocating K slice views.
  * **Scan megasteps** — ``run_plan`` fuses runs of consecutive batches
    with identical service composition (a stable phase of a STACKING
    plan) into ``lax.scan`` programs over chunk lengths
    ``_SCAN_CHUNKS``, so a stable phase costs one dispatch per chunk,
    not one per step.  Timed execution stays stepwise — the closed loop
    needs one wall-clock reading per batch.

Numerical contract: per-row results match the dict engine within
``MATCH_TOL`` (XLA may fuse a padded-width batch differently from the
exact-width batch, so bit-exactness across engines is NOT promised; the
dict engine remains the bit-exact-per-row reference).  The property test
in ``tests/test_exec_bucketed.py`` and the ``exec_bucketed_images_match``
e2e gate both pin this tolerance.
"""

from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.execution import shape_bucket
from repro.diffusion.executor import BatchDenoisingExecutor, \
    DenoiseSession

# bucketed-vs-dict per-row tolerance (docs/PERFORMANCE.md): padded-width
# XLA programs may fuse differently from exact-width ones, but per-row
# math is identical up to float32 reassociation
MATCH_TOL = {"atol": 1e-5, "rtol": 1e-5}

# scan chunk lengths, largest first: a stable phase of C steps runs as
# greedy chunks (e.g. C=23 -> 16+4+2+1 step), so each bucket compiles at
# most len(_SCAN_CHUNKS) scan programs ever
_SCAN_CHUNKS = (32, 16, 8, 4, 2)


def pool_step(step_fn):
    """Build the gather→step→scatter program body over a latent pool;
    ``step_fn(params, x, t_now, t_next)`` is the executor's step.  The
    body's name, ``bucketed_step``, names its program in a device trace
    (``jit_bucketed_step``)."""
    def bucketed_step(params, pool, idx, t_now, t_next):
        y = step_fn(params, pool[idx], t_now, t_next)
        return pool.at[idx].set(y)
    return bucketed_step


def pool_scan(step_fn):
    """Scan ``pool_step`` over a ``(C, 2, Bp)`` timestep stack."""
    def bucketed_scan(params, pool, idx, ts):
        def body(p, t):
            y = step_fn(params, p[idx], t[0], t[1])
            return p.at[idx].set(y), None
        out, _ = jax.lax.scan(body, pool, ts)
        return out
    return bucketed_scan


class BucketedDenoiseSession(DenoiseSession):
    """``DenoiseSession`` with device-resident pool execution.  Same
    interface and scheduling semantics (``retarget`` is inherited
    untouched); only the step dispatch differs."""

    def __init__(self, executor: BatchDenoisingExecutor, plan, key):
        super().__init__(executor, plan, key)
        ids = sorted(self.steps_done)
        self._ids = ids
        self._row = {k: i for i, k in enumerate(ids)}
        self._scratch = len(ids)
        self._pool_rows = len(ids) + 1
        cfg = executor.cfg
        shape = (cfg.image_size, cfg.image_size, cfg.in_channels)
        rows = [self.latents[k] for k in ids]
        self._pool = jnp.stack(rows + [jnp.zeros(shape, jnp.float32)])
        # the pool is now the single source of truth; fail loudly if
        # anything still pokes the dict
        self.latents = None
        self._step_prog_body = pool_step(executor.step_fn)
        self._scan_prog_body = pool_scan(executor.step_fn)
        self._scan_dispatch: Dict[tuple, int] = {}
        self._scan_steps = 0

    def _lanes(self, ks: List[int]):
        """Padded (idx, t_now, t_next) lane arrays for one batch;
        validates remaining schedules like the dict path."""
        Bp = shape_bucket(len(ks))
        idx = np.full((Bp,), self._scratch, np.int32)
        t_now = np.full((Bp,), -1, np.int32)
        t_next = np.full((Bp,), -1, np.int32)
        for lane, k in enumerate(ks):
            rem = self._remaining[k]
            if not rem:
                raise ValueError(
                    f"service {k} has no remaining denoising steps")
            idx[lane] = self._row[k]
            t_now[lane] = rem[0]
            t_next[lane] = rem[1] if len(rem) > 1 else -1
        return idx, t_now, t_next

    def run_batch(self, ks: List[int], timed: bool = False) -> float:
        with spans.span(spans.SESSION_LANES):
            idx, t_now, t_next = self._lanes(ks)
        Bp = len(idx)
        params = self.executor.params
        with spans.span(spans.SESSION_DISPATCH):
            prog = self.executor.program(
                ("bstep", self._pool_rows, Bp), self._step_prog_body,
                (params, self._pool, idx, t_now, t_next), donate=(1,))
            t0 = time.perf_counter()
            pool = prog(params, self._pool, idx, t_now, t_next)
        dt = 0.0
        if timed:
            with spans.span(spans.SESSION_WAIT):
                pool.block_until_ready()
            dt = time.perf_counter() - t0
        self._pool = pool
        self.executor.dispatches += 1
        self._dispatch[Bp] = self._dispatch.get(Bp, 0) + 1
        for k in ks:
            self._remaining[k].pop(0)
            self.steps_done[k] += 1
        return dt

    def run_plan(self, batches: List[List[int]]) -> None:
        """Fuse runs of consecutive identical-composition batches into
        scan megasteps; mixed phases fall back to single steps."""
        i, n = 0, len(batches)
        while i < n:
            ks = list(batches[i])
            sig = tuple(sorted(ks))
            j = i + 1
            while j < n and tuple(sorted(batches[j])) == sig:
                j += 1
            run = j - i
            if run >= 2 and ks:
                # never scan past a service's remaining schedule — the
                # shortfall surfaces as the same per-batch error the
                # stepwise path would raise
                run = min([run] + [len(self._remaining[k])
                                   for k in ks])
            if run >= 2:
                self._run_scan(ks, run)
                i += run
            else:
                self.run_batch(ks)
                i += 1

    def _run_scan(self, ks: List[int], C: int) -> None:
        Bp = shape_bucket(len(ks))
        idx = np.full((Bp,), self._scratch, np.int32)
        ts = np.full((C, 2, Bp), -1, np.int32)
        for lane, k in enumerate(ks):
            idx[lane] = self._row[k]
            rem = self._remaining[k]
            for c in range(C):
                ts[c, 0, lane] = rem[c]
                ts[c, 1, lane] = rem[c + 1] if c + 1 < len(rem) else -1
        params = self.executor.params
        off = 0
        for chunk in _SCAN_CHUNKS:
            while C - off >= chunk:
                prog = self.executor.program(
                    ("bscan", self._pool_rows, Bp, chunk),
                    self._scan_prog_body,
                    (params, self._pool, idx, ts[off:off + chunk]),
                    donate=(1,))
                self._pool = prog(params, self._pool, idx,
                                  ts[off:off + chunk])
                self.executor.dispatches += 1
                key = (Bp, chunk)
                self._scan_dispatch[key] = \
                    self._scan_dispatch.get(key, 0) + 1
                self._scan_steps += chunk
                off += chunk
        for k in ks:
            del self._remaining[k][:off]
            self.steps_done[k] += off
        while off < C:     # _SCAN_CHUNKS ends at 2, so at most 1 step
            self.run_batch(ks)
            off += 1

    def telemetry(self) -> dict:
        mine = self.executor.compile_log[self._clog0:]
        compile_by_bucket: Dict[int, float] = {}
        for key, s in mine:
            if key[0] in ("bstep", "bscan"):
                b = int(key[2])
                compile_by_bucket[b] = compile_by_bucket.get(b, 0.0) + s
        return {
            "exec_engine": "bucketed",
            **self.executor.arch_stats(),
            "dispatches": int(sum(self._dispatch.values())
                              + sum(self._scan_dispatch.values())),
            "by_bucket": {str(b): int(n)
                          for b, n in sorted(self._dispatch.items())},
            "scan_dispatches": {
                f"b{b}_c{c}": int(n)
                for (b, c), n in sorted(self._scan_dispatch.items())},
            "scan_fused_steps": int(self._scan_steps),
            "compiles": len(mine),
            "compile_s": float(sum(s for _, s in mine)),
            "compile_s_by_bucket": {
                str(b): float(s)
                for b, s in sorted(compile_by_bucket.items())},
        }

    def finish(self) -> Dict[int, np.ndarray]:
        pool = np.asarray(self._pool)
        return {k: pool[self._row[k]] for k in self._ids}


def measure_bucketed_curve(executor: BatchDenoisingExecutor, key,
                           batch_sizes, reps: int):
    """Fig. 1a sweep through the bucket programs: sizes sharing a bucket
    share one compiled program, so sweeping 1..16 compiles 4 programs
    instead of 16.  The reading for size X is the padded bucket's cost —
    exactly what the bucketed engine pays for a size-X batch."""
    cfg = executor.cfg
    sizes = [int(X) for X in batch_sizes]
    max_bucket = max(shape_bucket(X) for X in sizes)
    pool_rows = max_bucket + 1
    pool = jax.random.normal(
        key, (pool_rows, cfg.image_size, cfg.image_size,
              cfg.in_channels), jnp.float32)
    body = pool_step(executor.step_fn)
    params = executor.params
    t_mid = executor.T_train // 2
    out = []
    for X in sizes:
        Bp = shape_bucket(X)
        idx = np.full((Bp,), pool_rows - 1, np.int32)
        idx[:X] = np.arange(X, dtype=np.int32)
        t_now = np.full((Bp,), -1, np.int32)
        t_next = np.full((Bp,), -1, np.int32)
        t_now[:X] = t_mid
        t_next[:X] = t_mid - 1
        prog = executor.program(("bstep", pool_rows, Bp), body,
                                (params, pool, idx, t_now, t_next),
                                donate=(1,))
        # warm dispatch (the pool is donated, so rethread it)
        pool = prog(params, pool, idx, t_now, t_next)
        pool.block_until_ready()
        executor.dispatches += 1
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            pool = prog(params, pool, idx, t_now, t_next)
            pool.block_until_ready()
            best = min(best, time.perf_counter() - t0)
            executor.dispatches += 1
        out.append((X, best))
    return out
