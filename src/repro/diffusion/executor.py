"""Batch-denoising executor: runs a BatchPlan against a real denoiser.

The denoiser is the configuration's ``arch`` (``denoiser``): the DDIM
U-Net (``"unet"``) or a DiT (``"dit"``); everything below its ``eps_fn``
(sessions, pool, DDIM update) is the same for both.

Each service k ends the plan with T_k steps; its DDIM schedule is the
evenly-spaced T_k-step subsequence.  Batch n gathers the current latents
of its packed services (which sit at *different* step indices of
*different* schedules), advances them with ONE batched U-Net call using
per-sample timesteps, and scatters the results back — this is exactly the
parallelism the paper's Fig. 1a measures.

One engine serves every session, ``DenoiseSession``:

  * **Pool layout** — ``(K+1, H, W, C)``: row i holds service
    ``ids[i]``'s latent, row K is a scratch row for padded lanes.  The
    pool lives on the device for the whole session.
  * **Power-of-two buckets** — a batch of B services runs at padded
    width ``shape_bucket(B)`` (min 2) through one jitted
    gather→DDIM-step→scatter program.  Padded lanes gather the scratch
    row with ``t_now = -1``; ``ddim_step``'s inactive-passthrough returns
    them unchanged, and the duplicate scatter indices all write that same
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

The per-row reference the tests and the e2e suite compare it with,
``repro.diffusion.reference.ReferenceDenoiseSession``, shares the
schedule bookkeeping (``ServiceSchedules``) and steps each batch at its
exact width from a per-service dict; nothing on the served path opens it.

Step programs are AOT-compiled (``jit(f).lower(...).compile()``) and
cached on the executor in ``_programs``.  The U-Net weights are the
programs' first argument, never a closure: closed-over arrays would be
embedded in every program as constants (~143 MB at the published
width), bloating compile time and the persistent cache, and they could
not be compiled from ``jax.eval_shape`` shapes.  Compile wall-clock is recorded
in ``compile_log`` separately from execution, so timed readings are
steady-state by construction — ``timed`` mode runs the U-Net exactly
once per batch.

Also the measurement rig for refitting the delay model (Fig. 1a):
``timed`` mode records per-batch wall-clock vs batch size, and
``measure_delay_curve`` sweeps batch sizes through the bucket programs.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.configs.ddim_cifar10 import UNetConfig
from repro.core.execution import shape_bucket
from repro.core.plan import BatchPlan
from repro.diffusion import ddim, dit, unet

DENOISERS = {"unet": unet, "dit": dit}

# scan chunk lengths, largest first: a stable phase of C steps runs as
# greedy chunks (e.g. C=23 -> 16+4+2+1 step), so each bucket compiles at
# most len(_SCAN_CHUNKS) scan programs ever
_SCAN_CHUNKS = (32, 16, 8, 4, 2)


def denoiser(cfg: UNetConfig):
    """The module (``schema``, ``forward``) of ``cfg.arch``."""
    try:
        return DENOISERS[cfg.arch]
    except KeyError:
        raise ValueError(f"unknown denoiser arch {cfg.arch!r}; expected "
                         f"one of {sorted(DENOISERS)}") from None


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


class BatchDenoisingExecutor:
    def __init__(self, cfg: UNetConfig, params,
                 num_train_timesteps: Optional[int] = None):
        self.cfg = cfg
        self.params = params
        self._forward = denoiser(cfg).forward
        self.T_train = num_train_timesteps or cfg.num_train_timesteps
        # AOT-compiled step programs, keyed by (kind, *static shape info).
        # Compiling via lower().compile() keeps compilation OUT of the
        # execution path: the first timed call through a program is
        # already warm, so per-bucket warm-up state is simply "is the
        # key present here".
        self._programs: Dict[tuple, object] = {}
        # [(program key, compile seconds)] in compile order — what the
        # sessions' telemetry reports per bucket, kept apart from the
        # timed readings
        self.compile_log: List[Tuple[tuple, float]] = []
        # compile entries added by the most recent measure_delay_curve
        self.last_compile_log: List[Tuple[tuple, float]] = []
        # total jitted step-program executions (all sessions) — the
        # regression counter proving timed mode runs the U-Net once
        self.dispatches = 0

    def eps_fn(self, params, x, t):
        return self._forward(self.cfg, params, x, t)

    def step_fn(self, params, x, t_now, t_next):
        """One batched DDIM step with per-sample timesteps — the
        function every step program is built from."""
        return ddim.ddim_step(functools.partial(self.eps_fn, params), x,
                              t_now, t_next, self.T_train)

    def program(self, key: tuple, build, example_args,
                donate: tuple = ()):
        """AOT-compiled executable for ``key``, compiling (and logging
        compile wall-clock) on first use.  ``lower()`` only traces —
        example args are never executed or donated at compile time —
        so fetching a program is always side-effect-free."""
        prog = self._programs.get(key)
        if prog is None:
            t0 = time.perf_counter()
            with spans.span(spans.COMPILE, kind=key[0],
                            **self.arch_stats()):
                prog = jax.jit(build, donate_argnums=donate) \
                    .lower(*example_args).compile()
            self.compile_log.append((key, time.perf_counter() - t0))
            self._programs[key] = prog
        return prog

    def arch_stats(self) -> dict:
        """``arch``, and for a DiT the ``attn`` path its forward runs
        (``dit.attention_path``)."""
        out = {"arch": self.cfg.arch}
        if self.cfg.arch == "dit":
            out["attn"] = dit.attention_path(self.cfg)
        return out

    def open_session(self, plan: BatchPlan, key) -> "DenoiseSession":
        """Stepwise execution handle for the EXECUTORS registry: batches
        are driven one ``run_batch`` call at a time so a closed loop
        (``repro.core.execution``) can observe wall-clock and retarget
        remaining schedules between batches."""
        with spans.span(spans.SESSION_OPEN):
            return DenoiseSession(self, plan, key)

    def run(self, plan: BatchPlan, key, timed: bool = False
            ) -> Tuple[Dict[int, np.ndarray], List]:
        """Execute the plan.  Returns ({service: final image}, timings).

        timings: list of (batch_size, seconds) when timed=True.
        Zero-step services (the planner retired them) are never batched;
        their latent comes back untouched.  Untimed runs go through
        ``run_plan`` so stable plan phases fuse into scan megasteps;
        timed runs stay stepwise (one reading per batch).
        """
        sess = self.open_session(plan, key)
        batches = [[k for k, _ in batch] for batch in plan.batches]
        timings = []
        if timed:
            for ks in batches:
                timings.append((len(ks), sess.run_batch(ks, timed=True)))
        else:
            sess.run_plan(batches)
        return sess.finish(), timings

    def measure_delay_curve(self, key, batch_sizes=range(1, 17),
                            reps: int = 3) -> List[Tuple[int, float]]:
        """Fig. 1a measurement: steady-state per-step delay vs batch
        size, through the bucket programs the sessions run.  Sizes
        sharing a bucket share one compiled program — sweeping 1..16
        compiles 4 programs, not 16 — and the reading for size X is the
        padded bucket's cost, because that IS what a session pays for a
        size-X batch.  Compile time never lands in the readings
        (programs are AOT-compiled first); it is reported separately in
        ``last_compile_log``."""
        clog0 = len(self.compile_log)
        cfg = self.cfg
        sizes = [int(X) for X in batch_sizes]
        pool_rows = max(shape_bucket(X) for X in sizes) + 1
        pool = jax.random.normal(
            key, (pool_rows, cfg.image_size, cfg.image_size,
                  cfg.in_channels), jnp.float32)
        body = pool_step(self.step_fn)
        t_mid = self.T_train // 2
        out = []
        for X in sizes:
            Bp = shape_bucket(X)
            idx = np.full((Bp,), pool_rows - 1, np.int32)
            idx[:X] = np.arange(X, dtype=np.int32)
            t_now = np.full((Bp,), -1, np.int32)
            t_next = np.full((Bp,), -1, np.int32)
            t_now[:X] = t_mid
            t_next[:X] = t_mid - 1
            prog = self.program(("bstep", pool_rows, Bp), body,
                                (self.params, pool, idx, t_now, t_next),
                                donate=(1,))
            # warm dispatch (the pool is donated, so rethread it)
            pool = prog(self.params, pool, idx, t_now, t_next)
            pool.block_until_ready()
            self.dispatches += 1
            best = float("inf")
            for _ in range(reps):
                t0 = time.perf_counter()
                pool = prog(self.params, pool, idx, t_now, t_next)
                pool.block_until_ready()
                best = min(best, time.perf_counter() - t0)
                self.dispatches += 1
            out.append((X, best))
        self.last_compile_log = self.compile_log[clog0:]
        return out


class ServiceSchedules:
    """What every denoising session keeps per service: its initial
    noise, ``steps_done`` and its *remaining* DDIM timesteps.

    Noise is drawn per service from ``jax.random.split(key)`` in
    sorted-id order, one ``jax.random.normal`` each.  ``retarget`` swaps
    the remaining timesteps for a fresh evenly-spaced chain when a
    mid-flight replan changes a service's total step count; services
    retired at zero steps keep their noise latent untouched and are
    never batched.
    """

    def __init__(self, executor: BatchDenoisingExecutor, plan: BatchPlan):
        self.executor = executor
        cfg = executor.cfg
        self.shape = (cfg.image_size, cfg.image_size, cfg.in_channels)
        self.ids = sorted(plan.steps_completed)
        self.steps_done: Dict[int, int] = {k: 0 for k in self.ids}
        # remaining timesteps, next-to-run first; [] = done denoising
        self._remaining: Dict[int, List[int]] = {
            k: list(ddim.ddim_timesteps(T, executor.T_train)) if T > 0
            else []
            for k, T in plan.steps_completed.items()}
        # dispatches per batch width, and the compile log watermark so
        # telemetry() reports only THIS session's compiles (a warm
        # second session reports zero)
        self._dispatch: Dict[int, int] = {}
        self._clog0 = len(executor.compile_log)

    def _noise(self, key) -> List[jax.Array]:
        """Each service's initial latent, in sorted-id order."""
        keys = jax.random.split(key, max(len(self.ids), 1))
        return [jax.random.normal(kk, self.shape, jnp.float32)
                for _, kk in zip(self.ids, keys)]

    def _next_t(self, k: int) -> Tuple[int, int]:
        """(t_now, t_next) of service ``k``'s next step (t_next = -1 on
        its last)."""
        rem = self._remaining[k]
        if not rem:
            raise ValueError(
                f"service {k} has no remaining denoising steps")
        return rem[0], (rem[1] if len(rem) > 1 else -1)

    def _advance(self, ks: List[int], n: int = 1) -> None:
        for k in ks:
            del self._remaining[k][:n]
            self.steps_done[k] += n

    def retarget(self, totals: Dict[int, int]) -> None:
        """Re-aim services at new TOTAL step counts (executed steps
        included — the no-resurrection crediting of ``_ServerTrack``).
        A total equal to ``steps_done`` retires the service where it
        stands; a total below it, or new steps for a fully denoised
        chain, is a resurrection and raises."""
        for k, total in totals.items():
            done = self.steps_done[k]
            extra = int(total) - done
            if extra < 0:
                raise ValueError(
                    f"service {k}: retarget total {total} < "
                    f"{done} steps already executed")
            if extra == 0:
                self._remaining[k] = []
            elif done == 0:
                self._remaining[k] = list(
                    ddim.ddim_timesteps(extra, self.executor.T_train))
            elif not self._remaining[k]:
                raise ValueError(
                    f"service {k} already fully denoised; cannot "
                    f"schedule {extra} more steps")
            else:
                self._remaining[k] = list(ddim.retarget_timesteps(
                    self._remaining[k][0], extra))

    def _count(self, width: int) -> None:
        self.executor.dispatches += 1
        self._dispatch[width] = self._dispatch.get(width, 0) + 1

    def _compiles(self) -> List[Tuple[tuple, float]]:
        return self.executor.compile_log[self._clog0:]

    def telemetry(self) -> dict:
        """Engine, ``arch`` and this session's compile counters
        (surfaced through
        ``ExecutionResult.to_dict()['telemetry']['session']``)."""
        mine = self._compiles()
        return {
            "exec_engine": self.exec_engine,
            **self.executor.arch_stats(),
            "compiles": len(mine),
            "compile_s": float(sum(s for _, s in mine)),
        }


class DenoiseSession(ServiceSchedules):
    """One plan execution on the device-resident pool, one batch at a
    time (the diffusion entry of the EXECUTORS registry — see
    ``repro.api.execution``)."""

    exec_engine = "bucketed"

    def __init__(self, executor: BatchDenoisingExecutor, plan: BatchPlan,
                 key):
        super().__init__(executor, plan)
        self._row = {k: i for i, k in enumerate(self.ids)}
        self._scratch = len(self.ids)
        self._pool_rows = len(self.ids) + 1
        self._pool = jnp.stack(self._noise(key)
                               + [jnp.zeros(self.shape, jnp.float32)])
        self._step_prog_body = pool_step(executor.step_fn)
        self._scan_prog_body = pool_scan(executor.step_fn)
        self._scan_dispatch: Dict[tuple, int] = {}
        self._scan_steps = 0

    def _lanes(self, ks: List[int]):
        """Padded (idx, t_now, t_next) lane arrays for one batch."""
        Bp = shape_bucket(len(ks))
        idx = np.full((Bp,), self._scratch, np.int32)
        t_now = np.full((Bp,), -1, np.int32)
        t_next = np.full((Bp,), -1, np.int32)
        for lane, k in enumerate(ks):
            t_now[lane], t_next[lane] = self._next_t(k)
            idx[lane] = self._row[k]
        return idx, t_now, t_next

    def run_batch(self, ks: List[int], timed: bool = False) -> float:
        """Advance each service in ``ks`` by one step of its remaining
        schedule, in one batched call.  Returns the measured wall-clock
        seconds when ``timed`` (0.0 otherwise)."""
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
        self._count(Bp)
        self._advance(ks)
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
        self._advance(ks, off)
        while off < C:     # _SCAN_CHUNKS ends at 2, so at most 1 step
            self.run_batch(ks)
            off += 1

    def telemetry(self) -> dict:
        """Dispatches by bucket and scan chunk, and compile seconds by
        bucket, on top of the shared counters."""
        compile_by_bucket: Dict[int, float] = {}
        for key, s in self._compiles():
            if key[0] in ("bstep", "bscan"):
                b = int(key[2])
                compile_by_bucket[b] = compile_by_bucket.get(b, 0.0) + s
        return {
            **super().telemetry(),
            "dispatches": int(sum(self._dispatch.values())
                              + sum(self._scan_dispatch.values())),
            "by_bucket": {str(b): int(n)
                          for b, n in sorted(self._dispatch.items())},
            "scan_dispatches": {
                f"b{b}_c{c}": int(n)
                for (b, c), n in sorted(self._scan_dispatch.items())},
            "scan_fused_steps": int(self._scan_steps),
            "compile_s_by_bucket": {
                str(b): float(s)
                for b, s in sorted(compile_by_bucket.items())},
        }

    def finish(self) -> Dict[int, np.ndarray]:
        """Final images (zero-step services: their untouched latent)."""
        pool = np.asarray(self._pool)
        return {k: pool[self._row[k]] for k in self.ids}
