"""One run of one cell: set up, warm up, drive provisioning rounds, check.

A round is K services that arrive together at its start.  The harness
submits it through the system's front door,

    Provisioner(scenario, workload=DiffusionWorkload(cfg, params,
                exec_engine="bucketed"), scheduler=..., allocator=...,
                delay=g, execute_kwargs=...).run(key, execute="closed")

and submits the next one when it returns: one client that waits for
each reply (a closed loop).  The model ``g`` each round plans with is
the mix's ``plan_delay``:

* ``"refit"``: the first round plans with the set-up's calibration and
  every later round with the model the round before it refit
  (``report.execution.delay``), as an operator's calibrate-then-replan
  loop does;
* ``{"a": ..., "b": ...}``: every round plans with that fixed model, so
  that every run plans the same work for the same seed.

The harness times each round on the host clock, from submission to the
moment the images are in hand, notes when the program opened the
round's session (its planning is done by then), and wraps that session
in a ``Recorder`` that logs the session's calls for the reference.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench import reference, spec, traffic, yardstick


def span(name: str, on: bool):
    """A host span in the profiler's trace, where tracing is on."""
    if on:
        import jax
        return jax.profiler.TraceAnnotation(name)
    import contextlib
    return contextlib.nullcontext()


class Recorder:
    """The session a round opens, with its calls logged: batches and
    retargets in order, and the total each service was planned at."""

    def __init__(self, session, plan, annotate: bool):
        self.session = session
        self.initial = {int(k): int(T)
                        for k, T in plan.steps_completed.items()}
        self.events: list = []
        self.annotate = annotate

    def run_batch(self, ks, timed: bool = False) -> float:
        self.events.append(("batch", [int(k) for k in ks]))
        with span("chipbench.batch", self.annotate):
            return self.session.run_batch(ks, timed=timed)

    def retarget(self, totals) -> None:
        self.events.append(("retarget", {int(k): int(v)
                                         for k, v in totals.items()}))
        with span("chipbench.retarget", self.annotate):
            self.session.retarget(totals)

    def finish(self):
        with span("chipbench.finish", self.annotate):
            return self.session.finish()

    def telemetry(self) -> dict:
        return self.session.telemetry()


@dataclasses.dataclass
class Round:
    """What one round sent, got back and cost."""
    index: int
    stream: int
    wall_s: float                 # submission to images in hand
    plan_s: float                 # submission to the session opened
    deadlines: np.ndarray         # s, by service position
    delays: np.ndarray            # wall_s + modelled transmission, s
    steps: np.ndarray             # DDIM steps each service ran
    ok: np.ndarray                # image came back, finite, right shape
    images: Dict[int, np.ndarray]
    initial: Dict[int, int]
    events: list
    batch_sizes: List[int]
    measured_s: List[float]
    replans: int
    telemetry: dict
    refit: object                 # the DelayModel the round ended with
    steps_unaccounted: int

    @property
    def fids(self) -> np.ndarray:
        return np.array([yardstick.fid(int(s)) if ok else
                         yardstick.FID_AT_ZERO
                         for s, ok in zip(self.steps, self.ok)])


class Bench:
    """The cell's system under test, built from the seed."""

    def __init__(self, cell: spec.Cell, seed: int, annotate: bool = False):
        self.cell = cell
        self.seed = int(seed)
        self.mix = cell.mix
        self.sizes = cell.sizes
        self.annotate = annotate
        self.ref = spec.reference(cell.config["name"])
        self.wl = None
        self.g_cal = None
        self.compile_log_len = 0
        self.phase_s: Dict[str, float] = {}

    # -- set-up ------------------------------------------------------------
    def build(self) -> None:
        """Weights on the device from the seed, and the workload."""
        import jax
        from repro.api import DiffusionWorkload
        from repro.configs.ddim_cifar10 import UNetConfig
        fields = {f.name for f in dataclasses.fields(UNetConfig)}
        ucfg = UNetConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                             for k, v in self.sizes.items() if k in fields})
        with span("chipbench.setup.weights", self.annotate):
            params = self.ref.make_params(self.sizes, self.seed,
                                          self.sizes["param_dtype"])
            jax.block_until_ready(params)
        self.wl = DiffusionWorkload(cfg=ucfg, params=params,
                                    exec_engine="bucketed")

    def calibrate(self) -> None:
        """g(X) = aX + b from the step programs at the cell's own batch
        sizes: powers of two up to the round's size.  With K a power of
        two this compiles every program the rounds use."""
        from repro.core.execution import shape_bucket
        top = shape_bucket(int(self.mix["services_per_round"]))
        sizes = [1 << i for i in range(top.bit_length())]
        with span("chipbench.setup.calibrate", self.annotate):
            self.g_cal = self.wl.calibrate(
                traffic.jax_key(self.seed, 2), batch_sizes=sizes,
                reps=int(self.mix["calibration_reps"]))

    def first_model(self):
        """The model the first round plans with (``plan_delay``)."""
        how = self.mix["plan_delay"]
        if isinstance(how, dict):
            from repro.core.delay_model import DelayModel
            return DelayModel(a=float(how["a"]), b=float(how["b"]))
        if how != "refit":
            raise ValueError(f"plan_delay {how!r}: 'refit' or "
                             f"{{'a': .., 'b': ..}}")
        return self.g_cal

    def warm(self) -> None:
        """One round off the record, so that first-use work (eager ops,
        host paths) is done before the window."""
        with span("chipbench.setup.warm", self.annotate):
            self.round(0, self.first_model(), stream=2)

    def setup(self) -> None:
        for phase in (self.build, self.calibrate, self.warm):
            t0 = time.perf_counter()
            phase()
            self.phase_s[phase.__name__] = time.perf_counter() - t0
        self.compile_log_len = len(self.wl.executor.compile_log)

    # -- one round ---------------------------------------------------------
    def round(self, r: int, g, stream: int = 1) -> Round:
        from repro.api import Provisioner
        mix = self.mix
        scn = traffic.round_scenario(mix, self.seed, r, stream)
        key = traffic.jax_key(self.seed, 3, stream, r)
        recs, opened = [], []

        def session(workload, plan, key, **kw):
            opened.append(time.perf_counter())
            with span("chipbench.open_session", self.annotate):
                rec = Recorder(workload.open_session(plan, key, **kw), plan,
                               self.annotate)
            recs.append(rec)
            return rec

        kwargs = dict(mix["execute"], executor=session)
        with span("chipbench.round", self.annotate):
            t0 = time.perf_counter()
            report = Provisioner(
                scn, workload=self.wl, scheduler=mix["scheduler"],
                allocator=mix["allocator"], delay=g,
                execute_kwargs=kwargs).run(key, execute="closed")
            wall = time.perf_counter() - t0
        exe = report.execution
        rec = recs[0]
        shape = (self.sizes["image_size"], self.sizes["image_size"],
                 self.sizes["in_channels"])
        svcs = scn.services
        steps = {o.id: int(o.steps) for o in exe.outcomes}
        # steps each service was last planned at, and steps it ran,
        # from the session's own calls
        planned, ran = dict(rec.initial), {}
        for kind, arg in rec.events:
            if kind == "batch":
                for k in arg:
                    ran[k] = ran.get(k, 0) + 1
            else:
                planned.update(arg)
        unaccounted = abs(sum(r_.size for r_ in exe.records)
                          - sum(ran.values()))
        unaccounted += sum(abs(ran.get(s.id, 0) - steps.get(s.id, -1))
                           + abs(ran.get(s.id, 0) - planned.get(s.id, -1))
                           for s in svcs)
        ok, images = [], {}
        for s in svcs:
            img = exe.content.get(s.id) if exe.content else None
            good = (img is not None and np.shape(img) == shape
                    and bool(np.isfinite(img).all()))
            ok.append(good)
            if good:
                images[s.id] = np.asarray(img)
        # every image is sent once the round returns, all at once, so
        # each over the share the allocator gave it at the round's start
        tx = [yardstick.tx_delay(scn.content_bits,
                                 float(report.allocation[i]),
                                 s.spectral_eff)
              for i, s in enumerate(svcs)]
        return Round(
            index=r, stream=stream, wall_s=wall, plan_s=opened[0] - t0,
            deadlines=np.array([s.deadline for s in svcs]),
            delays=wall + np.array(tx),
            steps=np.array([steps.get(s.id, 0) for s in svcs]),
            ok=np.array(ok), images=images, initial=rec.initial,
            events=rec.events,
            batch_sizes=[r_.size for r_ in exe.records],
            measured_s=[r_.measured_s for r_ in exe.records],
            replans=int(exe.replans),
            telemetry=dict(exe.session_telemetry or {}),
            refit=exe.delay, steps_unaccounted=int(unaccounted))

    # -- the window --------------------------------------------------------
    def window(self, seconds: float, trace_dir: Optional[str] = None):
        """Rounds back to back until ``seconds`` have passed; the last
        round started runs to its end.  Rounds plan as ``plan_delay``
        says.  With ``trace_dir``, the profiler records the whole
        window.  Returns (rounds, window wall seconds)."""
        import jax
        rounds: List[Round] = []
        g = self.first_model()
        chain = self.mix["plan_delay"] == "refit"
        if trace_dir is not None:
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        while not rounds or time.perf_counter() - t0 < seconds:
            rounds.append(self.round(len(rounds), g))
            if chain:
                g = rounds[-1].refit
        wall = time.perf_counter() - t0
        if trace_dir is not None:
            jax.profiler.stop_trace()
        return rounds, wall

    def compiles_since_setup(self) -> int:
        return len(self.wl.executor.compile_log) - self.compile_log_len

    def release(self) -> None:
        """Drop the system's state, so the reference runs on a freed
        chip."""
        self.wl = None
        import gc
        gc.collect()


def end_to_end(rounds: List[Round], wall: float) -> dict:
    """The cell's end-to-end metrics over every service of the window."""
    fids = np.concatenate([r.fids for r in rounds])
    ratio = np.concatenate([r.delays / r.deadlines for r in rounds])
    served = sum(int(r.ok.sum()) for r in rounds)
    return {
        "fid_mean": float(fids.mean()),
        "delay_vs_deadline_p95": float(np.percentile(ratio, 95)),
        "services_per_s": served / wall,
    }


def round_stats(rounds: List[Round]) -> dict:
    """How the window's rounds went, for reading a run by hand."""
    steps = np.concatenate([r.steps for r in rounds])
    walls = np.array([r.wall_s for r in rounds])
    plans = np.array([r.plan_s for r in rounds])
    return {
        "round_wall_s": [float(np.min(walls)), float(np.median(walls)),
                         float(np.max(walls))],
        "steps_p0_p5_p50": [int(np.min(steps)),
                            float(np.percentile(steps, 5)),
                            float(np.median(steps))],
        "services_under_4_steps": int((steps < 4).sum()),
        "replans": int(sum(r.replans for r in rounds)),
        "refit_b_ms": [1e3 * float(np.min([r.refit.b for r in rounds])),
                       1e3 * float(np.max([r.refit.b for r in rounds]))],
        "plan_s": [float(np.min(plans)), float(np.median(plans)),
                   float(np.max(plans))],
    }


def check(bench: Bench, rounds: List[Round], n_sample: int, rows: int,
          control: bool = False, precision: Optional[str] = None) -> dict:
    """Compare a sample of the window's images, drawn from the seed and
    with the longest chain in it, against the reference; count steps the
    rounds do not account for and images that did not come back.

    The reference runs in the configuration's stated dtype and matmul
    precision (``precision`` overrides the latter).  With ``control``,
    the images compared are not the served ones but the reference's own
    in bfloat16 throughout, on the same chains: the next precision below
    the configuration's float32, put in the system's place."""
    import jax.numpy as jnp
    pool = [(ri, k) for ri, r in enumerate(rounds) for k in r.images]
    g = traffic.rng(bench.seed, 5)
    pick = set(int(i) for i in g.choice(len(pool), size=min(n_sample,
                                                           len(pool)),
                                        replace=False)) if pool else set()
    if pool:
        longest = max(range(len(pool)),
                      key=lambda i: (rounds[pool[i][0]].steps[pool[i][1]],
                                     -i))
        pick.add(longest)
    sample = [pool[i] for i in sorted(pick)]
    sz = bench.sizes
    shape = (sz["image_size"], sz["image_size"], sz["in_channels"])
    precision = precision or sz["matmul_precision"]
    chain_of = {}
    starts, chain_list, served = [], [], []
    for ri, k in sample:
        r = rounds[ri]
        if ri not in chain_of:
            chain_of[ri] = reference.chains(r.initial, r.events)
        key = traffic.jax_key(bench.seed, 3, r.stream, r.index)
        ids = sorted(r.initial)
        starts.append(reference.initial_noise(key, len(ids), ids.index(k),
                                              shape))
        chain_list.append(chain_of[ri][k])
        served.append(r.images[k])
    starts = [jnp.asarray(s) for s in starts]
    want = reference.run_chains(bench.ref, sz, bench.seed, starts,
                                chain_list, dtype=sz["dtype"],
                                precision=precision, rows=rows)
    if control:
        served = reference.run_chains(bench.ref, sz, bench.seed, starts,
                                      chain_list, dtype="bfloat16",
                                      precision=precision, rows=rows)
    errs = [reference.rel_err(s, w) for s, w in zip(served, want)]
    return {
        "image_rel_err_max": max(errs) if errs else float("inf"),
        "image_rel_err_mean": float(np.mean(errs)) if errs else float("inf"),
        "images_missing": int(sum(int((~r.ok).sum()) for r in rounds)),
        "steps_unaccounted": int(sum(r.steps_unaccounted for r in rounds)),
        "images_checked": len(errs),
        "steps_checked": int(sum(len(c) for c in chain_list)),
    }
