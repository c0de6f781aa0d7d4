"""Workload registry entries.

``DiffusionWorkload`` wraps the DDIM ``BatchDenoisingExecutor`` (the
paper's image-generation workload); ``DecodeWorkload`` wraps the LLM
``ServingEngine`` decode path (one denoising task == one decode
token).  Both satisfy the ``Workload`` protocol, so a
``Provisioner`` drives either through the identical
allocate -> schedule -> execute pipeline.

Model construction is lazy: importing this module (e.g. just to list
registry names) never touches jax or initializes parameters.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import numpy as np

from repro.api.protocols import WorkloadOutput
from repro.api.registry import register_workload
from repro.core.delay_model import DelayModel
from repro.core.plan import BatchPlan
from repro.core.quality_model import PowerLawFID, QualityModel


@register_workload("diffusion")
class DiffusionWorkload:
    """Batch denoising on a DDIM denoiser (the paper's workload): the
    U-Net or a DiT, as ``cfg.arch`` says (default: the smoke U-Net)."""

    name = "diffusion"

    def __init__(self, cfg=None, params=None, executor=None,
                 init_seed: int = 0,
                 exec_engine: Optional[str] = None):
        # accepted only because chipbench/bench.py passes
        # exec_engine="bucketed"; goes with the next benchmark change
        if exec_engine not in (None, "bucketed"):
            raise ValueError(f"unknown exec_engine {exec_engine!r}: "
                             f"every session runs on the pool")
        self.cfg = cfg
        self.params = params
        self._executor = executor
        self.init_seed = init_seed

    def _ex(self):
        if self._executor is None:
            import jax
            from repro.configs.ddim_cifar10 import SMOKE
            from repro.diffusion.executor import BatchDenoisingExecutor, \
                denoiser
            from repro.models.params import init_params
            cfg = self.cfg if self.cfg is not None else SMOKE
            params = self.params
            if params is None:
                params = init_params(denoiser(cfg).schema(cfg),
                                     jax.random.PRNGKey(self.init_seed))
            self.cfg, self.params = cfg, params
            self._executor = BatchDenoisingExecutor(cfg, params)
        return self._executor

    @property
    def executor(self):
        """The ``BatchDenoisingExecutor``, built on first use."""
        return self._ex()

    def default_delay(self) -> DelayModel:
        return DelayModel()                    # paper's RTX-3050 constants

    def default_quality(self) -> QualityModel:
        return PowerLawFID()

    def measure_delay_curve(self, key: Optional[Any] = None,
                            batch_sizes: Sequence[int] = (1, 2, 4, 8),
                            reps: int = 3):
        """Fig. 1a raw data: steady-state per-step delay vs batch size,
        each size read at its padded bucket.  Compile time never lands
        in the readings; the executor's ``last_compile_log`` carries it
        separately."""
        import jax
        key = key if key is not None else jax.random.PRNGKey(1)
        return self._ex().measure_delay_curve(key, batch_sizes=batch_sizes,
                                              reps=reps)

    def calibrate(self, key: Optional[Any] = None, *,
                  batch_sizes: Sequence[int] = (1, 2, 4, 8),
                  reps: int = 3) -> DelayModel:
        """Fit g(X) = aX + b to the measured curve, with the floors of
        ``DelayModel.refit`` (a > 0, b > 0): the planners divide by a,
        and a fast chip's nearly flat curve can fit a slightly negative
        coefficient."""
        curve = self.measure_delay_curve(key, batch_sizes, reps)
        return DelayModel().refit([c[0] for c in curve],
                                  [c[1] for c in curve])

    def execute(self, plan: BatchPlan, key: Optional[Any] = None,
                *, timed: bool = False) -> WorkloadOutput:
        import jax
        key = key if key is not None else jax.random.PRNGKey(0)
        images, timings = self._ex().run(plan, key, timed=timed)
        return WorkloadOutput(content=images, timings=timings)

    def open_session(self, plan: BatchPlan, key: Optional[Any] = None):
        """Stepwise execution handle (EXECUTORS registry entry): the
        closed loop in ``repro.core.execution`` drives batches itself."""
        import jax
        key = key if key is not None else jax.random.PRNGKey(0)
        return self._ex().open_session(plan, key)


@register_workload("llm_decode")
class DecodeWorkload:
    """Deadline-aware autoregressive decoding on the ServingEngine."""

    name = "llm_decode"

    def __init__(self, cfg=None, params=None, run=None,
                 max_len: int = 128, prompt_len: int = 8,
                 arch: str = "tinyllama-1.1b", engine=None,
                 init_seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.run = run
        self.max_len = max_len
        self.prompt_len = prompt_len
        self.arch = arch
        self._engine = engine
        self.init_seed = init_seed

    def _eng(self):
        if self._engine is None:
            import jax
            from repro.config import RunConfig, get_config, smoke_variant
            from repro.models import api as models_api
            from repro.serving.engine import ServingEngine
            cfg = self.cfg
            if cfg is None:
                cfg = smoke_variant(get_config(self.arch))
            params = self.params
            if params is None:
                params = models_api.init_model(
                    cfg, jax.random.PRNGKey(self.init_seed))
            run = self.run if self.run is not None else RunConfig()
            self.cfg, self.params, self.run = cfg, params, run
            self._engine = ServingEngine(cfg, params, run, self.max_len,
                                         delay=self.default_delay())
        return self._engine

    def default_delay(self) -> DelayModel:
        return DelayModel(a=0.002, b=0.02)     # CPU-scale decode constants

    def default_quality(self) -> QualityModel:
        from repro.serving.engine import TokenQuality
        return TokenQuality()

    def calibrate(self, key: Optional[Any] = None, *,
                  batch_sizes: Sequence[int] = (1, 2, 4),
                  reps: int = 2) -> DelayModel:
        return self._eng().measure_decode_delay(batch_sizes=batch_sizes,
                                                reps=reps)

    def _prompt(self, service_id: int, vocab: int) -> np.ndarray:
        rng = np.random.default_rng(self.init_seed * 7919 + service_id)
        return rng.integers(0, vocab, self.prompt_len).astype(np.int32)

    def _load_requests(self, plan: BatchPlan) -> None:
        from repro.serving.engine import Request
        eng = self._eng()
        top = max(plan.steps_completed.values(), default=0)
        if self.prompt_len + top > self.max_len:
            raise ValueError(
                f"plan wants {top} tokens but max_len={self.max_len} "
                f"leaves room for {self.max_len - self.prompt_len}; "
                f"raise max_len or tighten deadlines")
        eng.requests.clear()
        for k in sorted(plan.steps_completed):
            eng.requests[k] = Request(
                id=k, prompt=self._prompt(k, eng.cfg.vocab_size),
                deadline=float("inf"))

    def execute(self, plan: BatchPlan, key: Optional[Any] = None,
                *, timed: bool = False) -> WorkloadOutput:
        self._load_requests(plan)
        eng = self._eng()
        out = eng.execute(plan, sample_key=key, timed=timed)
        return WorkloadOutput(content={k: list(v) for k, v in out.items()},
                              timings=list(eng.last_timings))

    def open_session(self, plan: BatchPlan, key: Optional[Any] = None):
        """Stepwise decode handle (EXECUTORS registry entry); ``key`` is
        unused — decoding is greedy argmax."""
        self._load_requests(plan)
        return self._eng().open_session(plan)
