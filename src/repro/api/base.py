"""Shared facade machinery: one resolution path for the common kwargs.

Every facade — ``Provisioner``, ``OnlineProvisioner``,
``MultiServerProvisioner``, ``FleetProvisioner`` — derives from
``BaseProvisioner`` and accepts the same keyword set:

    engine=    planning-engine pin ("vec"/"scalar"/"jax",
               repro.core.arrays; None = process default)
    devices=   device list for sharded jax planning (consumed by the
               fleet/jax-batched paths; harmless elsewhere)
    seed=      one deterministic seed: injected into the allocator's
               kwargs when its signature takes ``seed`` and used as the
               default PRNG key for workload execution (fleet scenarios
               adopt it as their arrival seed)
    execute=   default execution mode for ``run()``:
               False/None (analytic), True (legacy one-shot workload
               execution), "open" (ExecutionLoop, no replanning) or
               "closed" (ExecutionLoop with drift-triggered replanning)

``execute_kwargs`` passes loop tuning through to ``execute_plan``
(``window``, ``drift_tol``, ...).

``provision(scenario, ...)`` is the single front door: it dispatches on
scenario shape (fleet / multi-server / online / static) and reproduces
the corresponding facade's ``run()`` output exactly.

The pre-unification positional constructor signatures still work
through ``_legacy_positionals`` (a ``DeprecationWarning`` shim,
test-enforced in tests/test_facades.py).
"""

from __future__ import annotations

import inspect
import warnings
from typing import Any, Dict, Optional, Tuple

import numpy as np

from repro.api.registry import ALLOCATORS

EXECUTE_MODES = (None, False, True, "open", "closed")


def jsonable(v):
    """Recursively convert numpy scalars/arrays so ``to_dict`` output
    survives ``json.dumps`` round-trips."""
    if isinstance(v, dict):
        return {str(k): jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [jsonable(x) for x in v]
    if isinstance(v, np.ndarray):
        return [jsonable(x) for x in v.tolist()]
    if isinstance(v, (np.floating,)):
        return float(v)
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.bool_,)):
        return bool(v)
    return v


def report_dict(kind: str, *, mean_fid: float, outage_rate: float,
                makespan: Optional[float] = None,
                components: Optional[Dict[str, str]] = None,
                telemetry: Optional[dict] = None, **extra) -> dict:
    """The common report ``to_dict`` protocol: every report kind carries
    at least kind / mean_fid / outage_rate / makespan / components /
    telemetry (JSON-serializable; benchmarks consume this instead of
    hand-picking fields)."""
    out = {
        "kind": kind,
        "mean_fid": None if mean_fid is None or np.isnan(mean_fid)
        else float(mean_fid),
        "outage_rate": float(outage_rate),
        "makespan": None if makespan is None else float(makespan),
        "components": {k: str(v) for k, v in (components or {}).items()},
        "telemetry": jsonable(telemetry or {}),
    }
    out.update(jsonable(extra))
    return out


class BaseProvisioner:
    """Common constructor surface + helpers for the four facades."""

    # pre-unification positional order (after ``scenario``) and the
    # defaults those parameters had — drives the deprecation shim
    _LEGACY: Tuple[str, ...] = ()
    _LEGACY_DEFAULTS: Dict[str, Any] = {}

    def __init__(self, scenario, *, engine: Optional[str] = None,
                 devices=None, seed: Optional[int] = None,
                 execute=None, execute_kwargs: Optional[dict] = None):
        self.scenario = scenario
        self.engine = engine
        self.devices = devices
        self.seed = seed
        self.execute_default = self._check_execute(execute)
        self.execute_kwargs = dict(execute_kwargs or {})

    @staticmethod
    def _check_execute(execute):
        if execute not in EXECUTE_MODES:
            raise ValueError(
                f"execute must be one of {EXECUTE_MODES}, got "
                f"{execute!r}")
        return execute

    def _resolve_execute(self, execute):
        """run(execute=None) falls back to the constructor default —
        the one resolution path for the knob."""
        if execute is None:
            return self.execute_default
        return self._check_execute(execute)

    @classmethod
    def _legacy_positionals(cls, args: tuple, given: Dict[str, Any]) \
            -> Dict[str, Any]:
        """Deprecation shim: map old positional component arguments
        onto their keywords.  ``given`` holds the keyword values as
        received so positional/keyword duplicates fail loudly."""
        if not args:
            return given
        names = cls._LEGACY
        if len(args) > len(names):
            raise TypeError(
                f"{cls.__name__}() takes at most {1 + len(names)} "
                f"positional arguments ({1 + len(args)} given)")
        shown = ", ".join(names[:len(args)])
        warnings.warn(
            f"positional {cls.__name__}(scenario, {shown}) is "
            f"deprecated; pass component arguments as keywords",
            DeprecationWarning, stacklevel=3)
        out = dict(given)
        for name, val in zip(names, args):
            default = cls._LEGACY_DEFAULTS.get(name)
            if given.get(name, default) != default:
                raise TypeError(
                    f"{cls.__name__}() got multiple values for "
                    f"argument '{name}'")
            out[name] = val
        return out

    # -- seed resolution --------------------------------------------------

    def _seeded_kwargs(self, allocator, kwargs: Optional[dict]) -> dict:
        """Inject ``seed=`` into the allocator's kwargs when its
        signature takes one (PSO etc.) and the caller didn't pin it."""
        kwargs = dict(kwargs or {})
        if self.seed is None or "seed" in kwargs:
            return kwargs
        try:
            params = inspect.signature(
                ALLOCATORS.resolve(allocator)).parameters
        except (TypeError, ValueError):
            params = {}
        if "seed" in params:
            kwargs["seed"] = int(self.seed)
        return kwargs

    def _resolve_key(self, key):
        """Default PRNG key for workload execution from ``seed=``."""
        if key is not None or self.seed is None:
            return key
        import jax
        return jax.random.PRNGKey(int(self.seed))


def provision(scenario, **kwargs):
    """The unified front door: dispatch on scenario shape and run.

    * ``FleetScenario``                      -> ``FleetProvisioner``
    * multi-server ``Scenario`` + arrivals/admission/handoff
                                             -> ``MultiServerProvisioner.run_online``
    * multi-server ``Scenario``              -> ``MultiServerProvisioner.run``
    * arrivals over time or ``admission=``   -> ``OnlineProvisioner``
    * static single-server ``Scenario``      -> ``Provisioner``

    Remaining keyword arguments split automatically between the chosen
    facade's constructor and its ``run()``; the result is exactly what
    calling that facade directly would return (test-enforced).
    """
    from repro.api.fleet import FleetProvisioner
    from repro.api.multiserver import MultiServerProvisioner
    from repro.api.online import OnlineProvisioner
    from repro.api.provisioner import Provisioner
    from repro.core.fleet import FleetScenario

    kw = dict(kwargs)

    def split(*run_keys):
        return {k: kw.pop(k) for k in run_keys if k in kw}

    if isinstance(scenario, FleetScenario):
        run_kw = split("mode", "epoch", "placement", "reservoir")
        return FleetProvisioner(scenario, **kw).run(**run_kw)

    dynamic = (not scenario.is_static or "admission" in kw
               or "admission_kwargs" in kw or "handoff" in kw
               or "online_placement" in kw)
    if scenario.n_servers > 1:
        if dynamic:
            run_kw = split("admission", "online_placement",
                           "admission_kwargs", "handoff", "validate")
            return MultiServerProvisioner(scenario, **kw) \
                .run_online(**run_kw)
        run_kw = split("assignment", "validate")
        return MultiServerProvisioner(scenario, **kw).run(**run_kw)
    if dynamic:
        run_kw = split("validate", "execute", "key")
        return OnlineProvisioner(scenario, **kw).run(**run_kw)
    run_kw = split("key", "execute", "timed", "calibrate", "refit",
                   "validate")
    return Provisioner(scenario, **kw).run(**run_kw)
