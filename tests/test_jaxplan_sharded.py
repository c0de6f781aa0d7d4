"""Multi-device equivalence suite for ``repro.core.jaxplan.sharded``
(ISSUE 7 tentpole): ``plan_many`` with the scenario axis sharded
across host devices must match the single-device call and the vec
loop within the documented 1e-9 mean-FID tolerance — across device
counts, non-divisible S and empty shards.

The fast CI matrix exports
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so every
parameterization actually runs there; locally without that flag the
multi-device cases skip with a reason saying exactly what to export.
"""

import os

import numpy as np
import pytest

pytest.importorskip("jax")

import jax  # noqa: E402

import repro.core.jaxplan as jaxplan  # noqa: E402
from repro.core import arrays  # noqa: E402
from repro.core.delay_model import DelayModel  # noqa: E402
from repro.core.jaxplan import sharded  # noqa: E402
from repro.core.quality_model import PowerLawFID  # noqa: E402

DELAY = DelayModel()
QUALITY = PowerLawFID()
TOL = 1e-9          # documented mean-FID tolerance (docs/PERFORMANCE.md)

N_DEV = len(jax.devices())


def needs_devices(n):
    """Skip marker whose reason tells the reader how to get n devices."""
    return pytest.mark.skipif(
        N_DEV < n,
        reason=f"needs {n} jax devices, have {N_DEV}: export "
               f"XLA_FLAGS=--xla_force_host_platform_device_count=8 "
               f"before jax initializes (the CI fast matrix does)")


def _instance(S, K, seed=0):
    rng = np.random.default_rng(seed)
    return rng.uniform(5.0, 20.0, size=(S, K))


def _assert_matches(a, b):
    """Sharded-vs-unsharded: same winners, objectives within TOL."""
    assert np.array_equal(a.best_level, b.best_level)
    assert np.array_equal(a.steps, b.steps)
    assert np.max(np.abs(a.mean_fid - b.mean_fid)) < TOL
    assert np.max(np.abs(a.makespan - b.makespan)) < TOL


@pytest.mark.parametrize("n_dev", [
    pytest.param(1, marks=needs_devices(1)),
    pytest.param(2, marks=needs_devices(2)),
    pytest.param(8, marks=needs_devices(8)),
])
@pytest.mark.parametrize("S", [5, 37, 64])
def test_sharded_matches_single_device(n_dev, S):
    """Device counts {1, 2, 8} x S divisible and not: identical plans."""
    taus = _instance(S, K=12, seed=S)
    single = jaxplan.plan_many(taus, delay=DELAY, quality=QUALITY)
    shard = sharded.plan_many_sharded(taus, delay=DELAY,
                                      quality=QUALITY, devices=n_dev)
    assert shard.num_scenarios == S
    _assert_matches(single, shard)


@needs_devices(2)
def test_sharded_matches_vec_loop():
    """The 1e-9 contract holds transitively against the vec engine."""
    S, K = 23, 9
    taus = _instance(S, K, seed=3)
    res = sharded.plan_many_sharded(taus, delay=DELAY, quality=QUALITY,
                                    devices=min(N_DEV, 8))
    ids = list(range(K))
    for s in range(S):
        tp = {i: float(taus[s, i]) for i in ids}
        pv = arrays.stacking_pass_vec(ids, tp, DELAY,
                                      int(res.best_level[s]))
        q = QUALITY.mean_fid([pv.steps_completed[k] for k in ids])
        assert abs(q - res.mean_fid[s]) < TOL


@needs_devices(8)
def test_empty_scenario_shards():
    """S smaller than the device count: whole shards are padding and
    must plan to nothing without disturbing the real rows."""
    taus = _instance(3, K=7, seed=5)
    single = jaxplan.plan_many(taus, delay=DELAY, quality=QUALITY)
    shard = sharded.plan_many_sharded(taus, delay=DELAY,
                                      quality=QUALITY, devices=8)
    assert shard.num_scenarios == 3
    _assert_matches(single, shard)


@needs_devices(2)
def test_valid_mask_and_offsets_shard_correctly():
    """Padding-within-scenario (valid mask) and replan offsets ride
    through the device split unchanged."""
    S, K = 11, 8
    taus = _instance(S, K, seed=7)
    rng = np.random.default_rng(8)
    valid = rng.random((S, K)) < 0.7
    valid[:, 0] = True                      # no all-invalid scenario
    offs = rng.integers(0, 4, size=(S, K))
    kw = dict(delay=DELAY, quality=QUALITY, offsets=offs, valid=valid)
    single = jaxplan.plan_many(taus, **kw)
    shard = sharded.plan_many_sharded(taus, devices=2, **kw)
    _assert_matches(single, shard)


@needs_devices(2)
def test_plan_many_devices_kwarg_dispatches():
    """``plan_many(devices=...)`` routes to the sharded module; int,
    explicit device list and None all mean what resolve_devices says."""
    taus = _instance(10, K=6, seed=9)
    base = jaxplan.plan_many(taus, delay=DELAY, quality=QUALITY)
    by_int = jaxplan.plan_many(taus, delay=DELAY, quality=QUALITY,
                               devices=2)
    by_list = jaxplan.plan_many(taus, delay=DELAY, quality=QUALITY,
                                devices=jax.devices()[:2])
    _assert_matches(base, by_int)
    _assert_matches(base, by_list)


def test_resolve_devices_contract():
    devs = sharded.resolve_devices(None)
    assert len(devs) == N_DEV
    assert sharded.resolve_devices(0) == devs
    assert sharded.resolve_devices(1) == devs[:1]
    assert sharded.resolve_devices(devs[:1]) == devs[:1]
    with pytest.raises(ValueError, match="XLA_FLAGS"):
        sharded.resolve_devices(N_DEV + 1)
    with pytest.raises(ValueError):
        sharded.resolve_devices([])


@needs_devices(2)
def test_sharded_engine_registry_exposure():
    """The engine registry namespace carries plan_many_sharded, so
    registry users reach it the same way they reach plan_many."""
    impl = arrays.engine_impl("jax")
    assert impl.plan_many_sharded is sharded.plan_many_sharded
    taus = _instance(6, K=4, seed=13)
    a = impl.plan_many(taus, delay=DELAY, quality=QUALITY)
    b = impl.plan_many_sharded(taus, delay=DELAY, quality=QUALITY,
                               devices=2)
    _assert_matches(a, b)


def test_ci_exports_host_device_flag():
    """The fast CI matrix must actually run the multi-device cases —
    guard the workflow wiring so they can never silently start
    skipping (ISSUE 7 acceptance)."""
    ci = os.path.join(os.path.dirname(__file__), os.pardir, ".github",
                      "workflows", "ci.yml")
    if not os.path.exists(ci):
        pytest.skip("no CI workflow in this checkout")
    with open(ci) as fh:
        text = fh.read()
    assert "tier1:" in text
    tier1 = text.split("tier1:", 1)[1].split("\n  bench:", 1)[0]
    assert "--xla_force_host_platform_device_count=8" in tier1
