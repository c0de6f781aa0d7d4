"""Hypothesis properties for the jit-compiled jax planner engine,
mirroring tests/test_arrays_properties.py with the jax engine's
contract: for ARBITRARY inputs the objective must match the NumPy
reference within the documented tolerance (docs/PERFORMANCE.md) and
the returned plan must satisfy the paper's constraints.  Bit identity
is *not* asserted — XLA reassociation can flip exactly-tied candidate
choices.  Skipped when hypothesis or jax is missing.

Budgets are kept small (tau' <= 4) so the candidate-level axis stays
within a couple of jit shape buckets — the suite pays a handful of
compiles, not one per example.
"""

import jax
import numpy as np
import pytest

pytest.importorskip("hypothesis")
pytest.importorskip("jax")

from hypothesis import given, settings, strategies as st

import repro.core.jaxplan as jaxplan
from repro.core import arrays
from repro.core.delay_model import DelayModel
from repro.core.offset import StackingOffset
from repro.core.online import _OffsetQuality
from repro.core.quality_model import PowerLawFID
from repro.core.service import ServiceRequest
from repro.core.stacking import stacking

DELAY = DelayModel()          # paper constants
QUALITY = PowerLawFID()
TOL = 1e-9                    # documented objective tolerance


def _services(taus):
    return [ServiceRequest(id=i, deadline=t, spectral_eff=7.0)
            for i, t in enumerate(taus)]


def _tau_prime(taus):
    return {i: t for i, t in enumerate(taus)}


def _fid(plan, ids, oq=QUALITY):
    return oq.mean_fid([plan.steps_completed[k] for k in ids])


taus_strategy = st.lists(
    st.floats(min_value=0.05, max_value=4.0, allow_nan=False,
              allow_infinity=False),
    min_size=1, max_size=8)


@settings(max_examples=25, deadline=None)
@given(taus=taus_strategy)
def test_full_search_jax_matches_vec(taus):
    svcs, tp = _services(taus), _tau_prime(taus)
    ids = list(range(len(taus)))
    vec = stacking(svcs, tp, DELAY, QUALITY, engine="vec")
    jx = stacking(svcs, tp, DELAY, QUALITY, engine="jax")
    assert abs(_fid(vec, ids) - _fid(jx, ids)) < TOL
    jx.validate(gen_deadlines=tp)   # and the paper's constraints hold


@settings(max_examples=20, deadline=None)
@given(taus=taus_strategy, data=st.data())
def test_offset_scheduler_jax_matches_vec(taus, data):
    svcs, tp = _services(taus), _tau_prime(taus)
    ids = list(range(len(taus)))
    offs = [data.draw(st.integers(0, 8)) for _ in taus]
    pv = StackingOffset("vec").plan(svcs, tp, DELAY, QUALITY, offs)
    pj = StackingOffset("jax").plan(svcs, tp, DELAY, QUALITY, offs)
    oq = _OffsetQuality(QUALITY, offs)
    oq.refresh_doomed(svcs, tp)
    assert abs(_fid(pv, ids, oq) - _fid(pj, ids, oq)) < TOL


@settings(max_examples=20, deadline=None)
@given(taus=taus_strategy)
def test_equal_steps_jax_matches_vec(taus):
    from repro.api.schedulers import equal_steps
    svcs, tp = _services(taus), _tau_prime(taus)
    ids = list(range(len(taus)))
    ref = arrays.equal_steps_vec(svcs, tp, DELAY, QUALITY)
    with arrays.engine_scope("jax"):
        jx = equal_steps(svcs, tp, DELAY, QUALITY)
    assert abs(_fid(ref, ids) - _fid(jx, ids)) < TOL
    jx.validate(gen_deadlines=tp)


# -- the sort-free per-round selection (ISSUE 7) ------------------------
#
# kernels._select_kth_key replaced the full composite-key sort inside
# the clustered sweep.  Its decision contract: for composite keys
# ``Tp * M + tie`` (tie a permutation of 0..K-1, so keys are unique
# even when every Tp collides) it returns exactly the x_n-th smallest
# key — the batching threshold — for EVERY x_n in 1..n_active.  The
# instances below are adversarially tie-heavy: Tp drawn from a tiny
# value set so most keys differ only in their tie rank.

@settings(max_examples=30, deadline=None)
@given(data=st.data())
def test_radix_select_matches_full_sort_on_tie_heavy_keys(data):
    from repro.core.jaxplan import kernels
    import jax.numpy as jnp

    K = data.draw(st.integers(2, 24))
    L = data.draw(st.integers(1, 4))
    # duplicate-heavy Tp rows: values from a set much smaller than K
    tp_vals = data.draw(st.lists(st.integers(0, 6), min_size=1,
                                 max_size=3))
    rng = np.random.default_rng(data.draw(st.integers(0, 2**31)))
    Tp = rng.choice(tp_vals, size=(L, K)).astype(np.int64)
    tie = rng.permutation(K).astype(np.int64)     # permuted tie ranks
    M = np.int64(1) << np.int64(max(K, 1).bit_length())
    key_np = Tp * M + tie[None, :]
    key_bits = int((int(key_np.max()) + 1).bit_length())

    # every batch size x_n in 1..K for every row, as one stacked call
    key_all = np.repeat(key_np, K, axis=0)        # (L*K, K)
    x_all = np.tile(np.arange(1, K + 1, dtype=np.int64), L)
    with jax.enable_x64(True):
        key = jnp.asarray(key_all)
        x_n = jnp.asarray(x_all)
        sel = np.asarray(kernels._select_kth_key(key, x_n, key_bits))
        ref = np.asarray(kernels._sort_kth_key(key, x_n))
    assert np.array_equal(sel, ref)
    # and the decision it feeds — the round's membership set — is
    # identical too
    assert np.array_equal(key_all <= sel[:, None],
                          key_all <= ref[:, None])


@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_tie_heavy_stacking_jax_matches_vec(data):
    """End to end: budgets drawn from a tiny value set (maximal tau'
    ties -> maximal tie-break pressure on the selection) still meet
    the engine contract."""
    vals = data.draw(st.lists(
        st.floats(min_value=0.5, max_value=4.0, allow_nan=False),
        min_size=1, max_size=2))
    taus = [vals[i % len(vals)]
            for i in range(data.draw(st.integers(2, 10)))]
    svcs, tp = _services(taus), _tau_prime(taus)
    ids = list(range(len(taus)))
    vec = stacking(svcs, tp, DELAY, QUALITY, engine="vec")
    jx = stacking(svcs, tp, DELAY, QUALITY, engine="jax")
    assert abs(_fid(vec, ids) - _fid(jx, ids)) < TOL
    jx.validate(gen_deadlines=tp)


@settings(max_examples=15, deadline=None)
@given(scenarios=st.lists(taus_strategy, min_size=1, max_size=6))
def test_plan_many_matches_per_scenario_vec(scenarios):
    K = max(len(t) for t in scenarios)
    S = len(scenarios)
    taus = np.zeros((S, K))
    valid = np.zeros((S, K), dtype=bool)
    for s, row in enumerate(scenarios):
        taus[s, :len(row)] = row
        valid[s, :len(row)] = True
    res = jaxplan.plan_many(taus, delay=DELAY, quality=QUALITY,
                            valid=valid)
    for s, row in enumerate(scenarios):
        tp = _tau_prime(row)
        ids = list(range(len(row)))
        pv = arrays.stacking_vec(_services(row), tp, DELAY, QUALITY)
        assert abs(_fid(pv, ids) - res.mean_fid[s]) < TOL
