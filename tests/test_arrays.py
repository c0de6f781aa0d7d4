"""Array-native planning engine: bit-identical equivalence suite.

The contract of ``repro.core.arrays`` (ISSUE 5) is that the vectorized
kernels return exactly the scalar reference's plans — same batches,
same start times, same ``steps_completed``, same objective — across
every planning entry point: the raw pass, the T* search, the balanced
baseline, the offset-native replanner, and the full online / offset /
multi-server pipelines.  ``assert_plans_equal`` compares with ``==``
on floats on purpose: "close enough" is not the bar.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import arrays
from repro.core.arrays import (ServiceArrays, engine_scope,
                               equal_steps_vec, first_best, get_engine,
                               offset_pass_vec, set_engine,
                               stacking_pass_vec, sweep_clustered,
                               sweep_lockstep)
from repro.core.delay_model import DelayModel
from repro.core.multiserver import provision_multi, simulate_online_multi
from repro.core.offset import StackingOffset, offset_pass
from repro.core.online import simulate_online
from repro.core.quality_model import PowerLawFID
from repro.core.service import make_scenario
from repro.core.stacking import stacking, stacking_pass

DELAY = DelayModel()          # paper constants
QUALITY = PowerLawFID()


def assert_plans_equal(a, b):
    assert a.batches == b.batches
    assert a.start_times == b.start_times
    assert a.steps_completed == b.steps_completed
    assert a.makespan() == b.makespan()


def _tau_prime(scn, slack):
    return {s.id: s.deadline - slack for s in scn.services}


# ---------------------------------------------------------------------------
# Engine selection
# ---------------------------------------------------------------------------

class TestEngineToggle:
    def test_default_is_vec(self):
        assert get_engine() == "vec"

    def test_set_and_scope(self):
        assert get_engine() == "vec"
        with engine_scope("scalar"):
            assert get_engine() == "scalar"
            with engine_scope(None):          # None = leave as-is
                assert get_engine() == "scalar"
        assert get_engine() == "vec"
        set_engine("scalar")
        try:
            assert get_engine() == "scalar"
        finally:
            set_engine("vec")

    def test_invalid_engine_rejected(self):
        with pytest.raises(ValueError):
            set_engine("gpu")
        with pytest.raises(ValueError):
            arrays.resolve_engine("turbo")
        with pytest.raises(ValueError):
            stacking(make_scenario(K=2, seed=0).services,
                     {0: 5.0, 1: 5.0}, DELAY, QUALITY, engine="nope")

    def test_env_var_sets_process_default(self):
        env = dict(os.environ, REPRO_PLANNER_ENGINE="scalar",
                   PYTHONPATH="src")
        out = subprocess.run(
            [sys.executable, "-c",
             "from repro.core import arrays; print(arrays.get_engine())"],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(__file__)))
        assert out.stdout.strip() == "scalar", out.stderr

    def test_bad_env_var_fails_loudly(self):
        env = dict(os.environ, REPRO_PLANNER_ENGINE="typo",
                   PYTHONPATH="src")
        out = subprocess.run(
            [sys.executable, "-c", "import repro.core.arrays"],
            capture_output=True, text=True, env=env,
            cwd=os.path.dirname(os.path.dirname(__file__)))
        assert out.returncode != 0
        assert "REPRO_PLANNER_ENGINE" in out.stderr


# ---------------------------------------------------------------------------
# Kernel-level equivalence: passes and sweeps
# ---------------------------------------------------------------------------

class TestPassEquivalence:
    def test_stacking_pass_grid(self):
        rng = np.random.default_rng(0)
        for seed in range(8):
            for K in (1, 3, 8, 20):
                scn = make_scenario(K=K, seed=seed)
                tp = _tau_prime(scn, float(rng.uniform(0, 2)))
                ids = [s.id for s in scn.services]
                for t_star in (1, 2, 5, 13, 40):
                    assert_plans_equal(
                        stacking_pass(ids, tp, DELAY, t_star),
                        stacking_pass_vec(ids, tp, DELAY, t_star))

    def test_stacking_pass_with_offsets(self):
        rng = np.random.default_rng(1)
        for seed in range(8):
            scn = make_scenario(K=10, tau_min=3.0, tau_max=9.0, seed=seed)
            tp = _tau_prime(scn, 0.5)
            ids = [s.id for s in scn.services]
            off = {k: int(rng.integers(0, 9)) for k in ids}
            for t_star in (1, 4, 9, 22):
                assert_plans_equal(
                    stacking_pass(ids, tp, DELAY, t_star, offsets=off),
                    stacking_pass_vec(ids, tp, DELAY, t_star,
                                      offsets=off))

    def test_tight_deadlines_and_infeasible(self):
        for seed in range(6):
            scn = make_scenario(K=6, tau_min=0.05, tau_max=2.5, seed=seed)
            tp = _tau_prime(scn, 0.3)       # some tau' go negative
            ids = [s.id for s in scn.services]
            for t_star in (0, 1, 3, 7):     # 0 = the degenerate branch
                assert_plans_equal(
                    stacking_pass(ids, tp, DELAY, t_star),
                    stacking_pass_vec(ids, tp, DELAY, t_star))

    def test_zero_services(self):
        """The drop-in contract covers the empty set: both passes
        return an empty plan instead of crashing on an empty
        reduction."""
        assert_plans_equal(stacking_pass([], {}, DELAY, 1),
                           stacking_pass_vec([], {}, DELAY, 1))
        assert stacking_pass_vec([], {}, DELAY, 1).batches == []

    def test_equal_deadline_ties(self):
        """Equal deadlines force Tp AND tau' ties — the id tie-break
        must match the scalar sort exactly."""
        for taus in ([10.0] * 8, [3.0, 3.0, 3.0, 15.0], [5.0] * 6):
            tp = {i: t for i, t in enumerate(taus)}
            ids = list(tp)
            for t_star in (1, 3, 9):
                assert_plans_equal(
                    stacking_pass(ids, tp, DELAY, t_star),
                    stacking_pass_vec(ids, tp, DELAY, t_star))

    def test_offset_pass_targets(self):
        rng = np.random.default_rng(2)
        for seed in range(8):
            scn = make_scenario(K=9, tau_min=2.0, tau_max=8.0, seed=seed)
            tp = _tau_prime(scn, 0.4)
            ids = [s.id for s in scn.services]
            targets = {k: int(rng.integers(0, 12)) for k in ids}
            assert_plans_equal(
                offset_pass(ids, tp, DELAY, targets),
                offset_pass_vec(ids, tp, DELAY, targets))

    def test_sweep_rows_match_single_passes(self):
        """Every row of the batched sweep equals the standalone pass for
        that level — candidates in a batch can't contaminate each
        other."""
        scn = make_scenario(K=12, seed=3)
        tp = _tau_prime(scn, 0.6)
        ids = [s.id for s in scn.services]
        off = {k: k % 4 for k in ids}
        arr = ServiceArrays.build(ids, tp, off)
        levels = list(range(1, 31))
        Tc, ms = sweep_clustered(arr, DELAY, levels)
        for i, level in enumerate(levels):
            plan = stacking_pass(ids, tp, DELAY, level, offsets=off)
            assert [plan.steps_completed[k] for k in ids] == \
                Tc[i].tolist()
            assert plan.makespan() == float(ms[i])
        targets = np.maximum(
            np.asarray(levels)[:, None] - arr.offsets[None, :], 0)
        Tc2, ms2 = sweep_lockstep(arr, DELAY, targets)
        for i, level in enumerate(levels):
            tgt = {k: max(0, level - off[k]) for k in ids}
            plan = offset_pass(ids, tp, DELAY, tgt)
            assert [plan.steps_completed[k] for k in ids] == \
                Tc2[i].tolist()
            assert plan.makespan() == float(ms2[i])


#: (K, deadline range in s, largest offset, delay) of a sweep whose
#: rows finish rounds apart, so its working set is compacted
COMPACT_CASES = {
    # the chip's g at K = 128, deadlines spread like a dense round
    "dense-k128": (128, (0.160, 0.550), 0,
                   DelayModel(a=1.8e-4, b=8.84e-4)),
    "uniform-k32": (32, (0.300, 0.300), 0,
                    DelayModel(a=1.8e-4, b=8.84e-4)),
    "offsets-k24": (24, (3.0, 9.0), 8, DELAY),
}


@pytest.mark.parametrize("case", sorted(COMPACT_CASES))
def test_compacted_sweep_matches_scalar(case):
    """Levels that leave the sweep's working set early, late or never
    keep the scalar pass's counts, makespan, batches and start times."""
    K, (lo, hi), off_max, delay = COMPACT_CASES[case]
    rng = np.random.default_rng(14)
    ids = list(range(K))
    tp = {k: float(d) for k, d in zip(ids, rng.uniform(lo, hi, K))}
    off = {k: int(o) for k, o in zip(ids, rng.integers(0, off_max + 1, K))}
    arr = ServiceArrays.build(ids, tp, off)
    level_max = max(off[k] + delay.max_steps(tp[k]) for k in ids)
    levels = np.arange(1, level_max + 1, dtype=np.int64)
    hist: list = []
    Tc, ms, _, _ = arrays._clustered_rounds(
        arr.ids, arr.tau_prime, arr.offsets, delay, levels, history=hist)

    first_rows = next(rows for rows, *_ in hist if rows.size < levels.size)
    dropped = np.setdiff1d(levels - 1, first_rows)
    picks = {0, levels.size - 1, first_best(Tc, QUALITY)[0],
             int(hist[-1][0][-1]), int(dropped[-1])}
    for i in sorted(picks):
        ref = stacking_pass(ids, tp, delay, int(levels[i]), offsets=off)
        assert Tc[i].tolist() == [ref.steps_completed[k] for k in ids]
        assert float(ms[i]) == ref.makespan()
        batches, starts = arrays._replay_clustered(arr.ids, i, hist, delay)
        assert batches == ref.batches and starts == ref.start_times
        assert_plans_equal(ref, stacking_pass_vec(ids, tp, delay,
                                                  int(levels[i]),
                                                  offsets=off))


class TestSearchEquivalence:
    def test_stacking_full_search(self):
        for seed in range(10):
            for K in (1, 4, 12, 24):
                scn = make_scenario(K=K, seed=seed)
                tp = _tau_prime(scn, 0.7)
                assert_plans_equal(
                    stacking(scn.services, tp, DELAY, QUALITY,
                             engine="scalar"),
                    stacking(scn.services, tp, DELAY, QUALITY,
                             engine="vec"))

    def test_equal_steps_search(self):
        from repro.api.schedulers import equal_steps
        for seed in range(8):
            scn = make_scenario(K=9, seed=seed)
            tp = _tau_prime(scn, 0.8)
            with engine_scope("scalar"):
                ref = equal_steps(scn.services, tp, DELAY, QUALITY)
            assert_plans_equal(
                ref, equal_steps_vec(scn.services, tp, DELAY, QUALITY))

    def test_first_best_matches_linear_scan(self):
        rng = np.random.default_rng(4)
        rows = rng.integers(0, 6, (40, 5))
        rows[7] = rows[3]                    # force duplicates
        best_i, best_q = first_best(rows, QUALITY)
        ref_i, ref_q = -1, float("inf")
        for i, counts in enumerate(rows.tolist()):
            q = QUALITY.mean_fid(counts)
            if q < ref_q - 1e-12:
                ref_i, ref_q = i, q
        assert (best_i, best_q) == (ref_i, ref_q)

    def test_registry_stacking_scalar_reference(self):
        from repro.api.registry import get_scheduler
        scn = make_scenario(K=6, seed=5)
        tp = _tau_prime(scn, 0.5)
        assert_plans_equal(
            get_scheduler("stacking_scalar")(scn.services, tp, DELAY,
                                             QUALITY),
            get_scheduler("stacking")(scn.services, tp, DELAY, QUALITY))


# ---------------------------------------------------------------------------
# Offset-native replanner equivalence
# ---------------------------------------------------------------------------

class TestOffsetEquivalence:
    def test_plan_with_progress(self):
        rng = np.random.default_rng(5)
        sc, ve = StackingOffset("scalar"), StackingOffset("vec")
        for seed in range(10):
            for K in (1, 2, 5, 12):
                for window in ((3.0, 8.0), (0.3, 2.0), (7.0, 20.0)):
                    scn = make_scenario(K=K, tau_min=window[0],
                                        tau_max=window[1], seed=seed)
                    tp = _tau_prime(scn, float(rng.uniform(0, 1.5)))
                    offs = [int(x) for x in rng.integers(0, 9, K)]
                    assert_plans_equal(
                        sc.plan(scn.services, tp, DELAY, QUALITY, offs),
                        ve.plan(scn.services, tp, DELAY, QUALITY, offs))

    def test_doomed_services(self):
        """A partially-generated service with a negative residual budget
        scores fid(0) — the doomed rule must bind identically."""
        sc, ve = StackingOffset("scalar"), StackingOffset("vec")
        scn = make_scenario(K=5, tau_min=3.0, tau_max=8.0, seed=6)
        tp = _tau_prime(scn, 0.5)
        tp[scn.services[0].id] = -0.5
        offs = [3, 0, 2, 0, 1]
        assert_plans_equal(
            sc.plan(scn.services, tp, DELAY, QUALITY, offs),
            ve.plan(scn.services, tp, DELAY, QUALITY, offs))

    def test_zero_offsets_delegate(self):
        for eng in ("scalar", "vec"):
            so = StackingOffset(eng)
            scn = make_scenario(K=8, seed=7)
            tp = _tau_prime(scn, 0.6)
            assert_plans_equal(
                so(scn.services, tp, DELAY, QUALITY),
                stacking(scn.services, tp, DELAY, QUALITY, engine=eng))


# ---------------------------------------------------------------------------
# Pipeline-level equivalence: online, multi-server, handoff
# ---------------------------------------------------------------------------

class TestPipelineEquivalence:
    def _inv_se(self, scn, scheduler, delay, quality):
        from repro.core.bandwidth import inv_se_allocate
        return inv_se_allocate(scn)

    @pytest.mark.parametrize("sched_name",
                             ["stacking", "stacking_offset",
                              "equal_steps"])
    def test_online_runs_bit_identical(self, sched_name):
        from repro.api.registry import get_scheduler
        sched = get_scheduler(sched_name)
        for seed in range(3):
            scn = make_scenario(K=9, tau_min=3.0, tau_max=8.0,
                                arrival_rate=1.0, seed=seed)
            rs = simulate_online(scn, sched, self._inv_se,
                                 engine="scalar")
            rv = simulate_online(scn, sched, self._inv_se, engine="vec")
            assert rs.outcomes == rv.outcomes
            assert rs.decisions == rv.decisions
            assert rs.mean_fid == rv.mean_fid

    def test_provision_multi_bit_identical(self):
        from repro.core.stacking import stacking as sched
        for seed in range(3):
            scn = make_scenario(K=9, n_servers=3,
                                server_speed_range=(0.6, 1.4), seed=seed)
            assignment = [i % 3 for i in range(scn.K)]
            a = provision_multi(scn, assignment, sched, self._inv_se,
                                engine="scalar")
            b = provision_multi(scn, assignment, sched, self._inv_se,
                                engine="vec")
            assert a.outcomes == b.outcomes
            assert a.mean_fid == b.mean_fid

    @pytest.mark.parametrize("handoff", [False, True])
    def test_online_multi_bit_identical(self, handoff):
        from repro.core.offset import stacking_offset as sched
        for seed in range(2):
            scn = make_scenario(K=9, n_servers=3, arrival_rate=1.0,
                                tau_min=3.0, tau_max=8.0,
                                server_speed_range=(0.6, 1.4), seed=seed)
            a = simulate_online_multi(scn, sched, self._inv_se,
                                      handoff=handoff, engine="scalar")
            b = simulate_online_multi(scn, sched, self._inv_se,
                                      handoff=handoff, engine="vec")
            assert a.result.outcomes == b.result.outcomes
            assert a.result.decisions == b.result.decisions
            assert a.handoffs == b.handoffs
            assert a.handoff_log == b.handoff_log


# ---------------------------------------------------------------------------
# ServiceArrays plumbing
# ---------------------------------------------------------------------------

class TestServiceArrays:
    def test_build_and_index(self):
        arr = ServiceArrays.build([7, 3, 11], {7: 1.5, 3: 2.5, 11: 0.5},
                                  offsets={3: 4})
        assert arr.K == 3
        assert arr.ids.tolist() == [7, 3, 11]
        assert arr.tau_prime.tolist() == [1.5, 2.5, 0.5]
        assert arr.offsets.tolist() == [0, 4, 0]
        assert arr.index == {7: 0, 3: 1, 11: 2}

    def test_vec_plans_validate(self):
        """The vectorized plans satisfy the paper's constraints
        directly, not just by matching the scalar output."""
        for seed in range(4):
            scn = make_scenario(K=10, tau_min=1.0, tau_max=9.0,
                                seed=seed)
            tp = _tau_prime(scn, 0.4)
            plan = stacking(scn.services, tp, DELAY, QUALITY,
                            engine="vec")
            plan.validate(gen_deadlines=tp)
