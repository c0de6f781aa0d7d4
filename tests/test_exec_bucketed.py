"""The served denoising session (the device-resident pool) vs the
per-row reference session.

Contract (docs/PERFORMANCE.md): per-row results match
``ReferenceDenoiseSession`` within ``MATCH_TOL`` — padded-width XLA
programs may fuse differently from exact-width ones, so bit identity
across the two is not promised.  The property
test generates arbitrary plan shapes; the fixed-plan tests pin the
scheduling edge cases (retarget mid-scan, composition breaks, zero-step
services) and the compile economics (≤ ⌈log2 K⌉ step programs, warm
second sessions).  Hypothesis is optional — the parametrized fixed
plans cover the same property deterministically when it is absent.
"""

import math

import numpy as np
import pytest

pytest.importorskip("jax")

import jax
import jax.numpy as jnp

from repro.api import DiffusionWorkload
from repro.configs.ddim_cifar10 import UNetConfig
from repro.core.delay_model import DelayModel
from repro.core.execution import shape_bucket
from repro.core.plan import BatchPlan
from repro.diffusion import unet
from repro.diffusion.executor import (_SCAN_CHUNKS, BatchDenoisingExecutor,
                                      DenoiseSession)
from repro.diffusion.reference import (MATCH_TOL, ReferenceDenoiseSession,
                                       reference_images)
from repro.models.params import init_params

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

# tiny U-Net: per-program compile is cheap, so the suite affords the
# handful of (pool_rows, bucket) shapes these tests touch
MICRO = UNetConfig(name="ddim-micro-test", image_size=8, base_channels=8,
                   channel_mults=(1,), num_res_blocks=1,
                   attn_resolutions=(), num_groups=4)


@pytest.fixture(scope="module")
def ex():
    params = init_params(unet.schema(MICRO), jax.random.PRNGKey(0))
    return BatchDenoisingExecutor(MICRO, params)


def make_plan(counts, batches):
    """A BatchPlan from explicit (service -> total steps) counts and an
    explicit batch sequence (list of id-lists)."""
    idx = {k: 0 for k in counts}
    bb = []
    for ks in batches:
        bb.append([(k, idx[k]) for k in ks])
        for k in ks:
            idx[k] += 1
    assert idx == dict(counts), "batches disagree with step counts"
    return BatchPlan(batches=bb, start_times=[0.0] * len(bb),
                     steps_completed=dict(counts), delay=DelayModel())


def stacking_batches(counts):
    """All-active-together rounds (the STACKING shape: composition
    shrinks as services retire)."""
    rem = dict(counts)
    out = []
    while any(v > 0 for v in rem.values()):
        ks = sorted(k for k, v in rem.items() if v > 0)
        out.append(ks)
        for k in ks:
            rem[k] -= 1
    return out


def assert_rows_match(got, want):
    assert set(got) == set(want)
    for k in want:
        np.testing.assert_allclose(got[k], want[k], **MATCH_TOL,
                                   err_msg=f"service {k}")


class TestEnginesMatch:
    @pytest.mark.parametrize("counts", [
        {0: 3, 1: 3, 2: 3},                    # one stable phase
        {0: 5, 1: 3, 2: 1, 3: 0},              # staggered + zero-step
        {0: 1, 1: 2, 2: 3, 3: 4, 4: 7},        # many distinct sizes
    ])
    def test_fixed_plans(self, ex, counts):
        plan = make_plan(counts, stacking_batches(counts))
        key = jax.random.PRNGKey(42)
        want = reference_images(ex, plan, key)
        got, _ = ex.run(plan, key)
        assert_rows_match(got, want)

    def test_timed_matches_untimed_bucketed(self, ex):
        """Timed execution is stepwise (no scan fusion) but must land
        on the same images as the scan-fused untimed path."""
        counts = {0: 4, 1: 4, 2: 2}
        plan = make_plan(counts, stacking_batches(counts))
        key = jax.random.PRNGKey(7)
        plain, no_t = ex.run(plan, key)
        timed, ts = ex.run(plan, key, timed=True)
        assert no_t == [] and len(ts) == plan.num_batches
        assert_rows_match(timed, plain)

    def test_zero_step_latent_untouched(self, ex):
        """A service the planner retired at T=0 comes back as its
        seeded noise, exactly."""
        plan = make_plan({0: 0, 1: 2}, [[1], [1]])
        key = jax.random.PRNGKey(13)
        imgs, _ = ex.run(plan, key)
        k0 = jax.random.split(key, 2)[0]
        raw = jax.random.normal(k0, (8, 8, 3), jnp.float32)
        np.testing.assert_array_equal(imgs[0], np.asarray(raw))

    if HAVE_HYPOTHESIS:
        @settings(max_examples=15, deadline=None)
        @given(counts=st.lists(st.integers(0, 5), min_size=1,
                               max_size=4),
               drop=st.integers(0, 2 ** 16 - 1),
               seed=st.integers(0, 2 ** 16 - 1))
        def test_property_bucket_equals_dict(self, ex, counts, drop,
                                             seed):
            """Arbitrary plan shapes: per-row served == reference within
            MATCH_TOL.  ``drop`` perturbs the all-active composition by
            deferring one service's steps, so compositions mix."""
            counts = {k: c for k, c in enumerate(counts)}
            victim = drop % max(len(counts), 1)
            deferred = min(counts.get(victim, 0), drop // 7 % 3)
            head = dict(counts)
            head[victim] = counts[victim] - deferred
            batches = stacking_batches(head)
            batches += [[victim]] * deferred
            plan = make_plan(counts, batches)
            key = jax.random.PRNGKey(seed)
            want = reference_images(ex, plan, key)
            got, _ = ex.run(plan, key)
            assert_rows_match(got, want)


class TestScheduling:
    def test_retarget_mid_scan(self, ex):
        """Retargeting between run_plan calls (i.e. between fused scan
        megasteps) lands on the same images as the reference session
        driven identically."""
        counts = {0: 6, 1: 6, 2: 6}
        key = jax.random.PRNGKey(3)
        plan = make_plan(counts, stacking_batches(counts))
        sessions = [ReferenceDenoiseSession(ex, plan, key),
                    ex.open_session(plan, key)]
        for sess in sessions:
            sess.run_plan([[0, 1, 2]] * 3)        # fused on the pool
            sess.retarget({0: 4, 1: 8})           # shrink / stretch
            sess.run_batch([0, 1, 2])
            sess.run_plan([[1, 2]] * 2)           # 0 retired at 4
            sess.run_plan([[1]] * 2)
            assert sess.steps_done == {0: 4, 1: 8, 2: 6}
        want, got = sessions[0].finish(), sessions[1].finish()
        assert_rows_match(got, want)

    def test_scan_breaks_on_composition_change(self, ex):
        """A composition change must end the fused run — and the same
        batch SIZE with different members is a different composition."""
        counts = {0: 5, 1: 4, 2: 2}
        batches = [[0, 1], [0, 1], [0, 1], [0, 2], [0, 2], [1]]
        plan = make_plan(counts, batches)
        sess = ex.open_session(plan, jax.random.PRNGKey(9))
        sess.run_plan([list(b) for b in batches])
        tele = sess.telemetry()
        # [0,1]x3 -> scan(2)+step; [0,2]x2 -> scan(2); [1] -> step
        assert tele["scan_fused_steps"] == 4
        assert tele["scan_dispatches"] == {"b2_c2": 2}
        assert tele["by_bucket"] == {"2": 2}
        # and the images still match the reference
        want = reference_images(ex, plan, jax.random.PRNGKey(9))
        assert_rows_match(sess.finish(), want)

    def test_retarget_errors_preserved(self, ex):
        """The shared no-resurrection rules hold on the pool path."""
        counts = {0: 3, 1: 3}
        plan = make_plan(counts, stacking_batches(counts))
        sess = ex.open_session(plan, jax.random.PRNGKey(1))
        sess.run_batch([0, 1])
        with pytest.raises(ValueError, match="already executed"):
            sess.retarget({0: 0})
        sess.retarget({0: 1})
        with pytest.raises(ValueError, match="no remaining"):
            sess.run_batch([0])


class TestCompileEconomics:
    def test_recompile_bound(self):
        """A mixed-size plan over K services compiles at most
        ⌈log2 K⌉ step programs (power-of-two buckets, min 2)."""
        params = init_params(unet.schema(MICRO), jax.random.PRNGKey(0))
        fresh = BatchDenoisingExecutor(MICRO, params)
        K = 8
        counts = {k: k + 1 for k in range(K)}    # sizes 8,7,...,1
        plan = make_plan(counts, stacking_batches(counts))
        sess = fresh.open_session(plan, jax.random.PRNGKey(2))
        for b in plan.batches:                   # stepwise: no scans
            sess.run_batch([k for k, _ in b])
        steps = [k for k, _ in fresh.compile_log if k[0] == "bstep"]
        assert len(steps) <= math.ceil(math.log2(K))
        assert {k[2] for k in steps} <= \
            {shape_bucket(n) for n in range(1, K + 1)}

    def test_second_session_is_warm(self, ex):
        counts = {0: 2, 1: 2, 2: 1}
        plan = make_plan(counts, stacking_batches(counts))
        ex.run(plan, jax.random.PRNGKey(4))
        sess = ex.open_session(plan, jax.random.PRNGKey(5))
        sess.run_plan(stacking_batches(counts))
        tele = sess.telemetry()
        assert tele["compiles"] == 0 and tele["compile_s"] == 0.0
        assert tele["dispatches"] > 0

    def test_delay_curve_shares_bucket_programs(self):
        """Sweeping 1..8 compiles 3 bucket programs, not 8, and the
        compile time lands in last_compile_log, not the readings."""
        params = init_params(unet.schema(MICRO), jax.random.PRNGKey(0))
        fresh = BatchDenoisingExecutor(MICRO, params)
        curve = fresh.measure_delay_curve(jax.random.PRNGKey(6),
                                          batch_sizes=range(1, 9),
                                          reps=2)
        assert [x for x, _ in curve] == list(range(1, 9))
        assert len(fresh.last_compile_log) == 3      # buckets 2, 4, 8
        # same-bucket sizes pay the same padded cost; readings are
        # steady-state, far under any compile time
        assert all(s < c for _, s in curve
                   for _, c in fresh.last_compile_log)


class TestEngineKnob:
    def test_registry_and_default(self, ex):
        """``open_session`` opens the pool session, and its telemetry
        says so."""
        plan = make_plan({0: 1}, [[0]])
        sess = ex.open_session(plan, jax.random.PRNGKey(0))
        assert isinstance(sess, DenoiseSession)
        assert sess.telemetry()["exec_engine"] == "bucketed"

    def test_unknown_engine_rejected(self):
        """The workload's compatibility keyword takes only the pool."""
        DiffusionWorkload(cfg=MICRO, exec_engine="bucketed")
        with pytest.raises(ValueError, match="unknown exec_engine"):
            DiffusionWorkload(cfg=MICRO, exec_engine="dict")

    def test_pool_rows_are_the_per_service_draws(self, ex):
        """The seeding contract: pool row i is
        ``normal(split(key, K)[i])`` for the i-th service in sorted-id
        order, bit for bit, and the scratch row is zero."""
        plan = make_plan({7: 2, 3: 1, 11: 0, 5: 1}, [[7, 3, 5], [7]])
        key = jax.random.PRNGKey(21)
        sess = ex.open_session(plan, key)
        pool = np.asarray(sess._pool)
        ids = sorted(plan.steps_completed)
        keys = jax.random.split(key, len(ids))
        assert pool.shape == (len(ids) + 1, 8, 8, 3)
        for i, k in enumerate(ids):
            np.testing.assert_array_equal(
                pool[i],
                np.asarray(jax.random.normal(keys[i], (8, 8, 3),
                                             jnp.float32)))
        np.testing.assert_array_equal(pool[-1], np.zeros((8, 8, 3)))

    def test_shape_bucket_grid(self):
        assert [shape_bucket(n) for n in (1, 2, 3, 4, 5, 8, 9, 16)] == \
            [2, 2, 4, 4, 8, 8, 16, 16]
        assert _SCAN_CHUNKS[-1] == 2      # remainder is at most 1 step
