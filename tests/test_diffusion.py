"""Diffusion substrate: U-Net, DDIM schedules, batch-denoising executor."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.ddim_cifar10 import SMOKE
from repro.core.delay_model import DelayModel
from repro.core.plan import BatchPlan
from repro.core.quality_model import PowerLawFID
from repro.core.service import make_scenario
from repro.core.stacking import stacking
from repro.core.bandwidth import inv_se_allocate, tau_prime_of
from repro.diffusion import ddim, unet
from repro.diffusion.executor import BatchDenoisingExecutor
from repro.models.params import init_params


@pytest.fixture(scope="module")
def unet_params():
    return init_params(unet.schema(SMOKE), jax.random.PRNGKey(0))


class TestUNet:
    def test_forward_shape_per_sample_t(self, unet_params):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 16, 3))
        t = jnp.array([0.0, 10.0, 500.0, 999.0])
        eps = unet.forward(SMOKE, unet_params, x, t)
        assert eps.shape == x.shape
        assert bool(jnp.all(jnp.isfinite(eps)))

    def test_per_sample_t_matters(self, unet_params):
        """Different timesteps change the output (conditioning works).
        The final conv is ~zero-init (DDPM convention), so give it real
        weights for this sensitivity check."""
        params = dict(unet_params)
        params["conv_out"] = jax.random.normal(
            jax.random.PRNGKey(9), params["conv_out"].shape) * 0.1
        x = jnp.broadcast_to(
            jax.random.normal(jax.random.PRNGKey(2), (1, 16, 16, 3)),
            (2, 16, 16, 3))
        t = jnp.array([10.0, 900.0])
        eps = unet.forward(SMOKE, params, x, t)
        assert float(jnp.abs(eps[0] - eps[1]).max()) > 1e-4

    def test_reference_forward_matches_forward(self, unet_params,
                                               monkeypatch):
        """The plain float32 reference (XLA GroupNorm, highest matmul
        precision) agrees with the served forward pass; with the Pallas
        kernel forced on in interpret mode it still does, so the chip's
        eps check compares like with like."""
        x = jax.random.normal(jax.random.PRNGKey(4), (3, 16, 16, 3))
        t = jnp.array([999, 400, 0], jnp.int32)
        ref = unet.reference_forward(SMOKE, unet_params, x, t)
        for force in ("0", "1"):
            monkeypatch.setenv("REPRO_FORCE_PALLAS", force)
            got = unet.forward(SMOKE, unet_params, x, t)
            rel = float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref))
            assert rel < 1e-5, (force, rel)

    def test_mixed_batch_equals_individual(self, unet_params):
        """Batch denoising invariant: running two services in one batch
        gives the same result as running them separately (Fig. 1a's
        parallelism is lossless)."""
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 16, 3))
        t = jnp.array([100.0, 700.0])
        together = unet.forward(SMOKE, unet_params, x, t)
        alone0 = unet.forward(SMOKE, unet_params, x[:1], t[:1])
        alone1 = unet.forward(SMOKE, unet_params, x[1:], t[1:])
        np.testing.assert_allclose(np.asarray(together),
                                   np.asarray(jnp.concatenate([alone0,
                                                               alone1])),
                                   atol=1e-5, rtol=1e-5)


class TestDDIM:
    def test_timestep_subsequence(self):
        ts = ddim.ddim_timesteps(10, 1000)
        assert len(ts) == 10
        assert ts[0] > ts[-1]                     # descending
        assert ts[-1] == 0
        assert all(0 <= t < 1000 for t in ts)

    def test_schedule_table_ends_done(self):
        tab = ddim.schedule_table(5)
        assert len(tab) == 6 and tab[-1] == -1

    def test_step_reduces_noise_towards_x0(self, unet_params):
        """DDIM with a perfect eps predictor recovers x0 in one step."""
        x0 = jax.random.normal(jax.random.PRNGKey(4), (2, 16, 16, 3))
        eps_true = jax.random.normal(jax.random.PRNGKey(5), x0.shape)
        acp = ddim.alphas_cumprod()
        t = 600
        a = acp[t]
        xt = np.sqrt(a) * x0 + np.sqrt(1 - a) * eps_true
        out = ddim.ddim_step(lambda x, tt: eps_true, xt,
                             jnp.full((2,), t), jnp.full((2,), -1))
        np.testing.assert_allclose(np.asarray(out), np.asarray(x0),
                                   atol=1e-4, rtol=1e-4)

    def test_inactive_passthrough(self):
        x = jnp.ones((2, 4, 4, 3))
        out = ddim.ddim_step(lambda x, t: x * 0 + 1.0, x,
                             jnp.array([-1, 500]), jnp.array([-1, 250]))
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(x[0]))
        assert float(jnp.abs(out[1] - x[1]).max()) > 1e-5


class TestExecutor:
    def test_plan_execution_matches_plain_sampling(self, unet_params):
        """A single-service STACKING plan must produce exactly the same
        image as plain DDIM sampling with the same step count."""
        delay, quality = DelayModel(), PowerLawFID()
        scn = make_scenario(K=1, tau_min=3, tau_max=3, seed=0)
        tp = tau_prime_of(scn, inv_se_allocate(scn))
        plan = stacking(scn.services, tp, delay, quality)
        T = plan.steps_completed[0]
        assert T > 0

        ex = BatchDenoisingExecutor(SMOKE, unet_params)
        key = jax.random.PRNGKey(7)
        imgs, _ = ex.run(plan, key)

        eps_fn = lambda x, t: unet.forward(SMOKE, unet_params, x, t)
        k0 = jax.random.split(key, 1)[0]
        want = ddim.sample(eps_fn, k0, (1, 16, 16, 3), T)
        np.testing.assert_allclose(imgs[0], np.asarray(want[0]),
                                   atol=1e-3, rtol=1e-3)

    def test_stacking_plan_matches_plain_sampling_per_service(
            self, unet_params):
        """A five-service STACKING plan on the served session: each
        service's image is plain DDIM sampling at its own T, from its
        own key of ``split(key, 5)`` in sorted-id order."""
        scn = make_scenario(K=5, tau_min=2, tau_max=6, seed=1)
        tp = tau_prime_of(scn, inv_se_allocate(scn))
        plan = stacking(scn.services, tp, DelayModel(), PowerLawFID())
        ids = sorted(plan.steps_completed)
        assert len({plan.steps_completed[k] for k in ids}) >= 2
        ex = BatchDenoisingExecutor(SMOKE, unet_params)
        key = jax.random.PRNGKey(17)
        imgs, _ = ex.run(plan, key)
        eps_fn = lambda x, t: unet.forward(SMOKE, unet_params, x, t)
        keys = jax.random.split(key, len(ids))
        for i, k in enumerate(ids):
            want = ddim.sample(eps_fn, keys[i], (1, 16, 16, 3),
                               plan.steps_completed[k])
            np.testing.assert_allclose(imgs[k], np.asarray(want[0]),
                                       atol=1e-3, rtol=1e-3,
                                       err_msg=f"service {k}")

    def test_multi_service_plan_executes_all(self, unet_params):
        delay, quality = DelayModel(), PowerLawFID()
        scn = make_scenario(K=5, tau_min=2, tau_max=6, seed=1)
        tp = tau_prime_of(scn, inv_se_allocate(scn))
        plan = stacking(scn.services, tp, delay, quality)
        ex = BatchDenoisingExecutor(SMOKE, unet_params)
        imgs, _ = ex.run(plan, jax.random.PRNGKey(8))
        assert set(imgs) == set(plan.steps_completed)
        for v in imgs.values():
            assert v.shape == (16, 16, 3)
            assert np.isfinite(v).all()

    def test_timed_run_matches_untimed(self, unet_params):
        """Regression (ISSUE 5): timed mode used to re-run the step
        after the timing pair, advancing every batch TWO DDIM steps.
        Timing must be side-effect-free — identical images for a fixed
        key, one timing entry per batch.  Since ISSUE 10 the timed call
        IS the only U-Net execution (AOT compile is separate), so a
        timed run costs exactly one dispatch per batch, not two."""
        delay, quality = DelayModel(), PowerLawFID()
        scn = make_scenario(K=3, tau_min=2, tau_max=4, seed=2)
        tp = tau_prime_of(scn, inv_se_allocate(scn))
        plan = stacking(scn.services, tp, delay, quality)
        assert plan.num_batches > 0
        ex = BatchDenoisingExecutor(SMOKE, unet_params)
        key = jax.random.PRNGKey(11)
        before = ex.dispatches
        imgs_timed, timings = ex.run(plan, key, timed=True)
        assert ex.dispatches - before == plan.num_batches
        imgs_plain, no_timings = ex.run(plan, key)
        assert no_timings == []
        assert len(timings) == plan.num_batches
        assert all(x == len(b) for (x, _), b in zip(timings,
                                                    plan.batches))
        for k in imgs_plain:
            np.testing.assert_array_equal(imgs_timed[k], imgs_plain[k])

    def test_zero_step_service_returns_untouched_latent(self,
                                                        unet_params):
        """Regression: `run` used to force every service through
        max(T_k, 1) steps, denoising services the planner had retired
        at T_k = 0.  A zero-step service must never be batched and its
        latent must come back exactly as seeded."""
        plan = BatchPlan(batches=[[(1, 0)], [(1, 1)]],
                         start_times=[0.0, 1.0],
                         steps_completed={0: 0, 1: 2},
                         delay=DelayModel())
        ex = BatchDenoisingExecutor(SMOKE, unet_params)
        key = jax.random.PRNGKey(13)
        imgs, _ = ex.run(plan, key)
        assert set(imgs) == {0, 1}
        # service 0: the raw seeded noise, untouched (ids are seeded
        # in sorted order, exactly as DenoiseSession does it)
        k0 = jax.random.split(key, 2)[0]
        raw = jax.random.normal(k0, (16, 16, 3), jnp.float32)
        np.testing.assert_array_equal(imgs[0], np.asarray(raw))
        assert not np.array_equal(imgs[1], np.asarray(
            jax.random.normal(jax.random.split(key, 2)[1], (16, 16, 3),
                              jnp.float32)))


class TestDenoiseSession:
    """The stepwise execution handle behind the EXECUTORS registry."""

    def _plan(self, K=3, seed=2):
        scn = make_scenario(K=K, tau_min=2, tau_max=4, seed=seed)
        tp = tau_prime_of(scn, inv_se_allocate(scn))
        return stacking(scn.services, tp, DelayModel(), PowerLawFID())

    def test_session_matches_one_shot_run(self, unet_params):
        plan = self._plan()
        ex = BatchDenoisingExecutor(SMOKE, unet_params)
        key = jax.random.PRNGKey(21)
        want, _ = ex.run(plan, key)
        sess = ex.open_session(plan, key)
        for batch in plan.batches:
            sess.run_batch([k for k, _ in batch])
        got = sess.finish()
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])

    def test_retarget_no_resurrection(self, unet_params):
        plan = self._plan()
        ex = BatchDenoisingExecutor(SMOKE, unet_params)
        sess = ex.open_session(plan, jax.random.PRNGKey(22))
        k = min(plan.steps_completed)
        sess.run_batch([k])
        with pytest.raises(ValueError, match="already executed"):
            sess.retarget({k: 0})
        # retiring at exactly the executed count is legal...
        sess.retarget({k: sess.steps_done[k]})
        with pytest.raises(ValueError, match="no remaining"):
            sess.run_batch([k])
        # ...but re-growing a fully retired chain is a resurrection
        with pytest.raises(ValueError, match="fully denoised"):
            sess.retarget({k: sess.steps_done[k] + 3})

    def test_retarget_mid_flight_completes(self, unet_params):
        plan = self._plan(K=2, seed=3)
        ex = BatchDenoisingExecutor(SMOKE, unet_params)
        sess = ex.open_session(plan, jax.random.PRNGKey(23))
        k = min(plan.steps_completed)
        sess.run_batch([k])
        total = sess.steps_done[k] + 2   # shrink/stretch to done+2
        sess.retarget({k: total})
        sess.run_batch([k])
        sess.run_batch([k])
        assert sess.steps_done[k] == total
        with pytest.raises(ValueError, match="no remaining"):
            sess.run_batch([k])
        imgs = sess.finish()
        assert np.isfinite(imgs[k]).all()
