"""A run with the timed path broken underneath comes out not correct.

Each test plants one fault in the system's step, skips only the look for
a chip, and drives the rest of a run on the CPU at the U-Net's smoke
size.  The sound run beside them comes out correct.
"""

import jax
import pytest

from chipbench import run, yardstick
from repro.diffusion.executor import BatchDenoisingExecutor
from smoke import smoke_cell

SOUND_STEP = BatchDenoisingExecutor.step_fn


def unchanged(self, params, x, t_now, t_next):
    """The step returns its state as it found it."""
    return x


def half_batch(self, params, x, t_now, t_next):
    """Only the first half of the batch is stepped; the rest is left
    out."""
    y = SOUND_STEP(self, params, x, t_now, t_next)
    h = max(x.shape[0] // 2, 1)
    return y.at[h:].set(x[h:])


def altered(self, params, x, t_now, t_next):
    """Every image the step produces is off by 2%."""
    return SOUND_STEP(self, params, x, t_now, t_next) * 1.02


def _run():
    return run.measure(smoke_cell(), 4_000_000_123, 0.4, False,
                       jax.devices()[0], yardstick.peaks("TPU v5 lite"), 1)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert out["checks"]["image_rel_err_max"]["value"] < 1e-5


@pytest.mark.parametrize("fault", [unchanged, half_batch, altered],
                         ids=lambda f: f.__name__)
def test_fault_is_caught(monkeypatch, fault):
    monkeypatch.setattr(BatchDenoisingExecutor, "step_fn", fault)
    out = _run()
    assert out["correct"] is False
    c = out["checks"]["image_rel_err_max"]
    assert c["value"] > c["limit"]
