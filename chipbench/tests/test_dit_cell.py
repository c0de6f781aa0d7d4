"""The DiT cell on the CPU, at a small DiT: a sound run is correct, a
wrong step and the bfloat16 control are not, and the two readers find
what they read.

``smoke.py`` shrinks a cell to the smoke U-Net, whatever its
configuration; this file shrinks the DiT cell to a small DiT instead
(depth 2, hidden 96, 4 heads of 24, patch 2, an 8x8x4 latent), keeping
every other key of the cell's files.
"""

import dataclasses
import json
import types

import jax
import pytest

from chipbench import bench, run, spec, yardstick
from repro.diffusion.executor import BatchDenoisingExecutor

CELL = "dit-xl2-256.dense-k32"
SMALL = {"image_size": 8, "hidden_size": 96, "depth": 2, "num_heads": 4}
SOUND_STEP = BatchDenoisingExecutor.step_fn


def dit_smoke_cell(**mix_overrides) -> spec.Cell:
    cell = spec.resolve(CELL)
    sizes = dict(cell.sizes, **SMALL)
    mix = json.loads(json.dumps(cell.mix))
    mix.update(services_per_round=4, deadline_ms=[60.0, 160.0],
               total_bandwidth_hz=2.0e6, calibration_reps=1,
               plan_delay={"a": 1.5e-3, "b": 7e-3})
    mix["check"] = dict(mix["check"], services=3, rows=4)
    mix.update(mix_overrides)
    return dataclasses.replace(cell, sizes=sizes, mix=mix)


def _run():
    return run.measure(dit_smoke_cell(), 4_000_000_123, 0.4, False,
                       jax.devices()[0], yardstick.peaks("TPU v5 lite"), 1)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"] is True
    assert out["checks"]["image_rel_err_max"]["value"] < 1e-5
    assert out["attempted"] >= 4 and out["failed"] == 0


def test_altered_step_is_caught(monkeypatch):
    def altered(self, params, x, t_now, t_next):
        return SOUND_STEP(self, params, x, t_now, t_next) * 1.02
    monkeypatch.setattr(BatchDenoisingExecutor, "step_fn", altered)
    out = _run()
    assert out["correct"] is False


def test_control_fails_the_limit():
    """The reference in bfloat16 throughout, on chains of 26-35 steps,
    against the cell's own limit."""
    b = bench.Bench(dit_smoke_cell(deadline_ms=[200.0, 520.0]),
                    7_000_000_001)
    b.setup()
    rounds, _ = b.window(0.3)
    b.release()
    limit = b.mix["check"]["image_rel_err_limit"]
    sound = bench.check(b, rounds, 3, 4)
    control = bench.check(b, rounds, 3, 4, control=True)
    print(sound["image_rel_err_max"], control["image_rel_err_max"])
    assert sound["image_rel_err_max"] <= limit
    assert control["image_rel_err_max"] > limit


# One call of the kernel as a TPU trace names it: the HLO instruction
# with its operands' shapes and layouts.
FLASH = ('%dit.attn.3 = f32[32,16,256,72]{3,2,1,0:T(8,128)} custom-call('
         'f32[32,16,256,72]{3,2,1,0:T(8,128)} %bitcast.94, '
         'f32[32,16,256,72]{3,2,1,0:T(8,128)S(1)} %bitcast.95, '
         'f32[32,16,256,72]{3,2,1,0:T(8,128)} %bitcast.96), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints='
         '{f32[32,16,256,72]{3,2,1,0}, f32[32,16,256,72]{3,2,1,0}, '
         'f32[32,16,256,72]{3,2,1,0}}')
GN = ('%f.45 = f32[8,1024,128]{2,1,0:T(8,128)S(1)} custom-call(f32[8,1024,'
      '128]{2,1,0:T(8,128)S(1)} %bitcast.615, f32[1,128]{1,0:T(1,128)S(1)}'
      ' %bitcast.776, f32[1,128]{1,0:T(1,128)S(1)} %bitcast.777), '
      'custom_call_target="tpu_custom_call"')


def _obs(pallas=(), batch_sizes=(32, 32, 7), window_s=2.0):
    rnd = types.SimpleNamespace(batch_sizes=list(batch_sizes))
    summ = types.SimpleNamespace(pallas=list(pallas), window_s=window_s)
    return types.SimpleNamespace(
        rounds=[rnd], trace=summ, peaks=yardstick.peaks("TPU v5 lite"),
        sizes=spec.resolve(CELL).sizes)


def test_flash_calls_are_found_by_signature():
    mod = spec.metric_module("flash_attn_roofline")
    got = list(mod.calls([(FLASH, 2e-4), (GN, 1e-4)]))
    assert got == [(32, 16, 256, 72, 2e-4)]
    gn = spec.metric_module("gn_silu_roofline")
    assert list(gn.calls([(FLASH, 2e-4)])) == []
    # q, k, v and o at 151 MB bound the call (0.184 ms against 0.049 ms
    # of operations at the peak)
    flops = mod.call_flops(32, 16, 256, 72)
    need = mod.call_bytes(32, 16, 256, 72) / 819e9
    assert flops / 197e12 < need
    assert mod.read(_obs([(FLASH, 2 * need)])) == pytest.approx(50.0)
    assert mod.read(_obs([(GN, 1e-4)])) is None


def test_dit_mfu_counts_rows_that_served():
    mod = spec.metric_module("dit_mfu")
    sizes = spec.resolve(CELL).sizes
    want = 100.0 * 71 * mod.flops_per_row(sizes) / 2.0 / 197e12
    assert mod.read(_obs()) == pytest.approx(want)
    assert mod.read(_obs(batch_sizes=())) is None
