"""The round loop and the metric arithmetic, on the CPU at the smoke size.

The loop is driven directly for two rounds; the end-to-end and per-layer
arithmetic is then checked against the same numbers worked out here.
"""

import dataclasses
import types

import numpy as np
import pytest

from chipbench import bench, reference, spec, trace, traffic, yardstick
from repro.diffusion import ddim
from smoke import smoke_cell

SEED = 2_147_483_659   # above 2**31


@pytest.fixture(scope="module")
def two_rounds():
    b = bench.Bench(smoke_cell(), SEED)
    b.setup()
    first = b.round(0, b.g_cal)
    second = b.round(1, b.g_cal)
    return b, [first, second]


def test_rounds_serve_every_service(two_rounds):
    b, rounds = two_rounds
    k = b.mix["services_per_round"]
    for r in rounds:
        assert len(r.deadlines) == k and r.ok.all() and len(r.images) == k
        assert r.steps_unaccounted == 0 and r.wall_s > 0
        assert sum(r.batch_sizes) == int(r.steps.sum()) > 0
    assert b.compiles_since_setup() == 0


def test_delay_is_wall_plus_modelled_transmission(two_rounds):
    b, rounds = two_rounds
    for r in rounds:
        scn = traffic.round_scenario(b.mix, SEED, r.index)
        # inverse-SE allocation gives every service the same air time
        inv = sum(1.0 / s.spectral_eff for s in scn.services)
        tx = scn.content_bits * inv / scn.total_bandwidth_hz
        np.testing.assert_allclose(r.delays - r.wall_s, tx, rtol=1e-9)
        np.testing.assert_allclose(r.deadlines,
                                   [s.deadline for s in scn.services])


def test_end_to_end_arithmetic(two_rounds):
    _, rounds = two_rounds
    got = bench.end_to_end(rounds, 2.0)
    fids = [yardstick.fid(int(s)) for r in rounds for s in r.steps]
    ratio = np.concatenate([r.delays / r.deadlines for r in rounds])
    assert got["fid_mean"] == pytest.approx(np.mean(fids))
    assert got["delay_vs_deadline_p95"] == pytest.approx(
        np.percentile(ratio, 95))
    assert got["services_per_s"] == pytest.approx(
        sum(len(r.steps) for r in rounds) / 2.0)
    lost = dataclasses.replace(rounds[0], ok=np.array(
        [False] + [True] * (len(rounds[0].ok) - 1)))
    worse = bench.end_to_end([lost, rounds[1]], 2.0)
    assert worse["services_per_s"] == pytest.approx(
        got["services_per_s"] - 0.5)
    assert worse["fid_mean"] > got["fid_mean"]


def test_check_is_exact_on_the_cpu(two_rounds):
    b, rounds = two_rounds
    got = bench.check(b, rounds, 2, 4)
    assert got["image_rel_err_max"] < 1e-5
    assert got["images_missing"] == 0 and got["steps_unaccounted"] == 0
    longest = max(int(r.steps.max()) for r in rounds)
    assert got["steps_checked"] >= longest


@pytest.mark.parametrize("how", ["refit", {"a": 2e-4, "b": 1e-3}],
                         ids=["refit", "fixed"])
def test_window_plans_each_round_as_the_mix_says(how):
    b = bench.Bench(smoke_cell(plan_delay=how), SEED)
    b.g_cal = "calibration"
    used = []

    def fake_round(r, g, stream=1):
        used.append(g)
        return types.SimpleNamespace(refit=f"refit {r}")

    b.round = fake_round
    rounds, wall = b.window(0.01)
    assert len(rounds) == len(used) >= 2 and wall >= 0.01
    if how == "refit":
        assert used == ["calibration"] + [f"refit {r}"
                                          for r in range(len(used) - 1)]
    else:
        assert {(g.a, g.b) for g in used} == {(2e-4, 1e-3)}


def test_unknown_plan_delay_is_refused():
    b = bench.Bench(smoke_cell(plan_delay="median"), SEED)
    with pytest.raises(ValueError, match="plan_delay"):
        b.first_model()


def test_traffic_is_seeded_and_uniform():
    mix = smoke_cell(services_per_round=4000).mix
    a = traffic.round_scenario(mix, SEED, 3)
    assert a == traffic.round_scenario(mix, SEED, 3)
    assert a != traffic.round_scenario(mix, SEED, 4)
    assert a != traffic.round_scenario(mix, SEED + 2**32, 3)
    for key, scale, got in (
            ("deadline_ms", 1e3, [s.deadline for s in a.services]),
            ("spectral_eff", 1.0, [s.spectral_eff for s in a.services])):
        lo, hi = mix[key]
        u = (np.array(got) * scale - lo) / (hi - lo)
        assert 0 <= u.min() and u.max() < 1
        # i.i.d. uniform: each tenth of the range holds about a tenth
        counts = np.histogram(u, bins=10, range=(0, 1))[0]
        assert counts.min() > 300 and counts.max() < 500


@pytest.mark.parametrize("T", [1, 2, 5, 17, 64, 999, 1000, 1500])
def test_schedule_copies_match_the_system(T):
    assert reference.ddim_timesteps(T) == list(ddim.ddim_timesteps(T))
    assert reference.retarget_timesteps(700, T) == list(
        ddim.retarget_timesteps(700, T))
    np.testing.assert_allclose(reference.alphas_cumprod(),
                               ddim.alphas_cumprod(1000))


def test_chain_replay():
    events = [("batch", [0, 1]), ("batch", [0]),
              ("retarget", {0: 4, 1: 1}), ("batch", [0]), ("batch", [0])]
    got = reference.chains({0: 10, 1: 3}, events)
    first = reference.ddim_timesteps(10)
    assert got[0][:2] == [(first[0], first[1]), (first[1], first[2])]
    tail = reference.retarget_timesteps(first[2], 2)
    assert got[0][2:] == [(tail[0], tail[1]), (tail[1], -1)]
    assert got[1] == [(reference.ddim_timesteps(3)[0],
                       reference.ddim_timesteps(3)[1])]


def _gn_call(b, hw, c):
    """A GroupNorm+SiLU call's HLO text, as the TPU's trace names it."""
    x = f"f32[{b},{hw},{c}]{{2,1,0:T(8,128)S(1)}}"
    v = f"f32[1,{c}]{{1,0:T(1,128)S(1)}}"
    return (f"%f.45 = {x} custom-call({x} %bitcast.615, {v} %bitcast.776, "
            f"{v} %bitcast.777), custom_call_target=\"tpu_custom_call\", "
            f"operand_layout_constraints={{}}")


def _summary(**kw):
    base = dict(window_s=2.0, busy_s=1.5, chips=1, op_s={}, gap_s={},
                pallas=[])
    base.update(kw)
    return trace.Summary(**base)


def test_per_layer_readers(two_rounds):
    b, rounds = two_rounds
    rows = sum(sum(r.batch_sizes) for r in rounds)
    t = _summary(pallas=[(_gn_call(2, 256, 32), 1e-6),
                         (_gn_call(4, 64, 64), 3e-6),
                         ('%f.9 = f32[4,64]{1,0} custom-call(f32[4,64]{1,0} '
                          '%x), custom_call_target="tpu_custom_call"', 5e-6)])
    obs = trace.Observation(rounds=rounds, trace=t, sizes=b.sizes,
                            peaks=yardstick.peaks("TPU v5 lite"), compiles=0)
    read = {m["name"]: spec.metric_reader(m["name"])(obs)
            for m in b.cell.per_layer}
    assert read["idle_share"] == pytest.approx(25.0)
    assert read["step_ms_per_row"] == pytest.approx(1500.0 / rows)
    assert read["unet_mfu"] == pytest.approx(
        100 * rows * yardstick.unet_flops_per_row(b.sizes) / 2.0 / 197e12)
    need = (yardstick.gn_silu_call_bytes(2, 256, 32)
            + yardstick.gn_silu_call_bytes(4, 64, 64))
    assert read["gn_silu_roofline"] == pytest.approx(
        100 * need / 819e9 / 4e-6)
    wall = sum(r.wall_s for r in rounds)
    timed = sum(sum(r.measured_s) for r in rounds)
    assert read["host_share"] == pytest.approx(100 * (1 - timed / wall))
    padded = sum(int(k) * n for r in rounds
                 for k, n in r.telemetry["by_bucket"].items())
    assert read["pad_share"] == pytest.approx(100 * (1 - rows / padded))
    plan = sum(r.plan_s for r in rounds)
    assert 0 < plan < wall
    assert read["plan_share"] == pytest.approx(100 * plan / wall)
    met = np.concatenate([r.delays <= r.deadlines for r in rounds])
    assert read["deadline_met_share"] == pytest.approx(100 * met.mean())
    assert read["compiles_in_window"] == 0
    empty = trace.Observation(rounds=rounds,
                              trace=_summary(window_s=0.0, busy_s=0.0),
                              sizes=b.sizes, peaks=obs.peaks, compiles=0)
    for name in ("step_ms_per_row", "unet_mfu", "gn_silu_roofline",
                 "idle_share"):
        assert spec.metric_reader(name)(empty) is None
