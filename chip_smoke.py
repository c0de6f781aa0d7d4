"""Run the batch-denoising main path once, end to end, on one TPU chip.

    python chip_smoke.py [--seed N]

Everything runs in this one process (a chip belongs to one process at a
time; nothing here starts a child):

1. builds the published-width ``ddim-cifar10`` U-Net (``CONFIG``: 128
   base channels, multipliers (1, 2, 2, 2), attention at 16x16, about
   35.7M parameters) with random weights from ``--seed``, on the
   bucketed device-resident engine;
2. calibrates g(X) = aX + b on the chip through the bucketed step
   programs (batch sizes 1, 2, 4, 8: buckets 2, 4 and 8);
3. samples the paper's Sec. IV scenario with K = 8 services and scales
   its time axis (deadlines and transmission times) by the measured
   g(1) over the paper's, so each service keeps the paper's budget of
   about 18-53 size-1 steps and the planner has a trade-off to make;
4. plans with offset-aware STACKING under inverse-SE bandwidth and runs
   the plan in closed loop (rolling refit, replan on drift) through
   ``repro.api.Provisioner``.  K + 1 pool rows equal the calibration's
   8 + 1, so the session reuses the calibration's step programs;
5. checks that every service came back as a finite (32, 32, 3) image,
   that the executed batches account for every planned step, that the
   compiled bucketed step contains the Pallas GroupNorm+SiLU kernel
   (``tpu_custom_call``), and that the U-Net's predicted noise on mixed
   timesteps agrees with the plain float32 reference
   (``unet.reference_forward``) within ``EPS_REL_BOUND``.

Earlier lines of standard output report the device, the compile cache,
(a, b), compile seconds per bucket, batches, replans and refits, the
outage rate and mean FID, and the correctness check.  The last line is
one JSON object, ``{"ok": true, "device": {...}}``.  The script exits
non-zero, without that line, when the default device is not a TPU or
any check fails.  The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache`` beside this
file (``repro.compile_cache``).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
K = 8                                  # services == largest bucket
CALIB_SIZES = (1, 2, 4, 8)
EPS_TIMESTEPS = (999, 800, 600, 400, 200, 100, 10, 0)

# Relative error bound on eps, ||eps - eps_ref|| / ||eps_ref||.  The
# chip runs float32 matmuls and convolutions at its default precision:
# one bfloat16 pass, which rounds every operand to 8 mantissa bits
# (unit roundoff 2^-9 ~ 2e-3).  Through the U-Net's ~30 serial
# convolutions that compounds to about 1e-2: rounding only the
# convolution operands to bfloat16 on the CPU gives 1.07e-2 at this
# width and seed.  5e-2 leaves 4-5x headroom for that, while a wrong
# group statistic or a dropped kernel output is an O(1) error.
EPS_REL_BOUND = 5e-2


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the scenario")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU, but JAX's default device is on platform "
             f"{dev.platform!r} ({dev.device_kind})")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")

    sys.path.insert(0, str(ROOT / "src"))
    from repro import compile_cache
    cache_dir = compile_cache.enable(ROOT)
    cached = Path(cache_dir)
    n_cached = len(list(cached.iterdir())) if cached.is_dir() else 0
    print(f"compile_cache: dir={cache_dir} entries_before={n_cached}")

    import jax.numpy as jnp
    import numpy as np
    from repro.api import DiffusionWorkload, Provisioner
    from repro.configs.ddim_cifar10 import CONFIG
    from repro.core.delay_model import DelayModel
    from repro.core.service import DEFAULT_CONTENT_BITS, make_scenario
    from repro.diffusion import unet
    from repro.diffusion.bucketed import pool_step

    # 1-2. the published-width U-Net, calibrated on the chip
    wl = DiffusionWorkload(cfg=CONFIG, init_seed=args.seed,
                           exec_engine="bucketed")
    ex = wl.executor
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(ex.params))
    print(f"model: {CONFIG.name} params={n_params} "
          f"base_channels={CONFIG.base_channels} "
          f"mults={CONFIG.channel_mults} attn={CONFIG.attn_resolutions}")
    g = wl.calibrate(jax.random.PRNGKey(args.seed + 1),
                     batch_sizes=CALIB_SIZES, reps=5)
    # a second reading of the same curve: its spread against the fit
    recheck = [(int(x), float(s)) for x, s in
               wl.measure_delay_curve(jax.random.PRNGKey(args.seed + 1),
                                      batch_sizes=CALIB_SIZES, reps=5)]
    print(f"calibration: a={g.a!r} b={g.b!r} "
          f"curve_recheck_s={json.dumps(recheck)}")
    calib_compile = {}
    for key, s in ex.compile_log:
        calib_compile[str(key[2])] = calib_compile.get(str(key[2]), 0) + s
    print(f"compile_s_by_bucket (calibration): "
          f"{json.dumps(calib_compile, sort_keys=True)}")
    if g.g(1) <= 0 or not np.isfinite(g.g(1)):
        fail(f"calibrated g(1)={g.g(1)!r} is not a positive delay")

    # 3. the paper's scenario on the chip's clock
    scale = g.g(1) / DelayModel().g(1)
    scn = make_scenario(K=K, tau_min=7.0 * scale, tau_max=20.0 * scale,
                        content_bits=DEFAULT_CONTENT_BITS * scale,
                        seed=args.seed)
    print(f"scenario: K={K} time_scale={scale!r} deadlines_s="
          f"{json.dumps([s.deadline for s in scn.services])}")

    # 4. plan, execute in closed loop, deliver
    n_compiles = len(ex.compile_log)
    t0 = time.perf_counter()
    report = Provisioner(
        scn, workload=wl, scheduler="stacking_offset", allocator="inv_se",
        delay=g, execute_kwargs={"min_batches": 2, "drift_tol": 0.25,
                                 "headroom": 1.15},
    ).run(jax.random.PRNGKey(args.seed + 2), execute="closed")
    run_s = time.perf_counter() - t0
    exe = report.execution
    tele = exe.session_telemetry or {}
    print(f"execution: engine={exe.exec_engine} "
          f"batches={len(exe.records)} replans={exe.replans} "
          f"refits={exe.refits} outage_rate={exe.outage_rate!r} "
          f"mean_fid={exe.mean_fid!r} delivered_fid={exe.delivered_fid!r} "
          f"makespan_s={exe.wall_clock!r} run_s={run_s!r}")
    print(f"session: dispatches={tele.get('dispatches')} "
          f"by_bucket={json.dumps(tele.get('by_bucket'))} "
          f"compiles={tele.get('compiles')} compile_s_by_bucket="
          f"{json.dumps(tele.get('compile_s_by_bucket'))}")
    print(f"refit: a={exe.delay.a!r} b={exe.delay.b!r}")

    # 5. checks
    if exe.exec_engine != "bucketed":
        fail(f"ran on the {exe.exec_engine!r} engine, not 'bucketed'")
    if len(ex.compile_log) != n_compiles:
        fail(f"the session compiled {len(ex.compile_log) - n_compiles} "
             f"step programs the calibration had not")
    shape = (CONFIG.image_size, CONFIG.image_size, CONFIG.in_channels)
    for o in exe.outcomes:
        img = np.asarray(exe.content[o.id])
        if img.shape != shape or not np.isfinite(img).all():
            fail(f"service {o.id}: image of shape {img.shape}, "
                 f"finite={bool(np.isfinite(img).all())}")
    executed = sum(r.size for r in exe.records)
    planned = sum(o.steps for o in exe.outcomes)
    if executed != planned or tele.get("dispatches") != len(exe.records):
        fail(f"executed {executed} steps in {tele.get('dispatches')} "
             f"dispatches for {planned} planned steps in "
             f"{len(exe.records)} batches")
    if exe.replans == 0:
        for o in exe.outcomes:
            if o.steps != report.plan.steps_completed[o.id]:
                fail(f"service {o.id}: executed {o.steps} steps, "
                     f"planned {report.plan.steps_completed[o.id]}")
    print(f"delivery: services={len(exe.outcomes)} steps={planned} "
          f"images=finite{shape}")

    rows = K + 1
    pool = jnp.zeros((rows,) + shape, jnp.float32)
    lane = np.zeros((K,), np.int32)
    step = ex.program(("bstep", rows, K), pool_step(ex.step_fn),
                      (ex.params, pool, lane, lane, lane), donate=(1,))
    n_kernels = step.as_text().count("tpu_custom_call")
    print(f"bucketed_step: bucket={K} tpu_custom_call={n_kernels}")
    if n_kernels == 0:
        fail("the compiled bucketed step has no Pallas kernel")

    x = jax.random.normal(jax.random.PRNGKey(args.seed + 3),
                          (len(EPS_TIMESTEPS),) + shape, jnp.float32)
    t = jnp.asarray(EPS_TIMESTEPS, jnp.int32)
    t0 = time.perf_counter()
    eps = np.asarray(jax.jit(ex.eps_fn)(ex.params, x, t), np.float64)
    eps_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ref = np.asarray(unet.reference_forward(CONFIG, ex.params, x, t),
                     np.float64)
    ref_s = time.perf_counter() - t0
    ref_norm = float(np.linalg.norm(ref))
    rel = float(np.linalg.norm(eps - ref)) / max(ref_norm, 1e-300)
    print(f"eps_check: rows={len(EPS_TIMESTEPS)} rel_err={rel!r} "
          f"bound={EPS_REL_BOUND!r} ref_norm={ref_norm!r} "
          f"eps_s={eps_s!r} reference_s={ref_s!r}")
    if not (np.isfinite(rel) and ref_norm > 0 and rel <= EPS_REL_BOUND):
        fail(f"eps relative error {rel!r} exceeds {EPS_REL_BOUND!r}")

    compile_s = sum(s for _, s in ex.compile_log)
    print(f"compile_s_total={compile_s!r} "
          f"wall_s={time.perf_counter() - t_start!r}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
