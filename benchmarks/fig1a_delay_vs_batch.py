"""Fig. 1a: per-step denoising delay vs. batch size, and the affine fit
g(X) = aX + b, re-measured on the host's CPU with the smoke U-Net (the
paper measures an RTX-3050; a, b are hardware constants by design).
Each size is read at what the served session pays for it, its padded
power-of-two bucket, so sizes sharing a bucket (3-4, 5-8, 9-16) share a
reading.  Also reports the analytic TPU v5e estimate."""

import jax

from repro.api import DiffusionWorkload
from repro.configs.ddim_cifar10 import SMOKE, CONFIG
from repro.core.delay_model import fit, tpu_estimate, PAPER_A, PAPER_B
from repro.diffusion import unet
from repro.models.params import param_bytes


def run(csv_rows):
    wl = DiffusionWorkload(cfg=SMOKE, init_seed=0)
    curve = wl.measure_delay_curve(jax.random.PRNGKey(1),
                                   batch_sizes=[1, 2, 3, 4, 6, 8, 12, 16],
                                   reps=3)
    model = fit([c[0] for c in curve], [c[1] for c in curve])
    for X, secs in curve:
        csv_rows.append(("fig1a_delay_batch%d" % X, secs * 1e6,
                         f"pred={model.g(X) * 1e6:.0f}us"))
    csv_rows.append(("fig1a_fit_a", model.a * 1e6, "us/sample (cpu)"))
    csv_rows.append(("fig1a_fit_b", model.b * 1e6, "us fixed (cpu)"))
    csv_rows.append(("fig1a_paper_a", PAPER_A * 1e6, "us/sample (rtx3050)"))
    csv_rows.append(("fig1a_paper_b", PAPER_B * 1e6, "us fixed (rtx3050)"))
    # affine-form quality of fit
    resid = max(abs(model.g(X) - s) / s for X, s in curve)
    csv_rows.append(("fig1a_fit_max_rel_resid", resid * 100, "percent"))
    # analytic full-size U-Net on TPU v5e (DESIGN.md §3)
    full_flops = 6.1e9          # ~35M-param CIFAR U-Net fwd @ 32x32
    pbytes = param_bytes(unet.schema(CONFIG), 2)
    tpu = tpu_estimate(full_flops, pbytes)
    csv_rows.append(("fig1a_tpu_v5e_a", tpu.a * 1e6, "us/sample (analytic)"))
    csv_rows.append(("fig1a_tpu_v5e_b", tpu.b * 1e6, "us fixed (analytic)"))
