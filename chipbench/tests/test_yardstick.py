"""The benchmark's own arithmetic agrees with the system's today."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from chipbench import yardstick
from repro.configs.ddim_cifar10 import CONFIG, SMOKE
from repro.core.quality_model import PowerLawFID
from repro.core.service import ServiceRequest
from repro.diffusion import unet
from repro.models.params import abstract_params


@pytest.mark.parametrize("steps", [0, 1, 2, 7, 20, 100, 1000])
def test_fid_matches_the_system(steps):
    assert yardstick.fid(steps) == PowerLawFID().fid(steps)


@pytest.mark.parametrize("bits,hz,eta", [(24576.0, 4.0e5, 7.3),
                                         (8.0, 1.0, 5.0), (1e6, 8.05e6, 10.0)])
def test_tx_delay_matches_the_system(bits, hz, eta):
    svc = ServiceRequest(id=0, deadline=1.0, spectral_eff=eta)
    assert yardstick.tx_delay(bits, hz, eta) == pytest.approx(
        svc.tx_delay(hz, bits), rel=1e-15)


@pytest.mark.parametrize("cfg", [SMOKE, CONFIG], ids=lambda c: c.name)
def test_flops_match_xla_cost_analysis(cfg):
    """XLA's count for the forward at batch 1 also holds the elementwise
    work (normalisation, SiLU, softmax, adds), which ours leaves out: at
    CONFIG that is 0.4% of the total (11,665,521,664 against our
    11,619,506,176), at the smoke size 1.6%.  So ours lies below XLA's
    by at most 2.5%, and never above it."""
    sz = dataclasses.asdict(cfg)
    p = abstract_params(unet.schema(cfg), jnp.float32)
    x = jax.ShapeDtypeStruct((1, cfg.image_size, cfg.image_size,
                              cfg.in_channels), jnp.float32)
    t = jax.ShapeDtypeStruct((1,), jnp.float32)
    compiled = jax.jit(lambda p, x, t: unet.forward(cfg, p, x, t)) \
        .lower(p, x, t).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ours = yardstick.unet_flops_per_row(sz)
    assert 0.975 * cost["flops"] <= ours <= cost["flops"]


def test_config_flops_and_kernel_calls():
    sz = dataclasses.asdict(CONFIG)
    assert yardstick.unet_flops_per_row(sz) == pytest.approx(11.7e9,
                                                             rel=0.01)
    # 22 residual blocks with two kernel calls each, and the output norm
    assert len(yardstick.gn_silu_shapes(sz)) == 45


def test_gn_bytes():
    assert yardstick.gn_silu_call_bytes(2, 1024, 128) == \
        2 * 2 * 1024 * 128 * 4 + 2 * 128 * 4


def test_unknown_device_is_an_error():
    assert yardstick.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        yardstick.peaks("cpu")
