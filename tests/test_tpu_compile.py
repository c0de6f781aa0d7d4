"""Compile rehearsals for one TPU v5e chip, with no chip attached.

The TPU compiler is installed with jax, and compiles for a described
``v5e:2x2`` topology: it refuses what the chip would refuse (layouts
Mosaic cannot lower, kernels over their scoped VMEM, programs over the
device's memory), though nothing runs.  Covered here: the Pallas
GroupNorm+SiLU kernel at every width the published ``ddim-cifar10``
U-Net feeds it, and the bucketed gather->DDIM-step->scatter program at
that width, compiled from ``jax.eval_shape`` parameter shapes; and the
same program for DiT-XL/2 at 256x256, with the whole-sequence attention
kernel at its published shapes.

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU library, and every
pytest-xdist worker imports this file.  All rehearsals stay in this one
file so that one worker loads the library for all of them.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.ddim_cifar10 import CONFIG, DIT_XL_2
from repro.diffusion import dit, unet
from repro.diffusion.executor import BatchDenoisingExecutor, pool_step
from repro.kernels.groupnorm_silu.kernel import groupnorm_silu_pallas
from repro.models.params import abstract_params, init_params

# (H, W, C) inputs of every GroupNorm+SiLU in the CONFIG U-Net, plus
# (32, 32, 512): the largest single-image block (2 MB f32) the kernel
# must hold in VMEM at CIFAR scale
GN_WIDTHS = [(4, 4, 256), (4, 4, 512), (8, 8, 256), (8, 8, 512),
             (16, 16, 128), (16, 16, 256), (16, 16, 384), (16, 16, 512),
             (32, 32, 128), (32, 32, 256), (32, 32, 384), (32, 32, 512)]
BUCKET = 8          # largest batch bucket of an 8-service session
DIT_BUCKET = 32     # largest bucket of the 32-service DiT cell


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:    # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A program compiled for a described chip is written to the cache
    but cannot be read back; keep the cache off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _param_shapes(sharding):
    shapes = jax.eval_shape(
        lambda k: init_params(unet.schema(CONFIG), k),
        jax.random.PRNGKey(0))
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        shapes)


def test_gn_widths_cover_the_published_unet():
    """GN_WIDTHS holds every GroupNorm+SiLU input of the CONFIG U-Net,
    read off the model itself."""
    seen = set()

    def record(x, scale, bias, groups):
        seen.add(tuple(x.shape[1:]))
        return unet.gn_silu_xla(x, scale, bias, groups)

    x = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
    t = jax.ShapeDtypeStruct((2,), jnp.int32)
    jax.eval_shape(functools.partial(unet.forward, CONFIG, norm=record),
                   _param_shapes(None), x, t)
    assert seen and seen <= set(GN_WIDTHS), sorted(seen - set(GN_WIDTHS))


@pytest.mark.parametrize("H,W,C", GN_WIDTHS)
def test_groupnorm_silu_compiles_for_v5e(one_chip, H, W, C):
    x = jax.ShapeDtypeStruct((BUCKET, H, W, C), jnp.float32,
                             sharding=one_chip)
    v = jax.ShapeDtypeStruct((C,), jnp.float32, sharding=one_chip)
    fn = functools.partial(groupnorm_silu_pallas,
                           num_groups=CONFIG.num_groups)
    compiled = jax.jit(fn).lower(x, v, v).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bucketed_step_compiles_for_v5e(one_chip, monkeypatch):
    """The executor's own bucketed step program at CONFIG width, one
    bucket, with the Pallas kernel on: the described chip is not the
    default device, so the test steers the kernel dispatch itself."""
    import repro.kernels
    monkeypatch.setattr(repro.kernels, "use_pallas", lambda: "tpu")
    params = _param_shapes(one_chip)
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert 35e6 < n_params < 36e6
    ex = BatchDenoisingExecutor(CONFIG, params)
    rows = BUCKET + 1
    shape = (CONFIG.image_size, CONFIG.image_size, CONFIG.in_channels)
    pool = jax.ShapeDtypeStruct((rows,) + shape, jnp.float32,
                                sharding=one_chip)
    lane = jax.ShapeDtypeStruct((BUCKET,), jnp.int32, sharding=one_chip)
    prog = ex.program(("bstep", rows, BUCKET), pool_step(ex.step_fn),
                      (params, pool, lane, lane, lane), donate=(1,))
    # one kernel per GroupNorm+SiLU of the forward pass
    assert prog.as_text().count("tpu_custom_call") == 45
    mem = prog.memory_analysis()
    assert mem.argument_size_in_bytes > 4 * n_params


def test_dit_bucketed_step_compiles_for_v5e(one_chip, monkeypatch):
    """DiT-XL/2's bucketed step at its largest bucket: one
    whole-sequence attention kernel per block, on q, k and v of [32 rows,
    1, 256 tokens, 16 heads x 72] straight from the qkv projection, with
    no relayout copy to or from heads-first [32, 256, 16, 72], and the
    f32 weights as arguments."""
    import repro.kernels
    monkeypatch.setattr(repro.kernels, "use_pallas", lambda: "tpu")
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        abstract_params(dit.schema(DIT_XL_2), jnp.float32))
    n_params = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert 668e6 < n_params < 682e6
    ex = BatchDenoisingExecutor(DIT_XL_2, params)
    rows = DIT_BUCKET + 1
    shape = (DIT_XL_2.image_size, DIT_XL_2.image_size, DIT_XL_2.in_channels)
    pool = jax.ShapeDtypeStruct((rows,) + shape, jnp.float32,
                                sharding=one_chip)
    lane = jax.ShapeDtypeStruct((DIT_BUCKET,), jnp.int32, sharding=one_chip)
    prog = ex.program(("bstep", rows, DIT_BUCKET), pool_step(ex.step_fn),
                      (params, pool, lane, lane, lane), donate=(1,))
    lines = prog.as_text().splitlines()
    calls = [line for line in lines if "tpu_custom_call" in line]
    assert len(calls) == DIT_XL_2.depth
    for call in calls:
        assert call.split(" = ")[1].startswith("f32[32,1,256,1152]")
    # no copy lays q, k, v or o out heads-first, nor feeds a call, even
    # through a bitcast
    assert not [line for line in lines if " copy(" in line
                and re.search(r"= \w+\[32,256,16,72\]", line)]
    defs = {name: (kind, re.findall(r"%([\w.-]+)", args))
            for name, kind, args in re.findall(
                r"%([\w.-]+) = \S+ ([\w-]+)\(([^)]*)\)", "\n".join(lines))}

    def made_by(name):
        while defs[name][0] == "bitcast":
            name = defs[name][1][0]
        return defs[name][0]

    for call in calls:
        for arg in defs[call.split(" = ")[0].strip().lstrip("%")][1]:
            assert not made_by(arg).startswith("copy"), call
    mem = prog.memory_analysis()
    assert mem.argument_size_in_bytes > 4 * n_params
