"""The DiT denoiser against the benchmark's plain reference, on the CPU.

The reference (``chipbench/configs/dit-xl-2-256.py``) is written apart
from the system: a Python loop over the blocks, a strided convolution
for the patches, plain softmax attention.  The comparisons run a small
DiT (depth 2, hidden 96, 4 heads of 24, patch 2, an 8x8x4 latent,
``learn_sigma``) on the reference's seeded weights, and check the
published size by shapes alone.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import reference, spec  # noqa: E402
from repro.api import DiffusionWorkload, Provisioner  # noqa: E402
from repro.core.plan import BatchPlan  # noqa: E402
from repro.configs.ddim_cifar10 import DIT_XL_2, UNetConfig  # noqa: E402
from repro.core.delay_model import DelayModel  # noqa: E402
from repro.core.quality_model import PowerLawFID  # noqa: E402
from repro.core.service import make_scenario  # noqa: E402
from repro.core.stacking import stacking  # noqa: E402
from repro.diffusion import dit  # noqa: E402
from repro.diffusion.executor import BatchDenoisingExecutor  # noqa: E402
from repro.models.params import abstract_params  # noqa: E402

REF = spec.reference("dit-xl-2-256")
SMALL = UNetConfig(name="dit-small-test", arch="dit", image_size=8,
                   in_channels=4, patch_size=2, hidden_size=96, depth=2,
                   num_heads=4, mlp_ratio=4.0, num_classes=1000,
                   frequency_embedding_size=256)
SZ = dataclasses.asdict(SMALL)
SEED = 2_147_483_659          # above 2**31, as the benchmark's seeds are

# Both sides compute in float32 on the CPU, where a float32 matmul is
# exact to float32 rounding; they differ only in the order of sums (a
# reshape-and-matmul patch embedding against a strided convolution,
# attention on the heads side by side against one head at a time, a scan
# against a loop), so a relative error of a few 1e-7 is expected.
# 1e-5 leaves room for that and none for a missing or misplaced term.
FWD_TOL = 1e-5
# A DDIM chain divides by sqrt(alpha_bar) at its first (noisiest) step,
# which magnifies the rounding above about a hundredfold.
CHAIN_TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    return REF.make_params(SZ, SEED, "float32")


def _rel(a, b):
    return reference.rel_err(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("kernel", ["xla", "pallas-interpret"])
def test_forward_matches_reference(params, kernel, monkeypatch):
    """Attention by XLA's softmax, and by the whole-sequence kernel
    that a TPU runs (here interpreted)."""
    if kernel == "pallas-interpret":
        monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 8, 4))
    t = jnp.array([999.0, 500.0, 3.0])
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, x, t: dit.forward(SMALL, p, x, t))(
            params, x, t)
        want = jax.jit(lambda p, x, t: REF.forward(SZ, p, x, t))(
            params, x, t)
    assert got.shape == x.shape
    # the noise is of order one, so the comparison is not of zeros
    assert 0.3 < float(jnp.abs(want).mean()) < 3.0
    assert _rel(got, want) < FWD_TOL


def test_rows_independent_in_a_mixed_timestep_batch(params):
    """Each row's noise is what it would be alone: the per-sample
    conditioning that lets one batch mix services at different steps."""
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 8, 8, 4))
    t = jnp.array([980.0, 20.0, 500.0, -1.0])
    fwd = jax.jit(lambda p, x, t: dit.forward(SMALL, p, x, t))
    together = fwd(params, x, t)
    for i in range(4):
        alone = fwd(params, x[i:i + 1], t[i:i + 1])
        assert _rel(together[i], alone[0]) < FWD_TOL
    # and the timestep matters
    assert _rel(together[0], fwd(params, x[:1], t[1:2])[0]) > 0.01


def test_bucketed_session_matches_reference_chains(params):
    """A STACKING plan of five services run by the bucketed session,
    against the reference's DDIM chains replayed from the session's
    calls (``chipbench.reference``), from the same noise."""
    scn = make_scenario(K=5, seed=4)
    tau = {s.id: s.deadline for s in scn.services}
    plan = stacking(scn.services, tau, DelayModel(a=0.25, b=1.0),
                    PowerLawFID())
    wl = DiffusionWorkload(cfg=SMALL, params=params)
    key = jax.random.PRNGKey(5)
    sess = wl.open_session(plan, key)
    events = []
    for batch in plan.batches:
        ks = [k for k, _ in batch]
        events.append(("batch", ks))
        sess.run_batch(ks)
    assert sess.telemetry()["arch"] == "dit"
    images = sess.finish()
    initial = dict(plan.steps_completed)
    chains = reference.chains(initial, events)
    ids = sorted(initial)
    stepped = [k for k in ids if chains[k]]
    assert len(stepped) >= 3 and len({len(chains[k]) for k in stepped}) >= 2
    starts = [reference.initial_noise(key, len(ids), ids.index(k), (8, 8, 4))
              for k in stepped]
    want = reference.run_chains(REF, SZ, SEED, starts,
                                [chains[k] for k in stepped],
                                precision="highest", rows=4)
    for k, w in zip(stepped, want):
        assert _rel(images[k], w) < CHAIN_TOL


def test_closed_loop_through_the_provisioner():
    """DiT runs through the front door as the U-Net does; with no
    params the workload builds them from DiT's schema."""
    wl = DiffusionWorkload(cfg=SMALL)
    scn = make_scenario(K=4, seed=6)
    rep = Provisioner(scn, workload=wl, scheduler="stacking_offset",
                      allocator="inv_se", delay=DelayModel(a=0.3, b=1.0),
                      execute_kwargs={"max_replans": 0}).run(
        jax.random.PRNGKey(7), execute="closed")
    ex = rep.execution
    assert ex.session_telemetry["arch"] == "dit"
    assert set(ex.content) == {s.id for s in scn.services}
    for img in ex.content.values():
        assert np.shape(img) == (8, 8, 4) and np.isfinite(img).all()
    assert sum(r.size for r in ex.records) > 0
    assert set(jax.tree.leaves(jax.tree.map(jnp.shape, wl.params)))
    assert "blocks" in wl.params


def _paths(tree, prefix=""):
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_paths(v, f"{prefix}{k}/"))
        return out
    return {prefix[:-1]: tuple(tree.shape)}


def test_schema_matches_reference_layout_at_published_width():
    sz = dataclasses.asdict(DIT_XL_2)
    got = _paths(abstract_params(dit.schema(DIT_XL_2), jnp.float32))
    want = {k: shape for k, (shape, _) in REF.layout(sz).items()}
    assert got == want
    n = sum(int(np.prod(s)) for s in got.values())
    assert n == pytest.approx(675e6, rel=0.01)


def test_flops_match_xla_cost_analysis(params):
    """XLA's count for the forward at batch 1 also holds the
    elementwise work (LayerNorm, modulation, softmax, GELU, adds),
    which ours leaves out: at this small size 3.1% of the total.  So
    ours lies below XLA's by at most 5%, and never above it."""
    x = jax.ShapeDtypeStruct((1, 8, 8, 4), jnp.float32)
    t = jax.ShapeDtypeStruct((1,), jnp.float32)
    compiled = jax.jit(lambda p, x, t: dit.forward(SMALL, p, x, t)) \
        .lower(params, x, t).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    ours = spec.metric_module("dit_mfu").flops_per_row(SZ)
    assert 0.95 * cost["flops"] <= ours <= cost["flops"]
    big = spec.metric_module("dit_mfu").flops_per_row(
        dataclasses.asdict(DIT_XL_2))
    assert big == pytest.approx(237.2e9, rel=0.005)     # 118.6 GMACs


def test_harness_builds_the_dit_config_from_its_file():
    """``Bench.build`` makes the workload's config from the cell's
    configuration file, keeping only ``UNetConfig`` fields: the DiT keys
    must be fields, or the harness would build a U-Net config, or a DiT
    of zero width, from the DiT file."""
    sizes = spec.resolve("dit-xl2-256.dense-k32").sizes
    fields = {f.name for f in dataclasses.fields(UNetConfig)}
    cfg = UNetConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                        for k, v in sizes.items() if k in fields})
    assert cfg.arch == "dit"
    for key in ("image_size", "in_channels", "patch_size", "hidden_size",
                "depth", "num_heads", "mlp_ratio", "num_classes",
                "frequency_embedding_size",
                "num_train_timesteps"):
        assert key in fields and getattr(cfg, key) == sizes[key], key
    # what dit.out_channels and the null-label conditioning assume
    assert sizes["learn_sigma"] is True
    assert sizes["class_label"] == sizes["num_classes"]
    assert cfg == DIT_XL_2


def test_attention_path_engages_the_whole_sequence_kernel(monkeypatch):
    """A session of 32 DiT-XL/2 services on a TPU runs the whole-sequence
    kernel; 1024 tokens (DiT-XL/2 at 512x512) do not fit a row in VMEM
    and are refused, not run another way; off the chip XLA runs
    attention; a U-Net session names no attention path."""
    import repro.kernels
    monkeypatch.setattr(repro.kernels, "use_pallas", lambda: "tpu")
    params = abstract_params(dit.schema(DIT_XL_2), jnp.float32)
    ex = BatchDenoisingExecutor(DIT_XL_2, params)
    plan = BatchPlan(batches=[], start_times=[],
                     steps_completed={k: 20 for k in range(32)},
                     delay=DelayModel(a=0.0034, b=0.0007))
    tel = ex.open_session(plan, jax.random.PRNGKey(0)).telemetry()
    assert tel["arch"] == "dit" and tel["attn"] == "whole"
    assert ex.arch_stats() == {"arch": "dit", "attn": "whole"}
    dit_512 = dataclasses.replace(DIT_XL_2, image_size=64)
    with pytest.raises(ValueError, match="does not fit"):
        dit.attention_path(dit_512)
    monkeypatch.setattr(repro.kernels, "use_pallas", lambda: "ref")
    assert dit.attention_path(DIT_XL_2) == "xla"
    unet_cfg = dataclasses.replace(SMALL, arch="unet")
    assert "attn" not in BatchDenoisingExecutor(unet_cfg, {}).arch_stats()


def test_roofline_reads_the_whole_sequence_call():
    """``flash_attn_roofline`` finds the whole-sequence kernel's call,
    q, k, v and o each [32, 1, 256, 1152], by its signature as the
    device trace prints it, and counts it as the tiled kernel's
    [32, 16, 256, 72]: with h = 1 and d = H*Dh the operations and the
    bytes are unchanged."""
    mod = spec.metric_module("flash_attn_roofline")
    x = "f32[32,1,256,1152]{3,2,1,0:T(8,128)}"
    call = (f"%dit.attn.56 = {x} custom-call({x} %get-tuple-element.223, "
            f"{x} %get-tuple-element.222, {x} %get-tuple-element.221), "
            'custom_call_target="tpu_custom_call", operand_layout_'
            "constraints={f32[32,1,256,1152]{3,2,1,0}, "
            "f32[32,1,256,1152]{3,2,1,0}, f32[32,1,256,1152]{3,2,1,0}}")
    assert list(mod.calls([(call, 1e-4)])) == [(32, 1, 256, 1152, 1e-4)]
    B, H, S, Dh = 32, 16, 256, 72
    assert mod.call_flops(32, 1, 256, 1152) == 4 * B * H * S * S * Dh
    assert mod.call_bytes(32, 1, 256, 1152) == 4 * B * S * H * Dh * 4
    assert mod.call_flops(32, 1, 256, 1152) == mod.call_flops(B, H, S, Dh)
