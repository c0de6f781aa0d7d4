"""``plan_many`` sharded over devices: the scenario axis split across
host/accelerator devices with ``shard_map``, one jitted program per
(device set, key-bit) combination.

``plan_many`` (repro.core.jaxplan.batched) already amortizes Python
dispatch by stacking ~10^3 scenarios into one jitted call, but that
call still runs on a single device.  Fleet-scale replanning — every
cell of an edge deployment replanned each tick — wants the scenario
axis spread over whatever devices the host exposes (real accelerators,
or CPU host devices via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).  Scenario
rows are fully independent, so the split is embarrassingly parallel:

* the S axis is padded to ``n_devices * bucket(ceil(S / n))`` — the
  padding rows are all-invalid scenarios that plan to nothing and are
  stripped from the result (a device whose shard is entirely padding
  simply converges in zero rounds);
* each device runs the SAME fused search (``kernels._plan_many_block``:
  vmapped clustered sweep -> masked power-law scoring -> first-best
  scan) on its block; no cross-device communication is needed, so the
  per-row arithmetic is identical to the single-device call and the
  equivalence contract stays the documented 1e-9 mean-FID tolerance
  against single-device ``plan_many`` and the vec loop
  (tests/test_jaxplan_sharded.py enforces it at device counts 1/2/8);
* compiled programs are cached per (device tuple, radix key bits), so
  repeated replan ticks at a stable fleet size pay compilation once.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional, Sequence, Union

import numpy as np

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.delay_model import DelayModel
from repro.core.jaxplan import kernels
from repro.core.jaxplan.batched import (PlanManyResult, _check_inputs,
                                        _pad_stack, _replan_prep)
from repro.core.quality_model import PowerLawFID

#: scenarios-per-device type of the ``devices=`` knob
Devices = Union[None, int, Sequence]


def resolve_devices(devices: Devices = None):
    """The device list a sharded plan will run on: ``None``/``0`` =
    every local device, an int n = the first n local devices (failing
    loudly when the host exposes fewer), or an explicit sequence of
    jax devices passed through as-is."""
    if devices is None or devices == 0:
        return list(jax.devices())
    if isinstance(devices, int):
        avail = jax.devices()
        if devices < 0 or devices > len(avail):
            raise ValueError(
                f"devices={devices} requested but only {len(avail)} "
                f"jax device(s) are configured; on CPU, export "
                f"XLA_FLAGS=--xla_force_host_platform_device_count=N "
                f"before jax initializes")
        return avail[:devices]
    devs = list(devices)
    if not devs:
        raise ValueError("devices must name at least one jax device")
    return devs


@lru_cache(maxsize=None)
def _sharded_fn(devs: tuple, key_bits: int):
    """The compiled sharded search for one device set: ``shard_map`` of
    ``kernels._plan_many_block`` with the scenario axis split across
    ``devs``.  Cached so replan ticks at a stable fleet size reuse one
    executable."""
    block = partial(kernels._plan_many_block, key_bits=key_bits)
    sharded = P("s")
    fn = jax.shard_map(
        block, mesh=Mesh(np.array(devs), ("s",)),
        in_specs=(sharded, sharded, sharded, sharded, sharded,
                  P(None), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(sharded, sharded, sharded, sharded),
        # the block is replication-free by construction (every output
        # is P("s")-sharded); the checker has no rule for
        # lax.while_loop, so it must be told rather than asked
        check_vma=False)
    return jax.jit(fn)


@lru_cache(maxsize=None)
def _sharded_replan_fn(devs: tuple, key_bits: int):
    """The compiled sharded REPLAN search (``_replan_many_block``) for
    one device set: same split as ``_sharded_fn`` plus the two extra
    per-row inputs (doomed mask, per-scenario level validity)."""
    block = partial(kernels._replan_many_block, key_bits=key_bits)
    sharded = P("s")
    fn = jax.shard_map(
        block, mesh=Mesh(np.array(devs), ("s",)),
        in_specs=(sharded, sharded, sharded, sharded, sharded,
                  sharded, P(None), sharded, P(), P(), P(), P(),
                  P(), P(), P()),
        out_specs=(sharded, sharded, sharded, sharded),
        check_vma=False)
    return jax.jit(fn)


def plan_many_sharded(tau_prime: np.ndarray, *, delay: DelayModel,
                      quality: PowerLawFID,
                      offsets: Optional[np.ndarray] = None,
                      valid: Optional[np.ndarray] = None,
                      t_star_max: int = 0,
                      devices: Devices = None) -> PlanManyResult:
    """``plan_many`` with the scenario axis sharded across devices.

    Same inputs and result type as ``plan_many`` plus the ``devices``
    knob (see ``resolve_devices``).  S is padded up to a multiple of
    the device count with all-invalid scenario rows; the padding is
    masked inside the kernel and stripped from the result, so S need
    not be divisible by (or even as large as) the device count.
    """
    devs = resolve_devices(devices)
    D = len(devs)
    taup0, off, vd, S, K = _check_inputs(tau_prime, quality, offsets,
                                         valid)
    # pad S to D equal blocks, each a power-of-two bucket so a growing
    # fleet reuses a handful of compiled variants per device count
    rows = kernels._bucket(max(1, -(-S // D)))
    taup_p, off_p, vd_p, tie, f_thr, lv_p, shift, kb = _pad_stack(
        taup0, off, vd, delay, t_star_max, D * rows)

    fn = _sharded_fn(tuple(devs), kb)
    with jax.enable_x64(True):
        best_i, counts, best_q, ms = fn(
            taup_p, off_p, vd_p, tie, f_thr, lv_p, shift, delay.a,
            delay.b, quality.alpha, quality.beta, quality.gamma,
            quality.fid_at_zero)
    best_i, counts = np.asarray(best_i)[:S], np.asarray(counts)
    best_q, ms = np.asarray(best_q), np.asarray(ms)
    return PlanManyResult(
        best_level=lv_p[np.maximum(best_i, 0)].astype(np.int64),
        steps=counts[:S, :K],
        mean_fid=best_q[:S],
        makespan=ms[:S],
    )


def replan_many_sharded(tau_prime: np.ndarray, *, delay: DelayModel,
                        quality: PowerLawFID,
                        offsets: Optional[np.ndarray] = None,
                        doomed: Optional[np.ndarray] = None,
                        valid: Optional[np.ndarray] = None,
                        t_star_max: int = 0,
                        devices: Devices = None) -> PlanManyResult:
    """``replan_many`` with the scenario axis sharded across devices —
    the shared-horizon residual-replan semantics of ``plan_many_sharded``
    (see ``repro.core.jaxplan.batched.replan_many`` for the contract)."""
    devs = resolve_devices(devices)
    D = len(devs)
    taup0, soff, vd, S, K = _check_inputs(tau_prime, quality, offsets,
                                          valid)
    dm = np.zeros((S, K), dtype=bool) if doomed is None \
        else np.broadcast_to(np.asarray(doomed, dtype=bool),
                             (S, K)).copy()
    rows = kernels._bucket(max(1, -(-S // D)))
    (taup_p, soff_p, vd_p, dm_p, tie, f_thr, lv_p, lv_ok, shift,
     kb) = _replan_prep(taup0, soff, vd, dm, delay, t_star_max,
                        D * rows)

    fn = _sharded_replan_fn(tuple(devs), kb)
    with jax.enable_x64(True):
        best_i, counts, best_q, ms = fn(
            taup_p, soff_p, vd_p, dm_p, tie, f_thr, lv_p, lv_ok, shift,
            delay.a, delay.b, quality.alpha, quality.beta, quality.gamma,
            quality.fid_at_zero)
    best_i, counts = np.asarray(best_i)[:S], np.asarray(counts)
    best_q, ms = np.asarray(best_q), np.asarray(ms)
    return PlanManyResult(
        best_level=lv_p[np.maximum(best_i, 0)].astype(np.int64),
        steps=counts[:S, :K],
        mean_fid=best_q[:S],
        makespan=ms[:S],
    )
