"""jnp ports of the (L, K) planning sweeps: ``lax.while_loop`` over
fixed-shape per-round state, every T*/water-level candidate advancing
together — the jit-compiled counterpart of ``repro.core.arrays``'
``_clustered_rounds`` / ``_lockstep_rounds``.

The kernels mirror the NumPy sweeps operation for operation (same
float64 arithmetic, same ``1e-12`` epsilons, same composite integer
sort keys), but jit/XLA may reassociate reductions and ``pow`` may
differ from libm in the last ulp, so the contract is *tolerance*
equivalence of objectives — never bit identity (that stays the NumPy
vec engine's contract against the scalar reference).  See
docs/PERFORMANCE.md ("jax engine").

Only completed *counts* and makespans come out of the jitted loops:
batch lists are inherently ragged, so the winning candidate is
materialized afterwards by the exact NumPy single-level pass
(``arrays.stacking_pass_vec`` / ``arrays.offset_pass_vec``) — the jax
engine spends its time where the work is, scoring L x K x rounds, and
returns plans constructed by the same code every other engine uses.

All public helpers here take/return NumPy arrays and run the jitted
core under ``jax.enable_x64(True)`` so the planner's float64
semantics never leak x64 config into the rest of the process (the
Pallas denoiser kernels stay float32).  Shapes are padded to
power-of-two buckets (``_bucket``) so online replans — whose residual
K and level count shrink every event — reuse a handful of compiled
variants instead of recompiling per instant.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.delay_model import DelayModel

# same sentinel as repro.core.arrays._TP_INF (not imported: this module
# must stay importable while arrays is mid-initialization during an
# env-var backend probe)
_TP_INF = np.int64(1) << 62


def _bucket(n: int) -> int:
    """Padded-shape buckets that bound jit recompilation across
    shrinking replan instances: powers of two (min 8) up to 4096 —
    the regime online replans churn through — then multiples of 2048,
    where population-scale sweeps would otherwise pay up to 2x padding
    for one extra compiled variant (K=10^4 pads to 10240, not 16384)."""
    if n <= 4096:
        return max(8, 1 << max(0, int(n - 1).bit_length()))
    return 2048 * ((int(n) + 2047) // 2048)


# -------------------------------------------------------------------------
# Per-round selection: the x_n-th smallest composite key, sort-free
# -------------------------------------------------------------------------
#
# The batching step needs ONE number per candidate row: the x_n-th
# smallest composite key (``Tp * M + tie``), which is the membership
# threshold of the round's batch.  A full ``jnp.sort`` over the (L, K)
# key table delivers it but dominates the whole kernel at K = 10^4 on
# CPU (XLA's sort is scalar per row; NumPy's beats it, which is why
# the single-scenario jax row used to lose to vec at that size).  The
# keys are bounded non-negative integers with a host-computable bit
# width, so a bitwise (radix) *selection* finds the same threshold in
# ``key_bits`` fused compare-and-count passes — no ordering of the
# inactive tail, no data movement, and it vectorizes over every
# candidate row and (under vmap) every scenario at once.

def _select_kth_key(key, x_n, key_bits):
    """The ``x_n``-th smallest value of ``key`` along the last axis,
    per row, via bitwise binary search: the largest ``T`` with
    ``count(key < T) < x_n`` over a monotone predicate IS that order
    statistic when keys are unique integers (they are: every active
    key embeds a distinct tie rank, and x_n never exceeds the active
    count, so the sentinel tail is never selected).  ``key_bits`` (a
    static python int) bounds the real-key domain; rows with
    ``x_n == 0`` return 0 and must be masked by the caller (the scalar
    path's ``thr = -1`` rule)."""

    one = jnp.ones((), dtype=key.dtype)

    def bit_step(i, thr):
        bit = (key_bits - 1 - i).astype(key.dtype)
        cand = thr | jnp.left_shift(one, bit)
        cnt = jnp.sum(key < cand[..., None], axis=-1, dtype=jnp.int64)
        return jnp.where(cnt < x_n, cand, thr)

    thr0 = jnp.zeros(key.shape[:-1], dtype=key.dtype)
    return lax.fori_loop(0, key_bits, bit_step, thr0)


def _sort_kth_key(key, x_n):
    """Reference selection via the full composite-key sort (the
    pre-sharding scheme), kept for the decision-identity property
    tests in tests/test_jaxplan_properties.py."""
    sorted_key = jnp.sort(key, axis=-1)
    return jnp.take_along_axis(sorted_key,
                               jnp.maximum(x_n - 1, 0)[..., None],
                               axis=-1)[..., 0]


def _key_bits(taup0: np.ndarray, off: np.ndarray, shift: int,
              step_cost: float) -> int:
    """Static bit width of the composite-key domain for a (possibly
    scenario-stacked) instance: real keys are ``Tp * M + tie`` with
    ``Tp <= tp_bound`` (the same bound ``_f_threshold`` clamps to), so
    every active key fits in this many bits and the radix selection's
    trip count is a host-side constant the jit cache can key on."""
    M = np.int64(1) << np.int64(shift)
    te0_max = np.int64(np.max(np.maximum(taup0, 0.0), initial=0.0)
                       / step_cost)
    tp_bound = np.int64(np.max(off, initial=0) if off.size else 0) \
        + 2 * te0_max + 4
    return max(1, int((tp_bound + 1) * M - 1).bit_length())


# -------------------------------------------------------------------------
# The clustered (Algorithm-1) sweep
# -------------------------------------------------------------------------

#: level rows per independently-converging while_loop (divides every
#: ``_bucket`` size).  Per-level round counts are heavily skewed — deep
#: levels converge in 2-4 rounds while shallow ones take 30+ — so one
#: lockstep loop over all L rows pays max_rounds * L row-rounds.
#: Chunking the level axis into CHUNK-row loops (run sequentially by
#: ``lax.map``) pays only sum(chunk_max * CHUNK), a ~3x cut at K=10^4,
#: and makes the L padding nearly free (pad chunks converge instantly).
_LEVEL_CHUNK = 4


def _clustered_chunk(taup0, off, levels, tie, f_thr, shift, a, b,
                     key_bits):
    """One scenario's Algorithm-1 rounds over one chunk of candidate
    levels: ``(taup0 (K,), off (K,), levels (Lc,), tie (K,), f_thr
    (Lc,))`` -> ``(Tc (Lc, K) int64, makespan (Lc,) float64)``.
    Literal port of ``arrays._clustered_rounds`` minus history
    recording and live-level compaction (the fixed-shape chunk runs
    every level to the end), with the per-round full sort replaced by the
    decision-identical radix selection (``key_bits`` is the static
    trip count)."""
    L, K = levels.shape[0], taup0.shape[0]
    g1 = a * 1 + b                       # delay.min_task_delay()
    step_cost = a + b
    # composite keys fit in ``key_bits`` (static, host-derived), so the
    # integer round state — keys, counts, Tp — runs in int32 whenever
    # the domain allows: identical integer arithmetic, half the memory
    # traffic of int64 on the K=10^4 sweeps the radix selection serves
    idt = jnp.int32 if key_bits <= 31 else jnp.int64
    sent = jnp.asarray(jnp.iinfo(idt).max, idt)  # past every real key
    M = (jnp.int64(1) << shift).astype(idt)
    tie_i = tie.astype(idt)
    f_thr_i = f_thr.astype(idt)          # values bounded by key_bits
    off_i = off.astype(idt)

    lv_pos = levels > 0
    lv_f = levels.astype(jnp.float64)
    b_lv = b * lv_f
    a_lv = a * jnp.maximum(lv_f, 1.0)

    taup = jnp.tile(taup0, (L, 1))
    Tc = jnp.zeros((L, K), dtype=idt)
    active = jnp.tile(taup0 >= g1, (L, 1))
    t = jnp.zeros((L,), dtype=jnp.float64)

    def cond(state):
        _, _, active, _ = state
        return active.any()

    def body(state):
        taup, Tc, active, t = state
        # ---- clustering (Eqs. 15-18, offset-shifted) -----------------
        Te = (taup / step_cost).astype(idt)
        Tp = off_i[None, :] + Tc + Te
        key = jnp.where(active, Tp * M + tie_i[None, :], sent)

        n_active = active.sum(axis=-1, dtype=jnp.int64)
        F = key <= f_thr_i[:, None]
        n_F = F.sum(axis=-1, dtype=jnp.int64)

        # ---- packing (Eqs. 19-20) ------------------------------------
        te_max = jnp.max(jnp.where(F, Te, -1), axis=-1)
        tau_min = jnp.min(jnp.where(F, taup, jnp.inf), axis=-1)
        cap_f = jnp.floor((tau_min - b * te_max)
                          / (a * jnp.maximum(te_max, 1)))
        tp_min = jnp.right_shift(key.min(axis=-1), shift.astype(idt))
        cap_nf = jnp.floor((step_cost * tp_min - b_lv) / a_lv)
        x_f = jnp.where(te_max > 0,
                        jnp.maximum(n_F, jnp.minimum(n_active, cap_f)),
                        n_F)
        x_nf = jnp.minimum(n_active,
                           jnp.where(lv_pos, jnp.maximum(1, cap_nf),
                                     n_active))
        x_n = jnp.where(n_F > 0, x_f, x_nf)
        x_n = jnp.maximum(1, jnp.minimum(x_n, n_active))
        x_n = jnp.where(n_active > 0, x_n, 0).astype(jnp.int64)

        # ---- batching -------------------------------------------------
        # membership threshold = the x_n-th smallest key, selected
        # without sorting (see _select_kth_key above)
        thr = _select_kth_key(key, x_n, key_bits)
        thr = jnp.where(x_n > 0, thr, jnp.asarray(-1, idt))
        packed0 = key <= thr[:, None]

        def drop_cond(s):
            packed, _, n_packed = s
            g = a * n_packed + b
            return (packed & (taup + 1e-12 < g[:, None])).any()

        def drop_body(s):
            packed, act, n_packed = s
            g = a * n_packed + b
            drop = packed & (taup + 1e-12 < g[:, None])
            packed = packed & ~drop         # cannot afford this batch ->
            act = act & ~drop               # service is finished
            n_packed = packed.sum(axis=-1, dtype=jnp.int64)
            return packed, act, n_packed

        packed, active, n_packed = lax.while_loop(
            drop_cond, drop_body, (packed0, active, x_n))

        has_batch = n_packed > 0
        g = a * n_packed + b
        t = t + jnp.where(has_batch, g, 0.0)
        adv = active & has_batch[:, None]   # wall clock advances for all
        taup = taup - jnp.where(adv, g[:, None], 0.0)      # (Eq. 15)
        Tc = Tc + packed.astype(idt)
        # services that can no longer fit even a dedicated batch are done
        active = active & (taup + 1e-12 >= g1)
        return taup, Tc, active, t

    _, Tc, _, t = lax.while_loop(cond, body, (taup, Tc, active, t))
    return Tc.astype(jnp.int64), t


def _clustered_core(taup0, off, levels, tie, f_thr, shift, a, b,
                    key_bits):
    """All L candidate levels, as ``_LEVEL_CHUNK``-row chunks swept
    sequentially (``lax.map``) so each chunk's while_loop stops when
    ITS levels converge instead of riding along to the globally
    slowest row.  Row arithmetic is identical to one big lockstep
    loop — chunks are independent level rows of the same instance."""
    L, K = levels.shape[0], taup0.shape[0]
    if L % _LEVEL_CHUNK:                  # non-bucket L: one lockstep loop
        return _clustered_chunk(taup0, off, levels, tie, f_thr, shift,
                                a, b, key_bits)
    C = L // _LEVEL_CHUNK

    def one_chunk(xs):
        lv_c, f_thr_c = xs
        return _clustered_chunk(taup0, off, lv_c, tie, f_thr_c, shift,
                                a, b, key_bits)

    Tc, t = lax.map(one_chunk, (levels.reshape(C, _LEVEL_CHUNK),
                                f_thr.reshape(C, _LEVEL_CHUNK)))
    return Tc.reshape(L, K), t.reshape(L)


# -------------------------------------------------------------------------
# The lockstep sweep (equal_steps / offset_pass targets)
# -------------------------------------------------------------------------

def _lockstep_core(taup0, targets, a, b):
    """One scenario's lockstep rounds over all L target rows:
    ``(taup0 (K,), targets (L, K) int64)`` -> ``(Tc, makespan)``.
    Literal port of ``arrays._lockstep_rounds``."""
    L, K = targets.shape
    g1 = a * 1 + b

    taup = jnp.tile(taup0, (L, 1))
    Tc = jnp.zeros((L, K), dtype=jnp.int64)
    active = (targets > 0) & (taup0 >= g1)[None, :]
    t = jnp.zeros((L,), dtype=jnp.float64)

    def cond(state):
        _, _, active, _ = state
        return active.any()

    def body(state):
        taup, Tc, active, t = state

        def drop_cond(s):
            act, n = s
            g = a * n + b
            return (act & (taup + 1e-12 < g[:, None])).any()

        def drop_body(s):
            act, n = s
            g = a * n + b
            drop = act & (taup + 1e-12 < g[:, None])
            act = act & ~drop
            return act, act.sum(axis=-1, dtype=jnp.int64)

        n0 = active.sum(axis=-1, dtype=jnp.int64)
        active, n = lax.while_loop(drop_cond, drop_body, (active, n0))

        has_batch = n > 0
        g = a * n + b
        t = t + jnp.where(has_batch, g, 0.0)
        taup = taup - jnp.where(active, g[:, None], 0.0)
        Tc = Tc + active.astype(jnp.int64)
        active = active & (Tc < targets) & (taup + 1e-12 >= g1)
        return taup, Tc, active, t

    _, Tc, _, t = lax.while_loop(cond, body, (taup, Tc, active, t))
    return Tc, t


# -------------------------------------------------------------------------
# Scoring + selection (inside jit, PowerLawFID only)
# -------------------------------------------------------------------------

def _powerlaw_rows(Tc, offsets, valid, doomed, alpha, beta, gamma, fid0):
    """Masked progress-aware mean FID of every row of a ``(L, K)``
    count matrix: ``fid(offset + count)`` with the ``doomed -> fid(0)``
    rule, averaged over ``valid`` services only (pad rows excluded)."""
    tot = Tc + offsets[None, :]
    f = jnp.where(tot > 0,
                  alpha * tot.astype(jnp.float64) ** (-beta) + gamma,
                  fid0)
    f = jnp.where(doomed[None, :], fid0, f)
    f = jnp.where(valid[None, :], f, 0.0)
    return f.sum(axis=-1) / jnp.maximum(valid.sum(axis=-1), 1)


def _first_best(qs, valid_rows):
    """The scalar outer searches' selection rule — the FIRST candidate
    strictly better (by 1e-12) than everything before it — as a scan.
    ``valid_rows`` masks padded/disallowed candidates out entirely."""
    L = qs.shape[0]

    def step(carry, xi):
        best_i, best_q = carry
        i, q, ok = xi
        take = ok & (q < best_q - 1e-12)
        return (jnp.where(take, i, best_i),
                jnp.where(take, q, best_q)), None

    (bi, bq), _ = lax.scan(
        step, (jnp.int64(-1), jnp.float64(jnp.inf)),
        (jnp.arange(L, dtype=jnp.int64), qs, valid_rows))
    return bi, bq


# -------------------------------------------------------------------------
# Host-side preparation + jitted wrappers
# -------------------------------------------------------------------------

def _tie_ranks(taup0: np.ndarray,
               ids: Optional[np.ndarray] = None) -> np.ndarray:
    """The round-invariant (tau', id) tie-break of ``arrays``, as an
    integer rank per service.  ``ids`` breaks tau' ties for the
    single-scenario wrappers; batched callers (row position == id)
    rely on the stable argsort instead."""
    if ids is not None:
        order = np.lexsort((ids, taup0))
        tie = np.empty(taup0.size, dtype=np.int64)
        tie[order] = np.arange(taup0.size, dtype=np.int64)
        return tie
    order = np.argsort(taup0, axis=-1, kind="stable")
    tie = np.empty_like(order, dtype=np.int64)
    np.put_along_axis(tie, order,
                      np.broadcast_to(
                          np.arange(taup0.shape[-1], dtype=np.int64),
                          order.shape).copy(), axis=-1)
    return tie


def _f_threshold(taup0: np.ndarray, off: np.ndarray, levels: np.ndarray,
                 shift: int, step_cost: float) -> np.ndarray:
    """The priority-cluster membership threshold in composite-key
    space (``key <= lv*M + (M-1)  <=>  Tp <= lv``), clamped to the Tp
    bound so the int64 keys stay far from overflow.  Batched over a
    leading scenario axis when present."""
    M = np.int64(1) << shift
    te0_max = np.floor(np.max(np.maximum(taup0, 0.0), axis=-1)
                       / step_cost).astype(np.int64)
    tp_bound = (off.max(axis=-1) if off.size else np.int64(0)) \
        + 2 * te0_max + 4
    assert int(np.max(tp_bound, initial=0) + 2) * int(M) < int(_TP_INF), \
        "key space overflow"
    lv = levels[..., :] if taup0.ndim == 1 else levels[None, :]
    bound = tp_bound if taup0.ndim == 1 else tp_bound[:, None]
    return np.where(lv >= 0, np.minimum(lv, bound) * M + (M - 1),
                    np.int64(-1))


def _pad_tail(arr: np.ndarray, n: int, value) -> np.ndarray:
    """Pad the last axis out to ``n`` with ``value``."""
    if arr.shape[-1] == n:
        return arr
    pad = [(0, 0)] * (arr.ndim - 1) + [(0, n - arr.shape[-1])]
    return np.pad(arr, pad, constant_values=value)


_clustered_jit = jax.jit(_clustered_core, static_argnums=(8,))
_lockstep_jit = jax.jit(_lockstep_core)


def clustered_counts(taup0: np.ndarray, off: np.ndarray,
                     levels: np.ndarray, delay: DelayModel,
                     ids: Optional[np.ndarray] = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Jit-compiled Algorithm-1 sweep for one scenario: completed
    counts ``(L, K)`` + makespan ``(L,)`` for every candidate level at
    once.  Inputs/outputs are NumPy; shapes are bucket-padded (extra
    services inactive at tau'=0, extra levels duplicating the last
    real level) and the padding stripped from the result."""
    taup0 = np.asarray(taup0, dtype=np.float64)
    off = np.asarray(off, dtype=np.int64)
    levels = np.asarray(levels, dtype=np.int64)
    K, L = taup0.size, levels.size
    Kp, Lp = _bucket(K), _bucket(L)
    taup_p = _pad_tail(taup0, Kp, 0.0)
    off_p = _pad_tail(off, Kp, 0)
    lv_p = _pad_tail(levels, Lp, int(levels[-1]) if L else 1)
    shift = np.int64(max(Kp, 1).bit_length())
    ids_p = None if ids is None else \
        _pad_tail(np.asarray(ids, dtype=np.int64), Kp,
                  int(np.max(ids, initial=0)) + 1)
    tie = _tie_ranks(taup_p, ids_p)
    f_thr = _f_threshold(taup_p, off_p, lv_p, int(shift), delay.a + delay.b)
    kb = _key_bits(taup_p, off_p, int(shift), delay.a + delay.b)
    with jax.enable_x64(True):
        Tc, t = _clustered_jit(taup_p, off_p, lv_p, tie, f_thr, shift,
                               delay.a, delay.b, kb)
    return np.asarray(Tc)[:L, :K], np.asarray(t)[:L]


def lockstep_counts(taup0: np.ndarray, targets: np.ndarray,
                    delay: DelayModel) -> Tuple[np.ndarray, np.ndarray]:
    """Jit-compiled lockstep sweep for one scenario: counts + makespan
    for every ``(L, K)`` additional-step target row at once (padded
    services carry target 0, so they never join a batch)."""
    taup0 = np.asarray(taup0, dtype=np.float64)
    targets = np.asarray(targets, dtype=np.int64)
    L, K = targets.shape
    Kp, Lp = _bucket(K), _bucket(L)
    taup_p = _pad_tail(taup0, Kp, 0.0)
    tg_p = _pad_tail(targets, Kp, 0)
    tg_p = np.pad(tg_p, [(0, Lp - L), (0, 0)], constant_values=0)
    with jax.enable_x64(True):
        Tc, t = _lockstep_jit(taup_p, tg_p, delay.a, delay.b)
    return np.asarray(Tc)[:L, :K], np.asarray(t)[:L]


def powerlaw_scores(Tc: np.ndarray, quality, offsets: Optional[np.ndarray],
                    doomed: Optional[np.ndarray] = None,
                    valid: Optional[np.ndarray] = None) -> np.ndarray:
    """Vectorized row scores for a PowerLawFID-based objective (the
    fast path of the jax engine's outer searches); callers fall back to
    ``arrays.score_rows`` for arbitrary quality models."""
    Tc = np.asarray(Tc)
    K = Tc.shape[-1]
    off = np.zeros(K, np.int64) if offsets is None \
        else np.asarray(offsets, np.int64)
    dm = np.zeros(K, bool) if doomed is None else np.asarray(doomed, bool)
    vd = np.ones(K, bool) if valid is None else np.asarray(valid, bool)
    with jax.enable_x64(True):
        qs = _powerlaw_jit(Tc, off, vd, dm, quality.alpha, quality.beta,
                           quality.gamma, quality.fid_at_zero)
    return np.asarray(qs)


_powerlaw_jit = jax.jit(_powerlaw_rows)


# One fused T* search over S stacked scenarios: vmapped clustered
# sweep -> masked power-law scoring -> first-best scan, all in a
# single call.  ``_plan_many_block`` is the unjitted body so the
# sharded entry point (repro.core.jaxplan.sharded) can wrap the SAME
# computation in shard_map per device; ``_plan_many_core`` is the
# single-device jit (the ``plan_many`` core).
def _plan_many_block(taup0, off, valid, tie, f_thr, levels, shift,
                     a, b, alpha, beta, gamma, fid0, key_bits):
    Tc, t = jax.vmap(
        _clustered_core,
        in_axes=(0, 0, None, 0, 0, None, None, None, None))(
            taup0, off, levels, tie, f_thr, shift, a, b, key_bits)
    qs = jax.vmap(_powerlaw_rows,
                  in_axes=(0, 0, 0, None, None, None, None, None))(
        Tc, off, valid, jnp.zeros(taup0.shape[-1], bool),
        alpha, beta, gamma, fid0)
    L = levels.shape[0]
    best_i, best_q = jax.vmap(_first_best, in_axes=(0, None))(
        qs, jnp.ones((L,), bool))
    idx = jnp.maximum(best_i, 0)
    counts = jnp.take_along_axis(Tc, idx[:, None, None], axis=1)[:, 0, :]
    ms = jnp.take_along_axis(t, idx[:, None], axis=1)[:, 0]
    return best_i, counts, best_q, ms


_plan_many_core = jax.jit(_plan_many_block, static_argnums=(13,))


# The REPLAN variant of the fused search — the online event loop's
# shared-horizon semantics, batched over concurrent replans.
# ``_plan_many_block`` folds the offsets into the clustered pass itself
# (the offset-native candidate family of ``stacking_offset``); a
# residual replan in ``repro.core.online`` instead reruns Algorithm 1
# with ZERO offsets over the residual budgets and only *scores*
# candidates progress-aware — ``fid(done + new)`` with the
# ``doomed -> fid(0)`` rule (``online._OffsetQuality``) — and each
# scenario's candidate grid stops at its own t_star_max (``lv_ok``),
# exactly the level set the per-cell ``stacking_vec`` search sweeps.
# The fleet harness (repro.core.fleet) batches every concurrent cell
# replan of a tick through this block in one jitted call.
def _replan_many_block(taup0, score_off, valid, doomed, tie, f_thr,
                       levels, lv_ok, shift, a, b, alpha, beta, gamma,
                       fid0, key_bits):
    pass_off = jnp.zeros(taup0.shape, dtype=score_off.dtype)
    Tc, t = jax.vmap(
        _clustered_core,
        in_axes=(0, 0, None, 0, 0, None, None, None, None))(
            taup0, pass_off, levels, tie, f_thr, shift, a, b, key_bits)
    qs = jax.vmap(_powerlaw_rows,
                  in_axes=(0, 0, 0, 0, None, None, None, None))(
        Tc, score_off, valid, doomed, alpha, beta, gamma, fid0)
    best_i, best_q = jax.vmap(_first_best)(qs, lv_ok)
    idx = jnp.maximum(best_i, 0)
    counts = jnp.take_along_axis(Tc, idx[:, None, None], axis=1)[:, 0, :]
    ms = jnp.take_along_axis(t, idx[:, None], axis=1)[:, 0]
    return best_i, counts, best_q, ms


_replan_many_core = jax.jit(_replan_many_block, static_argnums=(15,))
