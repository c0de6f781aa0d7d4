"""Array-native planning engine: the vectorized STACKING core.

Every planning hot path in this repo reduces to two inner sweeps:

  * the *clustered* sweep — Algorithm 1's clustering/packing/batching
    rounds for one auxiliary target T* (``stacking_pass``), optionally
    offset-shifted into total-step space (``repro.core.offset``);
  * the *lockstep* sweep — every still-short service joins every batch
    until it reaches a per-service target (``equal_steps`` and
    ``offset_pass`` are both instances).

The scalar reference implementations walk dict-keyed services in
while-loops, and the outer searches re-run them once per T* /
water-level candidate.  This module keeps a scenario's per-service
state (``tau_prime``, offsets, completed counts, active mask) in
contiguous NumPy arrays with an id<->index mapping (``ServiceArrays``)
and turns both sweeps into masked array kernels batched over ALL
candidate levels at once: state is ``(L, K)`` for L candidate levels x
K services, one python-level loop iteration per batch *round* (shared
by every candidate) instead of one per (candidate, round, service).
``Te``/``Tp`` tables, the priority-cluster split, the packing caps and
the unaffordable-member drop loop are all computed as whole-array ops.
Levels finish rounds apart (a level with no active service is done),
so the clustered sweep gathers its state down to the live levels once
enough have finished; its per-round snapshots cover those levels only.

Bit-identical by construction: the kernels perform the same float64
operations in the same order as the scalar loops (one subtraction per
wall-clock advance, the same 1e-12 epsilons, the same
(Tp, tau', id) sort keys), so plans — batches, start times,
``steps_completed``, objective — match the reference exactly;
``tests/test_arrays.py`` and the hypothesis suite enforce it across
the static, online, offset and multi-server entry points.

Engine selection: the consumers (``stacking``, ``equal_steps``,
``StackingOffset``, and the online/multi-server pipelines) dispatch on
the process-wide engine, ``"vec"`` by default.

    from repro.core import arrays
    arrays.set_engine("scalar")          # global: reference path
    with arrays.engine_scope("vec"):     # scoped override
        ...

or per call via their ``engine=`` parameter; the ``REPRO_PLANNER_ENGINE``
environment variable sets the process default.  The scalar path stays
the ground truth the vec engine is tested against (and what
``benchmarks/planner_speed.py`` measures the speedup over).

Plain NumPy on purpose: the state layout (flat arrays + masks, no
dicts) is exactly what a future jit/vmap backend needs — swapping
``np`` for ``jnp`` over fixed-shape ``(L, K)`` state is the intended
next step, not a rewrite.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro import spans
from repro.core.delay_model import DelayModel
from repro.core.plan import BatchPlan

# int64 sentinel pushing inactive services past every real Tp in the
# (Tp, tau', id) lexsort; far below int64 overflow when summed with keys
_TP_INF = np.int64(1) << 62
# the clustered sweep compacts its working rows to the live ones once
# fewer than this share of them are live (``_clustered_rounds``)
_COMPACT_BELOW = 0.8


# -------------------------------------------------------------------------
# Engine registry
# -------------------------------------------------------------------------
#
# One extensible name -> implementation map shared by ``set_engine``,
# ``resolve_engine`` and the ``REPRO_PLANNER_ENGINE`` guard.  The two
# built-in engines ("vec", "scalar") register ``None`` — their dispatch
# lives inline in the consumers — while optional backends register an
# implementation object whose attributes (``stacking``, ``equal_steps``,
# ``offset_plan``, ``optimal_plan``) the consumers call instead
# (``repro.core.jaxplan`` registers the "jax" engine this way).

_ENGINE_IMPLS: Dict[str, Optional[Any]] = {}
_BACKENDS_PROBED = False
_BACKEND_ERRORS: Dict[str, str] = {}
# optional backend modules probed on first unknown-engine lookup, so
# ``REPRO_PLANNER_ENGINE=jax`` (or ``set_engine("jax")``) works without
# anyone importing the backend first — and without paying its import
# cost when nobody asks for it
_OPTIONAL_BACKENDS = {"jax": "repro.core.jaxplan"}


def register_engine(name: str, impl: Optional[Any] = None) -> None:
    """Register a planner engine.  ``impl`` is ``None`` for the
    built-in engines (dispatched inline by the consumers) or a backend
    namespace providing ``stacking`` / ``equal_steps`` /
    ``offset_plan`` / ``optimal_plan`` entry points."""
    if name in _ENGINE_IMPLS and _ENGINE_IMPLS[name] is not impl:
        raise ValueError(f"planner engine {name!r} is already registered")
    _ENGINE_IMPLS[name] = impl


register_engine("vec")
register_engine("scalar")


def registered_engines() -> Tuple[str, ...]:
    """The currently registered engine names, sorted.  Optional
    backends appear once imported (or once first requested by name)."""
    return tuple(sorted(_ENGINE_IMPLS))


def _probe_backends() -> None:
    """Import the optional backend modules once so they can register
    their engines; a backend whose dependency is missing records the
    reason for the error message instead of failing the probe."""
    global _BACKENDS_PROBED
    if _BACKENDS_PROBED:
        return
    _BACKENDS_PROBED = True
    for eng, module in _OPTIONAL_BACKENDS.items():
        try:
            importlib.import_module(module)
        except ImportError as e:      # dependency absent: engine stays
            _BACKEND_ERRORS[eng] = str(e)   # unregistered, reason kept


def _require_engine(name: str) -> str:
    """Validate an engine name against the registry (probing optional
    backends on a miss), raising with the dynamic engine list."""
    if name not in _ENGINE_IMPLS:
        _probe_backends()
    if name not in _ENGINE_IMPLS:
        hint = (f" (backend unavailable: {_BACKEND_ERRORS[name]})"
                if name in _BACKEND_ERRORS else "")
        raise ValueError(
            f"unknown planner engine {name!r}; registered engines: "
            f"{', '.join(registered_engines())}{hint}")
    return name


def engine_impl(name: str) -> Optional[Any]:
    """The backend implementation registered for ``name`` (``None``
    for the built-in vec/scalar engines)."""
    return _ENGINE_IMPLS[_require_engine(name)]


def get_engine() -> str:
    """The process-wide planning engine ("vec" by default)."""
    return _ENGINE


def set_engine(name: str) -> None:
    """Select the process-wide planning engine."""
    global _ENGINE
    _ENGINE = _require_engine(name)


@contextlib.contextmanager
def engine_scope(name: Optional[str]):
    """Temporarily select an engine (``None`` = leave as-is).  The
    online/multi-server pipelines use this to honour their ``engine=``
    parameter around a whole event-driven run."""
    if name is None:
        yield
        return
    prev = get_engine()
    set_engine(name)
    try:
        yield
    finally:
        set_engine(prev)


def resolve_engine(engine: Optional[str]) -> str:
    """An explicit ``engine=`` argument, or the process default."""
    if engine is None:
        return get_engine()
    return _require_engine(engine)


# -------------------------------------------------------------------------
# Per-service state as contiguous arrays
# -------------------------------------------------------------------------

@dataclasses.dataclass
class ServiceArrays:
    """A scenario's per-service planning state in contiguous arrays,
    rows in the given service order (the ``make_plan`` convention every
    objective evaluation relies on)."""

    ids: np.ndarray          # (K,) int64 service ids
    tau_prime: np.ndarray    # (K,) float64 generation budgets
    offsets: np.ndarray      # (K,) int64 steps already executed
    index: Dict[int, int]    # id -> row

    @property
    def K(self) -> int:
        return int(self.ids.size)

    @classmethod
    def build(cls, service_ids: Sequence[int],
              tau_prime: Dict[int, float],
              offsets: Optional[Dict[int, int]] = None) -> "ServiceArrays":
        ids = np.asarray([int(k) for k in service_ids], dtype=np.int64)
        taup = np.asarray([float(tau_prime[int(k)]) for k in ids],
                          dtype=np.float64)
        if offsets:
            off = np.asarray([int(offsets.get(int(k), 0)) for k in ids],
                             dtype=np.int64)
        else:
            off = np.zeros(ids.size, dtype=np.int64)
        return cls(ids=ids, tau_prime=taup, offsets=off,
                   index={int(k): i for i, k in enumerate(ids)})


# -------------------------------------------------------------------------
# Kernels: (L, K) state, one python iteration per batch round
# -------------------------------------------------------------------------

def _clustered_rounds(ids: np.ndarray, taup0: np.ndarray, off: np.ndarray,
                      delay: DelayModel, levels: np.ndarray,
                      record: bool = False,
                      history: Optional[list] = None):
    """The Algorithm-1 clustering/packing/batching rounds, batched over
    candidate levels: row l plans against T* = ``levels[l]``.

    Returns ``(Tc, makespan, batches, start_times)`` — ``Tc`` is
    ``(L, K)`` completed counts, ``makespan`` ``(L,)``.  ``record=True``
    (single level only) additionally materializes the batch list in the
    scalar pass's exact order (sorted-cluster sequence).  ``history``
    (a caller-owned list) collects per-round ``(rows, key, packed,
    has_batch)`` snapshots so the outer searches can replay ANY row's
    batch list afterwards (``_replay_clustered``) without re-running a
    pass for the winning candidate.

    The rounds compute only the *working* rows, ``rows`` (sorted output
    row indices).  A row with no active service is finished: it packs
    nothing, so its ``Tc`` and clock never change again.  Once fewer
    than ``_COMPACT_BELOW`` of the working rows are live, the finished
    ones are written to the output and the working state (``taup``,
    ``Tc``, ``active``, ``t`` and the per-level constants) is gathered
    down to the live rows, under a ``repro.plan.compact`` span (stats
    ``kept``, ``of``).  Each row's values are computed by the same
    operations as without compaction; a single level never compacts.
    Each snapshot covers the working rows of its round; its ``rows``
    names them.
    """
    a, b = delay.a, delay.b
    levels = np.asarray(levels, dtype=np.int64)
    L, K = levels.size, taup0.size
    assert not record or L == 1, "batch recording needs a single level"
    g1 = delay.min_task_delay()          # == a * 1 + b
    step_cost = a + b                    # T^e divisor (size-1 batches)
    taup0 = np.asarray(taup0, dtype=np.float64)

    # The round-invariant tie-break: every committed batch subtracts the
    # SAME g from every active service of a row (Eq. 15) and inactive
    # services never re-activate, so pairwise tau' differences among
    # active services equal their initial differences — the (tau', id)
    # order the scalar sort breaks Tp ties with never changes.  Encoding
    # it once as an integer rank turns the per-round 3-key lexsort into
    # a values-only sort of ONE composite integer key,
    #     key = Tp * M + tie_rank        (unique per service),
    # whose x_n-th smallest value is a membership threshold.
    tie = np.empty(K, dtype=np.int64)
    tie[np.lexsort((ids, taup0))] = np.arange(K, dtype=np.int64)
    shift = int(max(K, 1).bit_length())
    M = np.int64(1) << shift

    taup = np.tile(taup0, (L, 1))
    Tc = np.zeros((L, K), dtype=np.int64)
    active = np.tile(taup0 >= g1, (L, 1))
    t = np.zeros(L, dtype=np.float64)
    off2 = off[None, :]
    # level-constant packing terms, hoisted out of the round loop (the
    # divisor clamp only changes masked-out values for levels <= 0)
    lv_pos = levels > 0
    b_lv = b * levels.astype(np.float64)
    a_lv = a * np.maximum(levels.astype(np.float64), 1.0)
    # the F threshold in key space: key <= lv*M + (M-1)  <=>  Tp <= lv.
    # Tp is bounded by off + 2*T^e0 + slack (T_c can't outgrow the
    # dedicated-batch bound), so clamping huge direct-call levels there
    # changes nothing and keeps the int64 key far from overflow
    te0_max = int(np.max(np.maximum(taup0, 0.0)) / step_cost) \
        if K else 0
    tp_bound = int(off.max() if K else 0) + 2 * te0_max + 4
    assert (tp_bound + 2) * int(M) < int(_TP_INF), "key space overflow"
    F_thr = np.where(levels >= 0,
                     np.minimum(levels, tp_bound) * M + (M - 1),
                     np.int64(-1))
    batches: List[List[Tuple[int, int]]] = []
    starts: List[float] = []
    rows = np.arange(L)                  # working row -> output row
    Tc_out = np.zeros((L, K), dtype=np.int64)
    t_out = np.zeros(L, dtype=np.float64)

    while True:
        n_active = active.sum(axis=-1)
        live = n_active > 0
        n_live = int(np.count_nonzero(live))
        if n_live == 0:
            break
        if n_live < _COMPACT_BELOW * rows.size:
            with spans.span(spans.PLAN_COMPACT, kept=n_live, of=rows.size):
                done = ~live
                Tc_out[rows[done]] = Tc[done]
                t_out[rows[done]] = t[done]
                rows, n_active = rows[live], n_active[live]
                taup, Tc, active, t = (taup[live], Tc[live], active[live],
                                       t[live])
                F_thr, b_lv, a_lv, lv_pos = (F_thr[live], b_lv[live],
                                             a_lv[live], lv_pos[live])

        # ---- clustering (Eqs. 15-18, offset-shifted) ---------------------
        # T^e: tasks completable in the remaining budget on dedicated
        # batches — int() truncation == floor for the (positive) budgets
        # of live services; inactive entries compute garbage that every
        # consumer below masks out through the key sentinel
        Te = (taup / step_cost).astype(np.int64)
        Tp = off2 + Tc + Te
        key = np.where(active, Tp * M + tie, _TP_INF)

        F = key <= F_thr[:, None]
        n_F = F.sum(axis=-1)

        # ---- packing (Eqs. 19-20) ----------------------------------------
        te_max = np.max(np.where(F, Te, -1), axis=-1)
        tau_min = np.min(np.where(F, taup, np.inf), axis=-1)
        cap_f = np.floor((tau_min - b * te_max)
                         / (a * np.maximum(te_max, 1)))
        tp_min = key.min(axis=-1) >> shift       # min Tp over active
        cap_nf = np.floor((step_cost * tp_min - b_lv) / a_lv)
        x_f = np.where(te_max > 0,
                       np.maximum(n_F, np.minimum(n_active, cap_f)),
                       n_F)
        # no-priority-cluster branch: F empty forces min Tp > T*, so
        # cap >= 1 whenever the level >= 1; the explicit clamp states
        # that invariant at the site (mirrors stacking_pass — the
        # generic max(1, ...) below would absorb a negative cap
        # identically, but without the branch's reasoning)
        x_nf = np.minimum(n_active,
                          np.where(lv_pos, np.maximum(1, cap_nf),
                                   n_active))
        x_n = np.where(n_F > 0, x_f, x_nf)
        x_n = np.maximum(1, np.minimum(x_n, n_active))
        x_n = np.where(n_active > 0, x_n, 0).astype(np.int64)

        # ---- batching -----------------------------------------------------
        # the x_n cheapest (Tp, tau', id) services per row == every key
        # at or below the x_n-th smallest (keys are unique; x_n never
        # exceeds n_active and inactive keys sit at the sentinel, so the
        # selection is all-active by construction)
        sorted_key = np.sort(key, axis=-1)
        thr = np.take_along_axis(sorted_key,
                                 np.maximum(x_n - 1, 0)[:, None],
                                 axis=-1)[:, 0]
        thr = np.where(x_n > 0, thr, np.int64(-1))
        packed = key <= thr[:, None]
        n_packed = x_n.copy()
        while True:
            g = a * n_packed + b
            drop = packed & (taup + 1e-12 < g[:, None])
            if not drop.any():
                break
            packed &= ~drop                 # cannot afford this batch ->
            active &= ~drop                 # service is finished
            n_packed = packed.sum(axis=-1)

        has_batch = n_packed > 0
        g = a * n_packed + b
        if record and has_batch[0]:
            idx = np.flatnonzero(packed[0])
            members = idx[np.argsort(key[0, idx])]
            batches.append([(int(ids[j]), int(Tc[0, j]))
                            for j in members])
            starts.append(float(t[0]))
        if history is not None:
            history.append((rows, key, packed, has_batch))
        np.add(t, g, out=t, where=has_batch)
        adv = active & has_batch[:, None]    # wall clock advances for all
        np.subtract(taup, g[:, None], out=taup, where=adv)     # (Eq. 15)
        Tc += packed
        # services that can no longer fit even a dedicated batch are done
        active &= taup + 1e-12 >= g1

    Tc_out[rows] = Tc
    t_out[rows] = t
    return Tc_out, t_out, batches, starts


def _lockstep_rounds(ids: np.ndarray, taup0: np.ndarray,
                     targets: np.ndarray, delay: DelayModel,
                     record: bool = False,
                     history: Optional[list] = None):
    """The lockstep sweep (``offset_pass`` / ``equal_steps`` inner
    loop), batched over per-row target vectors: every service still
    short of ``targets[l, k]`` additional steps joins every batch of
    row l, unaffordable members dropping out with the steps they have.

    Same return convention as ``_clustered_rounds``; recorded batches
    list members in service order, as the scalar loops do; ``history``
    collects ``(active, has_batch)`` snapshots for ``_replay_lockstep``.
    """
    a, b = delay.a, delay.b
    targets = np.asarray(targets, dtype=np.int64)
    L, K = targets.shape
    assert not record or L == 1, "batch recording needs a single target row"
    g1 = delay.min_task_delay()

    taup = np.tile(np.asarray(taup0, dtype=np.float64), (L, 1))
    Tc = np.zeros((L, K), dtype=np.int64)
    active = (targets > 0) & (taup0 >= g1)[None, :]
    t = np.zeros(L, dtype=np.float64)
    batches: List[List[Tuple[int, int]]] = []
    starts: List[float] = []

    while active.any():
        # drop members that cannot afford the current shared batch
        n = active.sum(axis=-1)
        while True:
            g = a * n + b
            drop = active & (taup + 1e-12 < g[:, None])
            if not drop.any():
                break
            active &= ~drop
            n = active.sum(axis=-1)
        has_batch = n > 0
        g = a * n + b
        if record and has_batch[0]:
            members = np.flatnonzero(active[0])
            batches.append([(int(ids[j]), int(Tc[0, j]))
                            for j in members])
            starts.append(float(t[0]))
        if history is not None:
            history.append((active.copy(), has_batch))
        np.add(t, g, out=t, where=has_batch)
        np.subtract(taup, g[:, None], out=taup, where=active)
        Tc += active
        active &= (Tc < targets) & (taup + 1e-12 >= g1)

    return Tc, t, batches, starts


def _replay_clustered(ids: np.ndarray, w: int, history: list,
                      delay: DelayModel):
    """Reconstruct row ``w``'s batch list from a clustered sweep's
    per-round snapshots — the same (batches, start_times) the scalar
    pass records, without re-running the pass.  Row ``w`` sits at its
    place in each snapshot's ``rows``; the first snapshot without it
    comes after the row finished, so the replay stops there."""
    a, b = delay.a, delay.b
    Tc = np.zeros(ids.size, dtype=np.int64)
    batches: List[List[Tuple[int, int]]] = []
    starts: List[float] = []
    t = 0.0
    seen, r = None, 0
    for rows, key, packed, has_batch in history:
        if rows is not seen:            # a compaction renumbered rows
            seen, r = rows, int(np.searchsorted(rows, w))
            if r == rows.size or rows[r] != w:
                break
        if not has_batch[r]:
            continue
        idx = np.flatnonzero(packed[r])
        members = idx[np.argsort(key[r, idx])]
        batches.append([(int(ids[j]), int(Tc[j])) for j in members])
        starts.append(t)
        t += a * len(members) + b
        Tc[packed[r]] += 1
    return batches, starts


def _replay_lockstep(ids: np.ndarray, w: int, history: list,
                     delay: DelayModel):
    """Reconstruct row ``w``'s batch list from a lockstep sweep's
    per-round snapshots (members in service order, as the scalar
    loops record)."""
    a, b = delay.a, delay.b
    Tc = np.zeros(ids.size, dtype=np.int64)
    batches: List[List[Tuple[int, int]]] = []
    starts: List[float] = []
    t = 0.0
    for active, has_batch in history:
        if not has_batch[w]:
            continue
        members = np.flatnonzero(active[w])
        batches.append([(int(ids[j]), int(Tc[j])) for j in members])
        starts.append(t)
        t += a * len(members) + b
        Tc[active[w]] += 1
    return batches, starts


def score_rows(rows: np.ndarray, quality) -> np.ndarray:
    """``quality.mean_fid`` of every row of a ``(L, K)`` count matrix,
    evaluated through the exact scalar call (vectorizing the quality
    model itself is off the table: SIMD ``pow`` differs from libm in
    the last ulp) but with duplicate rows — very common across
    neighbouring T* levels — scored once."""
    uniq, inverse = np.unique(np.asarray(rows), axis=0,
                              return_inverse=True)
    qs = np.empty(uniq.shape[0], dtype=np.float64)
    for u, counts in enumerate(uniq.tolist()):
        qs[u] = quality.mean_fid(counts)
    return qs[inverse.ravel()]


def first_best(rows: np.ndarray, quality) -> Tuple[int, float]:
    """The scalar outer searches' selection rule — the FIRST candidate
    strictly better (by 1e-12) than everything before it — over the
    rows of a ``(L, K)`` count matrix."""
    best_i, best_q = -1, float("inf")
    for i, q in enumerate(score_rows(rows, quality).tolist()):
        if q < best_q - 1e-12:
            best_i, best_q = i, q
    return best_i, best_q


# -------------------------------------------------------------------------
# Batched sweeps (scoring) and single-candidate passes (materialization)
# -------------------------------------------------------------------------

def sweep_clustered(arr: ServiceArrays, delay: DelayModel,
                    levels: Sequence[int]
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Completed counts + makespan of the Algorithm-1 pass for every
    candidate level at once: ``(Tc (L, K), makespan (L,))``.  Row l
    equals ``stacking_pass(..., t_star=levels[l], offsets=...)``'s
    ``steps_completed`` / ``makespan()`` exactly."""
    Tc, t, _, _ = _clustered_rounds(arr.ids, arr.tau_prime, arr.offsets,
                                    delay, np.asarray(levels))
    return Tc, t


def sweep_lockstep(arr: ServiceArrays, delay: DelayModel,
                   targets: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Completed counts + makespan of the lockstep pass for every
    target row at once (``targets`` is ``(L, K)`` *additional*-step
    targets aligned with ``arr`` rows)."""
    Tc, t, _, _ = _lockstep_rounds(arr.ids, arr.tau_prime,
                                   np.asarray(targets), delay)
    return Tc, t


def stacking_pass_vec(service_ids: Sequence[int],
                      tau_prime: Dict[int, float], delay: DelayModel,
                      t_star: int,
                      offsets: Optional[Dict[int, int]] = None
                      ) -> BatchPlan:
    """Drop-in vectorized ``stacking_pass``: one clustering-packing-
    batching sweep for a fixed T*, bit-identical to the scalar
    reference (same batches, same start times, same counts)."""
    arr = ServiceArrays.build(service_ids, tau_prime, offsets)
    Tc, _, batches, starts = _clustered_rounds(
        arr.ids, arr.tau_prime, arr.offsets, delay,
        np.asarray([t_star]), record=True)
    steps = {int(k): int(c) for k, c in zip(arr.ids, Tc[0])}
    return BatchPlan(batches=batches, start_times=starts,
                     steps_completed=steps, delay=delay)


def offset_pass_vec(service_ids: Sequence[int],
                    tau_prime: Dict[int, float], delay: DelayModel,
                    targets: Dict[int, int]) -> BatchPlan:
    """Drop-in vectorized ``repro.core.offset.offset_pass``: one
    lockstep sweep toward per-service additional-step targets."""
    arr = ServiceArrays.build(service_ids, tau_prime)
    tgt = np.asarray([[int(targets.get(int(k), 0)) for k in arr.ids]],
                     dtype=np.int64)
    Tc, _, batches, starts = _lockstep_rounds(arr.ids, arr.tau_prime,
                                              tgt, delay, record=True)
    steps = {int(k): int(c) for k, c in zip(arr.ids, Tc[0])}
    return BatchPlan(batches=batches, start_times=starts,
                     steps_completed=steps, delay=delay)


def stacking_vec(services, tau_prime: Dict[int, float], delay: DelayModel,
                 quality, t_star_max: int = 0) -> BatchPlan:
    """Algorithm 1 with the outer T* search as one batched sweep: all
    candidate levels share the per-round ``Te``/``Tp`` tables and
    advance together, then the first strictly-best level (the scalar
    search's tie rule) is materialized as the returned plan."""
    ids = [s.id for s in services]
    if t_star_max <= 0:
        t_star_max = max(1, max(delay.max_steps(tau_prime[k])
                                for k in ids))
    arr = ServiceArrays.build(ids, tau_prime)
    levels = np.arange(1, t_star_max + 1, dtype=np.int64)
    hist: list = []
    with spans.span(spans.PLAN_CLUSTERED):
        Tc, _, _, _ = _clustered_rounds(arr.ids, arr.tau_prime,
                                        arr.offsets, delay, levels,
                                        history=hist)
        best_i, _ = first_best(Tc, quality)
    assert best_i >= 0
    with spans.span(spans.PLAN_REPLAY):
        batches, starts = _replay_clustered(arr.ids, best_i, hist, delay)
    steps = {int(k): int(c) for k, c in zip(arr.ids, Tc[best_i])}
    return BatchPlan(batches=batches, start_times=starts,
                     steps_completed=steps, delay=delay)


def equal_steps_vec(services, tau_prime: Dict[int, float],
                    delay: DelayModel, quality) -> BatchPlan:
    """The balanced ``equal_steps`` baseline with its shared-target
    search as one batched lockstep sweep (row l targets T* = l + 1
    steps for every service), first strictly-best level materialized."""
    ids = [s.id for s in services]
    feasible = [k for k in ids if delay.max_steps(tau_prime[k]) > 0]
    t_max = max([delay.max_steps(tau_prime[k]) for k in feasible],
                default=1)
    arr = ServiceArrays.build(ids, tau_prime)
    levels = np.arange(1, max(1, t_max) + 1, dtype=np.int64)
    targets = np.broadcast_to(levels[:, None],
                              (levels.size, arr.K)).copy()
    hist: list = []
    Tc, _, _, _ = _lockstep_rounds(arr.ids, arr.tau_prime, targets,
                                   delay, history=hist)

    best_i, _ = first_best(Tc, quality)
    assert best_i >= 0
    batches, starts = _replay_lockstep(arr.ids, best_i, hist, delay)
    steps = {int(k): int(c) for k, c in zip(arr.ids, Tc[best_i])}
    return BatchPlan(batches=batches, start_times=starts,
                     steps_completed=steps, delay=delay)


# The process default, validated last so an optional backend named by
# the env var can be probed (and can import this partially-initialized
# module) with every definition above already bound.  A typo'd env var
# still fails loudly, at import time, listing the registered engines.
_ENGINE = _require_engine(os.environ.get("REPRO_PLANNER_ENGINE", "vec"))
