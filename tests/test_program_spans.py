"""The program's host spans (``repro.spans``), read back from a trace.

Two provisioning rounds run under one ``jax.profiler.trace``: one on the
``simulated`` executor with a planner twice too fast, so the closed loop
replans through the offset-native search, and one on the SMOKE U-Net's
served session with a fresh executor, so its step programs compile
inside the round.  The trace is read with ``jax.profiler.ProfileData``.
"""

import glob

import jax
import pytest

from repro import spans
from repro.api import DiffusionWorkload, Provisioner
from repro.configs.ddim_cifar10 import SMOKE
from repro.core import arrays
from repro.core.delay_model import DelayModel
from repro.core.quality_model import PowerLawFID
from repro.core.service import make_scenario

TRUE = DelayModel(a=0.1, b=0.2)
HALF = DelayModel(a=0.05, b=0.1)   # the planner's 2x-fast misestimate

NAMES = sorted(v for k, v in vars(spans).items()
               if k.isupper() and isinstance(v, str))

_PLAN_PARENTS = {spans.PLAN, spans.REPLAN}
#: the span each span may sit directly under (None: outermost)
PARENTS = {
    spans.PROVISION: {None},
    spans.ALLOCATE: {spans.PROVISION},
    spans.PLAN: {spans.PROVISION},
    spans.PLAN_CLUSTERED: _PLAN_PARENTS,
    spans.PLAN_COMPACT: {spans.PLAN_CLUSTERED, spans.PLAN_SHARED},
    spans.PLAN_LOCKSTEP: _PLAN_PARENTS,
    spans.PLAN_SHARED: _PLAN_PARENTS,
    spans.PLAN_REPLAY: _PLAN_PARENTS,
    spans.VALIDATE: {spans.PROVISION},
    spans.SIMULATE: {spans.PROVISION},
    spans.EXECUTE: {spans.PROVISION},
    spans.SESSION_OPEN: {spans.EXECUTE},
    spans.BATCH: {spans.EXECUTE},
    spans.SESSION_LANES: {spans.BATCH},
    spans.SESSION_DISPATCH: {spans.BATCH},
    spans.SESSION_WAIT: {spans.BATCH},
    spans.REPLAN: {spans.EXECUTE},
    spans.FINISH: {spans.EXECUTE},
    spans.COMPILE: {spans.SESSION_DISPATCH},
}


def _read(trace_dir):
    """Every ``repro.`` span as ``dict(name, start, end, stats, parent)``,
    each with the innermost span of its thread that holds it."""
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            mine = [dict(name=e.name, start=e.start_ns,
                         end=e.start_ns + e.duration_ns,
                         stats=dict(e.stats))
                    for e in line.events if e.name.startswith("repro.")]
            mine.sort(key=lambda s: (s["start"], -s["end"]))
            stack = []
            for s in mine:
                while stack and stack[-1]["end"] < s["end"]:
                    stack.pop()
                s["parent"] = stack[-1] if stack else None
                stack.append(s)
            out += mine
    return sorted(out, key=lambda s: s["start"])


def _inside(s, top):
    while s is not None and s is not top:
        s = s["parent"]
    return s is top


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The spans of both rounds, each round's report, and the compile
    log entries its round added."""
    d = str(tmp_path_factory.mktemp("spans"))
    sim = Provisioner(
        make_scenario(K=5, seed=1), scheduler="stacking_offset",
        allocator="inv_se", delay=HALF,
        execute_kwargs={"executor": "simulated",
                        "executor_kwargs": {"true_delay": TRUE},
                        "min_batches": 2, "drift_tol": 0.2})
    wl = DiffusionWorkload(cfg=SMOKE)
    net = Provisioner(
        make_scenario(K=3, seed=0), workload=wl,
        scheduler="stacking_offset", allocator="inv_se",
        delay=DelayModel(a=0.002, b=0.02),
        execute_kwargs={"max_replans": 2})
    ex = wl.executor
    with jax.profiler.trace(d):
        reports = [sim.run(jax.random.PRNGKey(0), execute="closed")]
        clog0 = len(ex.compile_log)
        reports.append(net.run(jax.random.PRNGKey(1), execute="closed"))
        compiled = ex.compile_log[clog0:]
    got = _read(d)
    rounds = [s for s in got if s["name"] == spans.PROVISION]
    assert len(rounds) == 2
    return got, rounds, reports, compiled


def _under(got, top, name):
    return [s for s in got if s["name"] == name and _inside(s, top)]


def test_every_span_name_appears(traced):
    got, *_ = traced
    assert set(NAMES) == set(PARENTS)
    assert set(NAMES) <= {s["name"] for s in got}


def test_spans_nest_as_documented(traced):
    got, *_ = traced
    for s in got:
        parent = s["parent"]["name"] if s["parent"] else None
        assert parent in PARENTS[s["name"]], (s["name"], parent)


@pytest.mark.parametrize("which", [0, 1], ids=["simulated", "unet"])
def test_one_batch_span_per_record_with_its_size(traced, which):
    got, rounds, reports, _ = traced
    batches = _under(got, rounds[which], spans.BATCH)
    records = reports[which].execution.records
    assert records
    assert [s["stats"]["size"] for s in batches] == \
        [r.size for r in records]


def test_session_open_and_finish_once_per_round(traced):
    got, rounds, *_ = traced
    # the simulated executor's sessions are not the denoising
    # executor's, so only the U-Net round opens one
    assert len(_under(got, rounds[0], spans.SESSION_OPEN)) == 0
    assert len(_under(got, rounds[1], spans.SESSION_OPEN)) == 1
    for top in rounds:
        assert len(_under(got, top, spans.FINISH)) == 1


@pytest.mark.parametrize("which", [0, 1], ids=["simulated", "unet"])
def test_replan_spans_count_replans(traced, which):
    got, rounds, reports, _ = traced
    replans = reports[which].execution.replans
    assert len(_under(got, rounds[which], spans.REPLAN)) == replans
    if which == 0:
        assert replans >= 1


def test_one_compile_span_per_new_compile_log_entry(traced):
    got, rounds, _, compiled = traced
    compiles = _under(got, rounds[1], spans.COMPILE)
    kinds = [s["stats"]["kind"] for s in compiles]
    assert compiled and kinds == [key[0] for key, _ in compiled]
    assert {s["stats"]["arch"] for s in compiles} == {"unet"}
    assert not _under(got, rounds[0], spans.COMPILE)


def test_bucketed_batch_splits_into_lanes_dispatch_wait(traced):
    got, rounds, *_ = traced
    for b in _under(got, rounds[1], spans.BATCH):
        kids = [s["name"] for s in got if s["parent"] is b]
        assert kids == [spans.SESSION_LANES, spans.SESSION_DISPATCH,
                        spans.SESSION_WAIT]


def test_run_id_shared_within_a_round_and_new_per_round(traced):
    got, rounds, *_ = traced
    ids = [top["stats"]["run"] for top in rounds]
    assert ids[0] != ids[1]
    for top, run in zip(rounds, ids):
        inner = [s for s in got if _inside(s, top)]
        assert len(inner) > 1
        assert {s["stats"]["run"] for s in inner} == {run}


def test_dense_sweep_compacts_and_a_single_level_does_not(tmp_path):
    """A K = 128 STACKING search drops finished levels from its sweep
    (``repro.plan.compact`` under ``repro.plan.clustered``, fewer rows
    kept than there were); a single-level pass never compacts."""
    g = DelayModel(a=1.8e-4, b=8.84e-4)
    scn = make_scenario(K=128, tau_min=0.16, tau_max=0.55, seed=14)
    tp = {s.id: s.deadline for s in scn.services}
    with jax.profiler.trace(str(tmp_path)):
        arrays.stacking_pass_vec(list(tp), tp, g, t_star=40)
        arrays.stacking_vec(scn.services, tp, g, PowerLawFID())
    got = _read(str(tmp_path))
    compact = [s for s in got if s["name"] == spans.PLAN_COMPACT]
    assert compact
    for s in compact:
        assert s["parent"]["name"] == spans.PLAN_CLUSTERED
        assert 0 < s["stats"]["kept"] < s["stats"]["of"]
