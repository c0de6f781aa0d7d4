"""Benchmark driver: one module per paper figure/table plus the
roofline, online-admission, multi-server, churn, fleet, planner-speed
and beyond-paper suites.  Prints ``name,us_per_call,derived`` CSV.

    python -m benchmarks.run [--only fig1a,fig2b,online,planner_speed,..]
    python -m benchmarks.run --list
    python -m benchmarks.run --only churn --workers 4 --json out/

(run from the repo root; ``benchmarks/__init__.py`` puts ``src`` on the
path, so no ``PYTHONPATH`` prefix is needed)

``--workers N`` fans grid suites (churn, multiserver — any suite whose
``run`` takes a ``workers`` keyword) out over N processes; results are
byte-identical at any worker count (benchmarks/par.py).  On a TPU it
refuses: the chip belongs to this one process.

A suite that raises still prints its ``<suite>_ERROR`` row, and the
driver then exits 1 once every suite has run.  JAX's persistent
compilation cache is on (``repro.compile_cache``): in
``JAX_COMPILATION_CACHE_DIR`` when set, else in ``.jax_cache`` at the
repository root.

``--json DIR`` additionally writes one machine-readable
``BENCH_<suite>.json`` per suite (rows + git SHA + per-suite wall time
+ worker count); CI uploads these as artifacts and
``benchmarks/compare.py`` gates them against the committed
``benchmarks/baseline.json``.
"""

import argparse
import inspect
import json
import subprocess
import sys
import time
from pathlib import Path

from benchmarks import (ablations, beyond_paper, churn, e2e,
                        fig1a_delay_vs_batch, fig1b_fid_vs_steps,
                        fig2a_e2e_delay, fig2b_fid_vs_services,
                        fig2c_fid_vs_min_delay, fleet, kernels_bench,
                        multiserver, online_admission, planner_speed,
                        roofline_report)


def api_suite(rows):
    """Registry census + analytic one-call pipeline smoke (docs/API.md)."""
    from repro.api import (Provisioner, list_admissions, list_allocators,
                           list_placements, list_schedulers,
                           list_workloads)
    from repro.core.service import make_scenario
    rows.append(("api_schedulers", float(len(list_schedulers())),
                 "|".join(list_schedulers())))
    rows.append(("api_allocators", float(len(list_allocators())),
                 "|".join(list_allocators())))
    rows.append(("api_workloads", float(len(list_workloads())),
                 "|".join(list_workloads())))
    rows.append(("api_admissions", float(len(list_admissions())),
                 "|".join(list_admissions())))
    rows.append(("api_placements", float(len(list_placements())),
                 "|".join(list_placements())))
    t0 = time.time()
    report = Provisioner(make_scenario(K=8, seed=0), scheduler="stacking",
                         allocator="coordinate").run()
    rows.append(("api_provisioner_run_s", time.time() - t0,
                 f"mean_fid={report.mean_fid:.2f},"
                 f"batches={report.plan.num_batches}"))


SUITES = {
    "api": api_suite,
    "fig1a": fig1a_delay_vs_batch.run,
    "fig1b": fig1b_fid_vs_steps.run,
    "fig2a": fig2a_e2e_delay.run,
    "fig2b": fig2b_fid_vs_services.run,
    "fig2c": fig2c_fid_vs_min_delay.run,
    "online": online_admission.run,
    "multiserver": multiserver.run,
    "churn": churn.run,
    "fleet": fleet.run,
    "e2e": e2e.run,
    "planner_speed": planner_speed.run,
    "roofline": roofline_report.run,
    "kernels": kernels_bench.run,
    "beyond": beyond_paper.run,
    "ablations": ablations.run,
}


def git_sha() -> str:
    """Current commit, for stamping BENCH_*.json artifacts."""
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=10, cwd=Path(__file__).resolve().parent,
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def device_count() -> int:
    """jax devices visible to this process (0 = jax not importable):
    stamped into BENCH_*.json so sharded-planner rows can be read in
    context — a 1-device artifact and an 8-device artifact are not
    comparable speedup-wise."""
    global _DEVICE_COUNT
    if _DEVICE_COUNT is None:
        try:
            import jax
            _DEVICE_COUNT = len(jax.devices())
        except Exception:   # noqa: BLE001 — absent or broken backend
            _DEVICE_COUNT = 0
    return _DEVICE_COUNT


_DEVICE_COUNT = None


def write_json(out_dir: Path, suite: str, rows, elapsed_s: float,
               sha: str, workers: int = 1) -> Path:
    from repro.core import arrays
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"BENCH_{suite}.json"
    payload = {
        "suite": suite,
        "git_sha": sha,
        # per-suite wall time + worker count so nightly baseline
        # refreshes capture planner/suite speed trends, not just FIDs
        "elapsed_s": round(elapsed_s, 3),
        "workers": workers,
        # the active planner engine (vec/scalar/jax, process default at
        # write time) so baseline refreshes can tell engine trends apart
        "engine": arrays.get_engine(),
        # jax device count (0 = no jax), next to engine/workers
        "devices": device_count(),
        "rows": [{"name": n, "value": v, "derived": d}
                 for n, v, d in rows],
    }
    path.write_text(json.dumps(payload, indent=2) + "\n")
    return path


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    ap.add_argument("--list", action="store_true",
                    help="print available suite names and exit")
    ap.add_argument("--json", metavar="DIR", default=None,
                    help="also write one BENCH_<suite>.json per suite")
    ap.add_argument("--workers", type=int, default=1,
                    help="process-parallel fan-out for grid suites "
                         "(churn, multiserver); 1 = serial")
    args = ap.parse_args(argv)
    if args.list:
        print("\n".join(SUITES))
        return
    names = list(SUITES) if not args.only else args.only.split(",")
    sha = git_sha() if args.json else ""
    from repro import compile_cache
    compile_cache.enable(Path(__file__).resolve().parents[1])

    rows = []
    failed = []
    print("name,us_per_call,derived")
    for name in names:
        t0 = time.time()
        before = len(rows)
        fn = SUITES[name]
        kwargs = {}
        if args.workers > 1 and \
                "workers" in inspect.signature(fn).parameters:
            kwargs["workers"] = args.workers
        try:
            fn(rows, **kwargs)
        except Exception as e:   # noqa: BLE001 — report, run the rest
            rows.append((f"{name}_ERROR", 0.0, repr(e)[:120]))
            failed.append(name)
        elapsed = time.time() - t0
        for r in rows[before:]:
            print(f"{r[0]},{r[1]:.4f},{r[2]}")
        if args.json:
            # record the worker count THIS suite actually ran with —
            # suites without a workers kwarg executed serially
            write_json(Path(args.json), name, rows[before:], elapsed,
                       sha, workers=kwargs.get("workers", 1))
        print(f"# {name} done in {elapsed:.1f}s", file=sys.stderr)
    if failed:
        print(f"# suites raised: {','.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
