"""Run one benchmark cell once, on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

It sets up (weights from the seed, calibration of the step programs at
the cell's batch sizes, one warm-up round), drives provisioning rounds
for ``--seconds``, checks a sample of the served images against the
plain reference, and prints one JSON object as the last line of standard
output.  With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` the profiler records the whole window and
the metrics are the cell's per-layer metrics, with a breakdown of device
time and idle gaps.  The numbers the
check compares are the last lines of standard error and the last key of
the JSON line.

It exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for, and where the system under test (``src/``)
is not beside it.  JAX's compilation cache lives in ``.jax_cache`` at
the root of the checkout; the trace, while it is read, in
``.chipbench/trace``.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench, spec, trace, yardstick  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".chipbench" / "trace"


class NoChip(SystemExit):
    pass


def start_jax(chips: int):
    """Import JAX with the compilation cache in the checkout, and return
    the devices; refuse anything but enough TPU chips."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    # the TPU runtime's own logs would go to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(f"chipbench: needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} device(s) on platform "
                     f"{devices[0].platform!r} ({devices[0].device_kind})")
    return devices


def import_system() -> None:
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chipbench: the system under test is not at "
                         f"{src}/repro")
    sys.path.insert(0, str(src))


def per_layer(cell, obs) -> dict:
    out = {}
    for m in cell.per_layer:
        v = spec.metric_reader(m["name"])(obs)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.resolve(args.workload)
    import_system()
    devices = start_jax(cell.chips)
    dev = devices[0]
    peaks = yardstick.peaks(dev.device_kind)
    result = measure(cell, args.seed, args.seconds, bool(args.trace), dev,
                     peaks, len(devices[:cell.chips]))
    emit(result)
    return 0


def measure(cell, seed: int, seconds: float, traced: bool, dev, peaks,
            count: int, bench_cls=bench.Bench) -> dict:
    """Everything after the chip is found: set-up, window, check."""
    b = bench_cls(cell, seed, annotate=traced)
    b.setup()
    setup_s = time.perf_counter() - T_START
    trace_dir = None
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = str(TRACE_DIR)
    rounds, wall = b.window(seconds, trace_dir)
    compiles = b.compiles_since_setup()
    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    b.release()
    chk = cell.mix["check"]
    t0 = time.perf_counter()
    got = bench.check(b, rounds, int(chk["services"]), int(chk["rows"]))
    check_s = time.perf_counter() - t0
    checks = {
        "image_rel_err_max": {"value": got["image_rel_err_max"],
                              "limit": float(chk["image_rel_err_limit"])},
        "images_missing": {"value": got["images_missing"], "limit": 0},
        "steps_unaccounted": {"value": got["steps_unaccounted"],
                              "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": count, "memory_peak_bytes": mem_peak}
    attempted = sum(len(r.deadlines) for r in rounds)
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": got["images_missing"]}
    if traced:
        summary = trace.reduce(trace.load(trace_dir))
        # a whole window's trace is some hundreds of MB: read, then gone
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        obs = trace.Observation(rounds=rounds, trace=summary,
                                sizes=cell.sizes, peaks=peaks,
                                compiles=compiles)
        out["metrics"] = per_layer(cell, obs)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        out["device"] = device
        out["breakdown"] = summary.breakdown()
    else:
        metrics = {"setup_s": setup_s, **bench.end_to_end(rounds, wall)}
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items() if k in units}
        out["device"] = device
    out["run"] = {"rounds": len(rounds), "window_s": wall,
                  "setup_phases_s": b.phase_s, "check_s": check_s,
                  "calibration": {"a": b.g_cal.a, "b": b.g_cal.b},
                  "image_rel_err_mean": got["image_rel_err_mean"],
                  "images_checked": got["images_checked"],
                  "steps_checked": got["steps_checked"],
                  **bench.round_stats(rounds)}
    # the compared numbers come last, beside their limits
    out["checks"] = checks
    return out


def emit(result: dict) -> None:
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result))


if __name__ == "__main__":
    sys.exit(main())
