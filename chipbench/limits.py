"""Read the two ends a correctness limit is set between, on the chip.

    python3 chipbench/limits.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

For each seed, in one process: set the cell up, drive its rounds for a
short window at the cell's own load, and compare the sample of images a
run compares: the system's served images against the reference at the
configuration's stated precision (the lower reading), and the control's
(the reference in bfloat16 throughout, on the same chains) against the
same reference (the upper reading).  The served images are also read
against the reference at ``"highest"`` precision, for the record.
Prints one JSON line per seed.  The benchmark's own runs never run the
control.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import bench, run, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    run.import_system()
    run.start_jax(cell.chips)
    chk = cell.mix["check"]
    for seed in args.seeds:
        b = bench.Bench(cell, seed)
        b.setup()
        rounds, wall = b.window(args.seconds)
        b.release()
        t0 = time.perf_counter()
        sound = bench.check(b, rounds, int(chk["services"]), int(chk["rows"]))
        check_s = time.perf_counter() - t0
        ctrl = bench.check(b, rounds, int(chk["services"]), int(chk["rows"]),
                           control=True)
        top = bench.check(b, rounds, int(chk["services"]), int(chk["rows"]),
                          precision="highest")
        print(json.dumps({
            "seed": seed, "rounds": len(rounds),
            "program": sound["image_rel_err_max"],
            "control": ctrl["image_rel_err_max"],
            "program_mean": sound["image_rel_err_mean"],
            "control_mean": ctrl["image_rel_err_mean"],
            "program_vs_highest": top["image_rel_err_max"],
            "images": sound["images_checked"],
            "steps": sound["steps_checked"],
            "missing": sound["images_missing"],
            "unaccounted": sound["steps_unaccounted"],
            "check_s": check_s}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
