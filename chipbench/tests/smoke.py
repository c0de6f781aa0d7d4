"""A cell at the system's smoke size, for driving the harness on the CPU."""

import dataclasses
import json

from chipbench import spec


def smoke_cell(name=None, **mix_overrides) -> spec.Cell:
    """``name``'s cell (the first in ``BENCHMARK.json`` by default) with the U-Net at the smoke size and a round of
    four services whose deadlines suit a CPU step of a few ms."""
    from repro.configs.ddim_cifar10 import SMOKE
    name = name or spec.load_benchmark()["workloads"][0]["name"]
    cell = spec.resolve(name)
    sizes = dict(cell.sizes)
    sizes.update({k: (list(v) if isinstance(v, tuple) else v)
                  for k, v in dataclasses.asdict(SMOKE).items()})
    mix = json.loads(json.dumps(cell.mix))
    mix.update(services_per_round=4, deadline_ms=[60.0, 160.0],
               total_bandwidth_hz=2.0e6, calibration_reps=1,
               plan_delay={"a": 1.5e-3, "b": 7e-3})
    mix["check"] = dict(mix["check"], services=3, rows=4)
    mix.update(mix_overrides)
    return dataclasses.replace(cell, sizes=sizes, mix=mix)
