"""``BENCHMARK.json`` and the files it names, found by name.

A cell names a configuration and a traffic mix; each lives in files of
its own, so a later change adds a cell or a metric by adding files:

* ``chipbench/configs/<config>.json``  the sizes as run, with the plain
  reference beside it in ``chipbench/configs/<config>.py``;
* ``chipbench/mixes/<traffic>.json``   the mix's parameters;
* ``chipbench/metrics/<metric>.py``    the reader of a per-layer metric.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
HERE = Path(__file__).resolve().parent

NAME_RE = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT_RE = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the BENCHMARK.json entry
    sizes: dict            # the configuration file
    mix: dict              # the traffic mix file
    end_to_end: list       # metric entries this cell reports untraced
    per_layer: list        # metric entries this cell reports traced


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell called ``name``, with its files read."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = configs[w["config"]]
    sizes = json.loads((Path(root) / cfg["file"]).read_text())
    mix = json.loads((HERE / "mixes" / f"{w['traffic']}.json").read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=cfg, sizes=sizes, mix=mix,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def _load(path: Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(config_name: str):
    """The plain reference module beside a configuration's file."""
    return _load(HERE / "configs" / f"{config_name}.py",
                 f"chipbench_ref_{config_name.replace('-', '_')}")


def metric_module(metric_name: str):
    """The module of a per-layer metric's reader."""
    return _load(HERE / "metrics" / f"{metric_name}.py",
                 f"chipbench_metric_{metric_name.replace('.', '_')}")


def metric_reader(metric_name: str):
    """``read(obs)`` of a per-layer metric; it returns a number or
    ``None`` where it finds nothing to read."""
    return metric_module(metric_name).read
