"""BENCHMARK.json names files that exist, in names its format allows."""

import json
import re

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
LINE_RE = re.compile(r"[^\n\t]{1,200}")
PATH_RE = re.compile(r"[A-Za-z0-9_./-]{1,200}")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(BENCH["command"]) <= 32
    assert len(json.dumps(BENCH)) <= 64 * 1024
    for p in BENCH["paths"]:
        assert PATH_RE.fullmatch(p) and not p.startswith("/")
        assert ".." not in p.split("/")
        assert (spec.ROOT / p).is_dir()
    for word in BENCH["command"]:
        assert LINE_RE.fullmatch(word)
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])


def test_names_and_units():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    named = metrics + BENCH["workloads"] + BENCH["configs"]
    for entry in named:
        assert spec.NAME_RE.fullmatch(entry["name"]), entry["name"]
    for group in (metrics, BENCH["workloads"], BENCH["configs"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    for m in metrics:
        assert spec.UNIT_RE.fullmatch(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers_of = {}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert LINE_RE.fullmatch(m["layer"])
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        layers_of.setdefault(m["layer"], []).append(m["name"])
        for w in m.get("workloads", []):
            assert w in CELLS


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_resolve(name):
    cell = spec.resolve(name)
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    for key in ("config", "traffic"):
        assert spec.NAME_RE.fullmatch(w[key])
    assert w["chips"] in (1, 4)
    assert LINE_RE.fullmatch(w["why"])
    assert cell.sizes["name"] == cell.config["name"]
    ref = spec.reference(cell.config["name"])
    assert callable(ref.forward) and callable(ref.make_params)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m["name"]))
        assert m["moves"] in reported
    for key in ("services_per_round", "deadline_ms", "spectral_eff",
                "content_bits", "total_bandwidth_hz", "scheduler",
                "allocator", "execute", "calibration_reps", "plan_delay",
                "check"):
        assert key in cell.mix, key


def test_configs():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert LINE_RE.fullmatch(c["source"]) and LINE_RE.fullmatch(c["why"])
        assert len(c["reduced"]) <= 16
        used = [w for w in BENCH["workloads"] if w["config"] == c["name"]]
        assert used
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
