"""Benchmark tooling (ISSUE 3 satellites): the --json artifact writer,
the compare.py regression gate (must demonstrably fail on a synthetic
regression), and the benchmarks package's src-path shim running from a
clean subprocess with no PYTHONPATH."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:           # `benchmarks` lives at the root,
    sys.path.insert(0, str(ROOT))       # not under pythonpath=src

from benchmarks import compare, run as bench_run   # noqa: E402


def _bench_file(tmp_path, suite, rows):
    payload = {"suite": suite, "git_sha": "deadbeef", "elapsed_s": 0.1,
               "rows": [{"name": n, "value": v, "derived": d}
                        for n, v, d in rows]}
    p = tmp_path / f"BENCH_{suite}.json"
    p.write_text(json.dumps(payload))
    return p


BASELINE = {"metrics": {
    "online_r0.5_stacking": {"value": 6.0, "kind": "lower_is_better",
                             "rel_tol": 0.05},
    "online_stacking_best": {"value": 1.0, "kind": "flag"},
}}


class TestCompareGate:
    def test_passes_within_tolerance(self, tmp_path):
        p = _bench_file(tmp_path, "online",
                        [("online_r0.5_stacking", 6.2, ""),
                         ("online_stacking_best", 1.0, "")])
        assert compare.compare(BASELINE, compare.load_measured([p])) == []

    def test_improvement_always_passes(self, tmp_path):
        p = _bench_file(tmp_path, "online",
                        [("online_r0.5_stacking", 1.0, ""),
                         ("online_stacking_best", 1.0, "")])
        assert compare.compare(BASELINE, compare.load_measured([p])) == []

    def test_fid_regression_fails(self, tmp_path):
        p = _bench_file(tmp_path, "online",
                        [("online_r0.5_stacking", 6.5, ""),
                         ("online_stacking_best", 1.0, "")])
        findings = compare.compare(BASELINE, compare.load_measured([p]))
        assert len(findings) == 1
        assert "online_r0.5_stacking" in findings[0]

    def test_flag_drop_fails(self, tmp_path):
        p = _bench_file(tmp_path, "online",
                        [("online_r0.5_stacking", 6.0, ""),
                         ("online_stacking_best", 0.0, "")])
        findings = compare.compare(BASELINE, compare.load_measured([p]))
        assert len(findings) == 1
        assert "flag dropped" in findings[0]

    def test_missing_metric_fails(self, tmp_path):
        p = _bench_file(tmp_path, "online",
                        [("online_r0.5_stacking", 6.0, "")])
        findings = compare.compare(BASELINE, compare.load_measured([p]))
        assert any("missing" in f for f in findings)

    def test_missing_flag_reports_suite_not_keyerror(self, tmp_path):
        """A gated FLAG absent from the artifacts (vs merely 0) must
        come back as a readable 'missing flag' finding naming the
        owning suite — never a KeyError."""
        base = {"metrics": {
            "planner_jax_sharded_ok": {"value": 1.0, "kind": "flag"},
            "churn_handoff_sane": {"value": 1.0, "kind": "flag"},
        }}
        p = _bench_file(tmp_path, "online",
                        [("online_r0.5_stacking", 6.0, "")])
        findings = compare.compare(base, compare.load_measured([p]))
        assert len(findings) == 2
        sharded = next(f for f in findings
                       if "planner_jax_sharded_ok" in f)
        assert "missing flag" in sharded
        assert "suite 'planner_speed'" in sharded
        churn = next(f for f in findings if "churn_handoff_sane" in f)
        assert "missing flag" in churn
        assert "suite 'churn'" in churn

    def test_suite_of_prefix_map(self):
        assert compare.suite_of("planner_tstar_K64_vec_ms") \
            == "planner_speed"
        assert compare.suite_of("offset_beats_shared_under_churn") \
            == "churn"
        assert compare.suite_of("multiserver_greedy") == "multiserver"
        assert compare.suite_of("api_schedulers") == "api"
        assert compare.suite_of("something_else") == "unknown"

    def test_unknown_kind_fails(self):
        base = {"metrics": {"x": {"value": 1.0, "kind": "sideways"}}}
        assert compare.compare(base, {"x": 1.0})

    def test_main_exit_codes(self, tmp_path):
        base_path = tmp_path / "baseline.json"
        base_path.write_text(json.dumps(BASELINE))
        good = _bench_file(tmp_path, "good",
                           [("online_r0.5_stacking", 6.0, ""),
                            ("online_stacking_best", 1.0, "")])
        assert compare.main([str(good),
                             "--baseline", str(base_path)]) == 0
        bad = _bench_file(tmp_path, "bad",
                          [("online_r0.5_stacking", 99.0, ""),
                           ("online_stacking_best", 1.0, "")])
        assert compare.main([str(bad),
                             "--baseline", str(base_path)]) == 1

    def test_update_refreshes_values_keeping_specs(self, tmp_path):
        base_path = tmp_path / "baseline.json"
        base_path.write_text(json.dumps(BASELINE))
        p = _bench_file(tmp_path, "online",
                        [("online_r0.5_stacking", 4.2, ""),
                         ("online_stacking_best", 1.0, "")])
        assert compare.main([str(p), "--baseline", str(base_path),
                             "--update"]) == 0
        refreshed = json.loads(base_path.read_text())
        m = refreshed["metrics"]["online_r0.5_stacking"]
        assert m["value"] == 4.2
        assert m["rel_tol"] == 0.05
        assert m["kind"] == "lower_is_better"

    def test_committed_baseline_gates_known_suites(self):
        """The repo baseline must only gate metrics the CI bench job
        actually produces (api, online, multiserver, churn, fleet,
        e2e, planner_speed suites)."""
        baseline = json.loads(
            (ROOT / "benchmarks" / "baseline.json").read_text())
        assert baseline["metrics"], "baseline must gate something"
        for name, spec in baseline["metrics"].items():
            # "exec_*" rows come out of the e2e suite (the execution
            # engine comparison), see _SUITE_PREFIXES in compare.py
            assert name.split("_")[0] in ("online", "multiserver",
                                          "api", "churn", "offset",
                                          "planner", "fleet", "e2e",
                                          "exec")
            assert spec["kind"] in ("flag", "lower_is_better")
        # every required suite is one the CI bench job runs (ci.yml)
        assert set(baseline["required_suites"]) == \
            {"api", "online", "multiserver", "churn", "fleet",
             "planner_speed", "e2e"}

    def test_fleet_flags_are_gated(self):
        """ISSUE 8 acceptance: the bench gate must pin the fleet
        population/memory/equivalence claims at 1."""
        baseline = json.loads(
            (ROOT / "benchmarks" / "baseline.json").read_text())
        m = baseline["metrics"]
        for flag in ("fleet_matches_multiserver",
                     "fleet_1m_services_ok", "fleet_bounded_memory"):
            assert m[flag] == {"value": 1.0, "kind": "flag"}
        # jax-vs-vec parity is gated at the documented 1e-9 tolerance
        parity = m["fleet_jax_vs_vec_fid_diff"]
        assert parity["kind"] == "lower_is_better"
        assert parity["tolerance"] == 0.0
        assert parity["abs_tol"] == 1e-9

    def test_planner_speed_flags_are_gated(self):
        """ISSUE 5 acceptance: the bench gate must pin the >=5x
        vec-speedup claim and the bit-identical-plans flag at 1."""
        baseline = json.loads(
            (ROOT / "benchmarks" / "baseline.json").read_text())
        m = baseline["metrics"]
        assert m["planner_vec_speedup_5x"] == \
            {"value": 1.0, "kind": "flag"}
        assert m["planner_vec_equivalent"] == \
            {"value": 1.0, "kind": "flag"}

    def test_churn_dominance_flag_is_gated(self):
        """ISSUE 4 acceptance: the bench gate must pin the offset-vs-
        shared dominance claim and the handoff sanity flag at 1."""
        baseline = json.loads(
            (ROOT / "benchmarks" / "baseline.json").read_text())
        m = baseline["metrics"]
        assert m["offset_beats_shared_under_churn"] == \
            {"value": 1.0, "kind": "flag"}
        assert m["churn_handoff_sane"] == {"value": 1.0, "kind": "flag"}


class TestToleranceOverride:
    """Per-row ``tolerance`` key: overrides the 5% default (and any
    ``rel_tol``), survives ``--update``."""

    def test_tolerance_overrides_default(self):
        base = {"metrics": {"online_x": {
            "value": 10.0, "kind": "lower_is_better",
            "tolerance": 0.5}}}
        # 40% worse: fails the 5% default, passes the 50% override
        assert compare.compare(base, {"online_x": 14.0}) == []

    def test_zero_tolerance_is_tight(self):
        base = {"metrics": {"online_x": {
            "value": 10.0, "kind": "lower_is_better",
            "tolerance": 0.0}}}
        assert compare.compare(base, {"online_x": 10.2})
        assert compare.compare(base, {"online_x": 10.0}) == []

    def test_tolerance_wins_over_rel_tol(self):
        base = {"metrics": {"online_x": {
            "value": 10.0, "kind": "lower_is_better",
            "rel_tol": 0.5, "tolerance": 0.01}}}
        assert compare.compare(base, {"online_x": 10.5})

    def test_gate_limit_default(self):
        rel, abs_tol, limit = compare.gate_limit(
            {"value": 10.0, "kind": "lower_is_better"})
        assert rel == compare.DEFAULT_REL_TOL
        assert limit == pytest.approx(10.5, abs=1e-6)

    def test_update_roundtrips_tolerance(self, tmp_path):
        base = {"metrics": {
            "online_r0.5_stacking": {"value": 6.0,
                                     "kind": "lower_is_better",
                                     "tolerance": 0.01,
                                     "abs_tol": 1e-6},
            "online_stacking_best": {"value": 1.0, "kind": "flag"},
        }}
        base_path = tmp_path / "baseline.json"
        base_path.write_text(json.dumps(base))
        p = _bench_file(tmp_path, "online",
                        [("online_r0.5_stacking", 5.5, ""),
                         ("online_stacking_best", 1.0, "")])
        assert compare.main([str(p), "--baseline", str(base_path),
                             "--update"]) == 0
        refreshed = json.loads(base_path.read_text())
        m = refreshed["metrics"]["online_r0.5_stacking"]
        assert m == {"value": 5.5, "kind": "lower_is_better",
                     "tolerance": 0.01, "abs_tol": 1e-6}


class TestGithubSummary:
    """--github-summary markdown rendering + the $GITHUB_STEP_SUMMARY
    append path."""

    BASE = {"metrics": {
        "online_r0.5_stacking": {"value": 6.0, "kind": "lower_is_better",
                                 "tolerance": 0.1},
        "online_stacking_best": {"value": 1.0, "kind": "flag"},
        "churn_handoff_sane": {"value": 1.0, "kind": "flag"},
    }}

    def test_all_pass_renders_green(self):
        md = compare.github_summary(
            self.BASE, {"online_r0.5_stacking": 6.2,
                        "online_stacking_best": 1.0,
                        "churn_handoff_sane": 1.0}, [])
        assert "**PASSED**" in md
        assert "❌" not in md
        assert md.count("✅") == 3
        # one table row per gated metric, with its gate limit
        assert "| `online_r0.5_stacking` | lower_is_better | 6.0000 " \
            "| 6.2000 | <= 6.6000 | ✅ |" in md

    def test_failures_render_red(self):
        md = compare.github_summary(
            self.BASE, {"online_r0.5_stacking": 9.0,
                        "online_stacking_best": 0.0}, [])
        assert "**FAILED**" in md
        # regressed metric, dropped flag, missing flag
        assert md.count("❌") == 3
        assert "_missing_" in md

    def test_suite_findings_listed(self):
        md = compare.github_summary(
            self.BASE, {"online_r0.5_stacking": 6.0,
                        "online_stacking_best": 1.0,
                        "churn_handoff_sane": 1.0},
            ["required suite 'fleet' has no BENCH_*.json among the "
             "measured files"])
        assert "**FAILED**" in md
        assert "⚠️" in md and "'fleet'" in md

    def test_main_appends_to_step_summary(self, tmp_path, monkeypatch):
        base_path = tmp_path / "baseline.json"
        base_path.write_text(json.dumps(BASELINE))
        p = _bench_file(tmp_path, "online",
                        [("online_r0.5_stacking", 6.0, ""),
                         ("online_stacking_best", 1.0, "")])
        summary = tmp_path / "summary.md"
        summary.write_text("prior content\n")
        monkeypatch.setenv("GITHUB_STEP_SUMMARY", str(summary))
        assert compare.main([str(p), "--baseline", str(base_path),
                             "--github-summary"]) == 0
        text = summary.read_text()
        assert text.startswith("prior content\n")   # appended, not clobbered
        assert "### Benchmark regression gate" in text
        assert "**PASSED**" in text

    def test_main_without_env_prints(self, tmp_path, monkeypatch,
                                     capsys):
        base_path = tmp_path / "baseline.json"
        base_path.write_text(json.dumps(BASELINE))
        p = _bench_file(tmp_path, "online",
                        [("online_r0.5_stacking", 6.0, ""),
                         ("online_stacking_best", 1.0, "")])
        monkeypatch.delenv("GITHUB_STEP_SUMMARY", raising=False)
        assert compare.main([str(p), "--baseline", str(base_path),
                             "--github-summary"]) == 0
        assert "### Benchmark regression gate" in capsys.readouterr().out


class TestRequiredSuites:
    """A suite dropped from the CI bench invocation must fail the gate
    even if its gated metrics were pruned from the baseline."""

    BASE = {"metrics": dict(BASELINE["metrics"]),
            "required_suites": ["online", "churn"]}

    def test_all_suites_present_passes(self, tmp_path):
        p1 = _bench_file(tmp_path, "online",
                         [("online_r0.5_stacking", 6.0, ""),
                          ("online_stacking_best", 1.0, "")])
        p2 = _bench_file(tmp_path, "churn", [("x", 1.0, "")])
        assert compare.check_suites(
            self.BASE, compare.load_suites([p1, p2])) == []

    def test_missing_suite_fails(self, tmp_path):
        p1 = _bench_file(tmp_path, "online",
                         [("online_r0.5_stacking", 6.0, ""),
                          ("online_stacking_best", 1.0, "")])
        findings = compare.check_suites(self.BASE,
                                        compare.load_suites([p1]))
        assert len(findings) == 1
        assert "churn" in findings[0]

    def test_main_fails_on_missing_suite(self, tmp_path):
        base_path = tmp_path / "baseline.json"
        base_path.write_text(json.dumps(self.BASE))
        p1 = _bench_file(tmp_path, "online",
                         [("online_r0.5_stacking", 6.0, ""),
                          ("online_stacking_best", 1.0, "")])
        assert compare.main([str(p1),
                             "--baseline", str(base_path)]) == 1

    def test_update_preserves_required_suites(self, tmp_path):
        base_path = tmp_path / "baseline.json"
        base_path.write_text(json.dumps(self.BASE))
        p1 = _bench_file(tmp_path, "online",
                         [("online_r0.5_stacking", 4.0, ""),
                          ("online_stacking_best", 1.0, "")])
        p2 = _bench_file(tmp_path, "churn", [("x", 1.0, "")])
        assert compare.main([str(p1), str(p2),
                             "--baseline", str(base_path),
                             "--update"]) == 0
        refreshed = json.loads(base_path.read_text())
        assert refreshed["required_suites"] == ["online", "churn"]

    def test_update_refuses_partial_measurement(self, tmp_path):
        """A refresh from files missing a required suite must fail
        instead of silently keeping that suite's stale values."""
        base_path = tmp_path / "baseline.json"
        base_path.write_text(json.dumps(self.BASE))
        p1 = _bench_file(tmp_path, "online",
                         [("online_r0.5_stacking", 4.0, ""),
                          ("online_stacking_best", 1.0, "")])
        assert compare.main([str(p1), "--baseline", str(base_path),
                             "--update"]) == 1
        unchanged = json.loads(base_path.read_text())
        assert unchanged["metrics"]["online_r0.5_stacking"]["value"] \
            == 6.0

    def test_update_refuses_crashed_suite(self, tmp_path):
        """A suite that crashed still writes its BENCH json (with only
        an <suite>_ERROR row), so the suite-name check passes — the
        refresh must still refuse because gated metrics are missing."""
        base_path = tmp_path / "baseline.json"
        base_path.write_text(json.dumps(self.BASE))
        p1 = _bench_file(tmp_path, "online",
                         [("online_ERROR", 0.0, "RuntimeError('x')")])
        p2 = _bench_file(tmp_path, "churn", [("x", 1.0, "")])
        assert compare.main([str(p1), str(p2),
                             "--baseline", str(base_path),
                             "--update"]) == 1
        unchanged = json.loads(base_path.read_text())
        assert unchanged["metrics"]["online_r0.5_stacking"]["value"] \
            == 6.0


class TestJsonWriter:
    def test_write_json_roundtrip(self, tmp_path):
        path = bench_run.write_json(
            tmp_path / "out", "demo",
            [("a", 1.0, "x"), ("b", 2.5, "y")], 1.234, "cafebabe")
        assert path.name == "BENCH_demo.json"
        payload = json.loads(path.read_text())
        assert payload["suite"] == "demo"
        assert payload["git_sha"] == "cafebabe"
        assert payload["elapsed_s"] == 1.234
        assert payload["rows"][1] == {"name": "b", "value": 2.5,
                                      "derived": "y"}
        # the planner engine is stamped next to workers/devices so
        # nightly refreshes can tell configuration trends apart; the
        # denoising session has one engine, so nothing names it
        assert payload["engine"] in ("vec", "scalar", "jax")
        assert "exec_engine" not in payload

    def test_git_sha_is_nonempty(self):
        assert bench_run.git_sha()


class TestBenchShim:
    """The benchmarks/__init__.py src-path shim (ISSUE 3 satellite):
    idempotent, and sufficient for a clean subprocess with no
    PYTHONPATH."""

    @pytest.fixture
    def clean_env(self):
        env = {k: v for k, v in os.environ.items()
               if k not in ("PYTHONPATH",)}
        env["JAX_PLATFORMS"] = "cpu"
        return env

    def test_run_list_from_clean_subprocess(self, clean_env):
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.run", "--list"],
            cwd=ROOT, env=clean_env, capture_output=True, text=True,
            timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        suites = proc.stdout.split()
        assert "multiserver" in suites
        assert "online" in suites
        assert "api" in suites
        assert "churn" in suites

    def test_shim_is_idempotent(self, clean_env):
        src = ("import importlib, sys, benchmarks;"
               "importlib.reload(benchmarks);"
               "import benchmarks as b2;"
               "src = [p for p in sys.path if p.rstrip('/').endswith('src')];"
               "assert len(src) <= 1, sys.path;"
               "import repro;"
               "print('ok')")
        proc = subprocess.run(
            [sys.executable, "-c", src], cwd=ROOT, env=clean_env,
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert proc.stdout.strip() == "ok"


class TestDriverFailures:
    def test_raising_suite_keeps_error_row_and_exits_nonzero(
            self, monkeypatch, tmp_path, capsys):
        def broken(rows):
            rows.append(("broken_partial", 1.0, ""))
            raise RuntimeError("suite blew up")

        # with the variable set, compile_cache.enable leaves JAX's
        # config alone, so this process's cache stays as it was
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setitem(bench_run.SUITES, "broken", broken)
        monkeypatch.setitem(bench_run.SUITES, "fine",
                            lambda rows: rows.append(("fine_ok", 2.0, "")))
        assert bench_run.main(["--only", "broken,fine"]) == 1
        out = capsys.readouterr().out
        assert "broken_ERROR,0.0000,RuntimeError('suite blew up')" in out
        assert "fine_ok,2.0000," in out        # later suites still run
        assert bench_run.main(["--only", "fine"]) == 0

    def test_parallel_map_refuses_fanout_on_tpu(self, monkeypatch):
        import jax
        from benchmarks import par
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="workers=1"):
            par.parallel_map(abs, [-1, -2], workers=2)
        assert par.parallel_map(abs, [-1, -2], workers=1) == [1, 2]
