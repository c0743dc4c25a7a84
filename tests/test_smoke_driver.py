"""CI gate machinery: RunResult schema validation + the parallel
scenario-smoke driver (repro.launch.smoke) failing on corrupted persisted
results, and the benchmark-regression gate (benchmarks.check_regression)."""

import dataclasses
import json
import pathlib
import sys

import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from benchmarks import check_regression  # noqa: E402
from repro.exp import (CANONICAL_METRICS, REQUIRED_SERIES,  # noqa: E402
                       RunResult, validate_run_result)
from repro.launch import smoke  # noqa: E402


def _valid_rr(engine="serving", scenario="serve_yahoo") -> RunResult:
    metrics = {m: 1.0 for m in CANONICAL_METRICS}
    series = {name: np.arange(3.0)
              for name in REQUIRED_SERIES.get(engine, ())}
    return RunResult(engine=engine, scenario=scenario,
                     config={"n_replicas": 8}, overrides={},
                     metrics=metrics, series=series, seed=42, sim_seed=42)


# ------------------------------------------------------ validate_run_result

def test_validate_accepts_valid_results():
    for engine in ("des", "fluid", "serving"):
        assert validate_run_result(_valid_rr(engine)) == []


@pytest.mark.parametrize("corrupt,needle", [
    (dict(metrics={m: 1.0 for m in CANONICAL_METRICS[1:]}),
     "missing canonical metrics"),
    (dict(metrics={**{m: 1.0 for m in CANONICAL_METRICS},
                   "short_avg_wait_s": float("nan")}),
     "non-finite canonical metrics"),
    (dict(series={"short_waits": np.empty(0),
                  "active_transients": np.arange(3.0),
                  "batch_occupancy": np.arange(3.0)}),
     "empty series"),
    (dict(series={"active_transients": np.arange(3.0),
                  "batch_occupancy": np.arange(3.0)}),
     "missing series"),
    (dict(seed=None), "seed"),
    (dict(sim_seed=None), "sim_seed"),
    (dict(config={}), "config missing"),
    (dict(schema_version=99), "schema_version"),
])
def test_validate_flags_each_corruption(corrupt, needle):
    rr = dataclasses.replace(_valid_rr("serving"), **corrupt)
    problems = validate_run_result(rr)
    assert problems and any(needle in p for p in problems), problems


def test_validate_real_quick_run_is_clean():
    from repro.exp import run

    rr = run("serve_yahoo", "serving", quick=True, seed=7, sim_seed=3,
             trace_overrides=dict(n_servers=150, n_short=8,
                                  horizon=2 * 3600.0))
    assert validate_run_result(rr) == []


# ------------------------------------------------------------ smoke driver

def test_smoke_validate_only_passes_on_clean_dir(tmp_path, capsys):
    _valid_rr().save(tmp_path / "serve_yahoo-serving.runresult.npz")
    assert smoke.main(["--validate-only", "--out-dir", str(tmp_path)]) == 0
    assert "PASS" in capsys.readouterr().out


def test_smoke_fails_on_deliberately_corrupted_runresult(tmp_path, capsys):
    """The acceptance gate: a corrupted persisted RunResult (canonical
    metric dropped) must fail the driver, not just a crashed run."""
    _valid_rr(scenario="good").save(tmp_path / "good-serving.runresult.npz")
    bad = dataclasses.replace(
        _valid_rr(scenario="bad"),
        metrics={m: 1.0 for m in CANONICAL_METRICS[2:]})
    bad.save(tmp_path / "bad-serving.runresult.npz")
    assert smoke.main(["--validate-only", "--out-dir", str(tmp_path)]) == 1
    out = capsys.readouterr().out
    assert "missing canonical metrics" in out and "FAIL" in out


def test_smoke_fails_on_empty_dir(tmp_path):
    assert smoke.main(["--validate-only", "--out-dir", str(tmp_path)]) == 1


def test_smoke_catalog_covers_engines():
    jobs = smoke.catalog(["coaster_r3", "serve_yahoo"])
    assert ("coaster_r3", "des") in jobs and ("coaster_r3", "fluid") in jobs
    assert ("serve_yahoo", "serving") in jobs
    assert ("coaster_r3", "serving") not in jobs


def test_smoke_runs_one_scenario_end_to_end(tmp_path):
    """Serial end-to-end pass over one scenario: runs des+fluid, persists,
    re-loads, validates — the CI job in miniature."""
    rc = smoke.main(["--quick", "--scenario", "eagle", "--processes", "1",
                     "--out-dir", str(tmp_path)])
    assert rc == 0
    assert sorted(p.name for p in tmp_path.glob("*.runresult.npz")) == \
        ["eagle-des.runresult.npz", "eagle-fluid.runresult.npz"]


# ------------------------------------------------- benchmark-regression gate

def _gate(tmp_path, baseline_metrics, artifact_doc):
    (tmp_path / "baselines").mkdir()
    (tmp_path / "bench").mkdir()
    (tmp_path / "baselines" / "x.quick.json").write_text(json.dumps(
        {"artifact": "x.json", "metrics": baseline_metrics}))
    (tmp_path / "bench" / "x.json").write_text(json.dumps(artifact_doc))
    return check_regression.main(["--artifacts", str(tmp_path / "bench"),
                                  "--baselines", str(tmp_path / "baselines")])


def test_gate_passes_within_tolerance(tmp_path):
    rc = _gate(tmp_path,
               {"a.wait": {"value": 100.0, "rel_tol": 0.2,
                           "direction": "lower"},
                "ladder.1.occ": {"value": 0.5, "rel_tol": 0.2}},
               {"a": {"wait": 110.0}, "ladder": [{}, {"occ": 0.55}]})
    assert rc == 0


def test_gate_fails_on_regression_in_bad_direction(tmp_path):
    rc = _gate(tmp_path, {"a.wait": {"value": 100.0, "rel_tol": 0.2,
                                     "direction": "lower"}},
               {"a": {"wait": 130.0}})
    assert rc == 1


def test_gate_ignores_improvement_in_good_direction(tmp_path):
    rc = _gate(tmp_path, {"a.wait": {"value": 100.0, "rel_tol": 0.2,
                                     "direction": "lower"}},
               {"a": {"wait": 10.0}})  # 10x better: not a regression
    assert rc == 0


def test_gate_fails_on_missing_metric_path_and_artifact(tmp_path):
    rc = _gate(tmp_path, {"nope.gone": {"value": 1.0}}, {"a": 1})
    assert rc == 1
    # a path resolving to a non-scalar is a FAIL row, not a crash
    (tmp_path / "bench" / "x.json").write_text(json.dumps({"nope": {"gone":
                                                                    [1, 2]}}))
    rc = check_regression.main(["--artifacts", str(tmp_path / "bench"),
                                "--baselines", str(tmp_path / "baselines")])
    assert rc == 1
    rc = check_regression.main(
        ["--artifacts", str(tmp_path / "nowhere"),
         "--baselines", str(tmp_path / "baselines")])
    assert rc == 1


def test_gate_two_sided_direction_both(tmp_path):
    base = {"occ": {"value": 0.5, "rel_tol": 0.1}}
    assert _gate(tmp_path, base, {"occ": 0.7}) == 1  # +40% drift fails
    (tmp_path / "bench" / "x.json").write_text(json.dumps({"occ": 0.52}))
    assert check_regression.main(
        ["--artifacts", str(tmp_path / "bench"),
         "--baselines", str(tmp_path / "baselines")]) == 0


def test_committed_serving_baseline_shape():
    """The committed baseline must point at serving.json and gate the slot
    ladder (the satellite wiring this PR adds)."""
    root = pathlib.Path(__file__).resolve().parents[1]
    spec = json.loads(
        (root / "benchmarks" / "baselines" / "serving.quick.json")
        .read_text())
    assert spec["artifact"] == "serving.json"
    assert any(k.startswith("slot_ladder.") for k in spec["metrics"])
    for mspec in spec["metrics"].values():
        assert "value" in mspec
        assert mspec.get("direction", "both") in ("lower", "higher", "both")


# ------------------------------------------------------- compile cache dir

def test_compile_cache_dir_follows_env_else_repo(monkeypatch):
    """Launchers keep JAX's persistent cache in $JAX_COMPILATION_CACHE_DIR
    (set nothing then) or at the fixed in-checkout ``.jax_cache``."""
    import jax

    from repro.launch.cache import DEFAULT_CACHE_DIR, enable_compile_cache

    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert enable_compile_cache() == str(DEFAULT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    root = pathlib.Path(__file__).resolve().parents[1]
    assert DEFAULT_CACHE_DIR == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
