"""The unified experiment API (repro.exp): RunResult schema + round-trips,
refactor-equivalence of run(engine="des") with the legacy Scenario.run()
path, grid sweeps on both engines, the declarative override spec, and the
fluid-vs-DES calibration tolerance."""

import json

import numpy as np
import pytest

from repro.core.metrics import _pctl
from repro.exp import (CANONICAL_METRICS, RunResult, SweepResult, calibrate,
                       compare_engines, resolve_overrides, run, sweep)
from repro.sched import FluidPolicyParams, get_scenario

#: test-sized cluster (same as tests/test_sched.py) so DES runs stay fast
SMALL = dict(n_servers=150, n_short=8)
SMALL_SIM = dict(n_servers=150, n_short_reserved=8)
SMALL_KW = dict(quick=True, trace_overrides=dict(SMALL, horizon=2 * 3600.0),
                sim_overrides=SMALL_SIM)


# ------------------------------------------------------------- _pctl helper

def test_pctl_shared_guard():
    assert _pctl(np.empty(0), 99) == 0.0
    arr = np.arange(101.0)
    assert _pctl(arr, 50) == float(np.percentile(arr, 50))


# ----------------------------------------------------------- schema + I/O

def _small_des():
    return run("coaster_r3", "des", seed=7, **SMALL_KW)


def test_runresult_schema_and_roundtrip(tmp_path):
    rr = _small_des()
    assert rr.engine == "des" and rr.scenario == "coaster_r3"
    assert all(m in rr.metrics for m in CANONICAL_METRICS)
    assert rr.series["short_waits"].size > 0
    assert rr.meta["trace"]["n_jobs"] > 0
    for name in ("a.json", "a.npz", "a.runresult"):  # npz appended to last
        back = RunResult.load(rr.save(tmp_path / name))
        assert back.equals(rr), name
    # deterministic JSON: same result -> same string, sorted keys
    assert rr.to_json() == RunResult.load(rr.save(tmp_path / "b.json")).to_json()


def test_run_des_byte_identical_to_legacy_scenario_run():
    """run(engine="des") must reproduce the legacy Scenario.run() path
    exactly on the quick presets — metrics dict (keys, order, floats) and
    the persisted series."""
    for name in ("coaster_r3", "eagle"):
        sc = get_scenario(name)
        tr = sc.trace(quick=True, seed=42)
        legacy = sc.run(quick=True, trace=tr)
        rr = run(name, "des", quick=True, seed=42, trace=tr)
        assert json.dumps(rr.metrics, indent=1, default=float) == \
            json.dumps(legacy.summary(), indent=1, default=float)
        assert np.array_equal(rr.series["short_waits"], legacy.short_waits)
        assert np.array_equal(rr.series["long_waits"], legacy.long_waits)
        assert np.array_equal(rr.series["transient_lifetimes"],
                              legacy.transient_lifetimes)


def test_fluid_engine_same_schema_and_series_kept():
    rr = run("coaster_r3", "fluid", seed=7, **SMALL_KW)
    assert rr.engine == "fluid"
    assert all(m in rr.metrics for m in CANONICAL_METRICS)
    # the previously-discarded fluid time series survive
    assert rr.series["short_delay"].size > 0
    assert rr.series["lr"].shape == rr.series["n_transient"].shape
    # percentiles flow through the shared _pctl guard
    assert rr.metrics["short_p90_wait_s"] == _pctl(rr.series["short_delay"],
                                                   90)
    # asking a fluid result for the DES series name raises, not zero-CDF
    with pytest.raises(KeyError, match="short_delay"):
        rr.cdf("short_waits")


# ---------------------------------------------------------- serving engine

#: serving presets registered by the scenario catalog
SERVE_PRESETS = ("serve_yahoo", "serve_flash_crowd", "serve_spot")
SERVE_KW = dict(quick=True, seed=7, sim_seed=3,
                trace_overrides=dict(SMALL, horizon=2 * 3600.0))


def test_serving_engine_schema_all_presets(tmp_path):
    for name in SERVE_PRESETS:
        rr = run(name, "serving", **SERVE_KW)
        assert rr.engine == "serving" and rr.scenario == name
        assert all(m in rr.metrics for m in CANONICAL_METRICS), name
        for extra in ("n_hedges", "n_revocations", "n_done"):
            assert extra in rr.metrics, name
        # per-request wait series survives, percentile guard shared
        assert rr.metrics["short_p90_wait_s"] == _pctl(
            rr.series["short_waits"], 90)
        assert rr.series["active_transients"].size > 0
        back = RunResult.load(rr.save(tmp_path / f"{name}.npz"))
        assert back.equals(rr), name


def test_serving_engine_deterministic():
    """Same (scenario, seed) => identical RunResult JSON (wall time aside)."""
    import dataclasses

    a = run("serve_yahoo", "serving", **SERVE_KW)
    b = run("serve_yahoo", "serving", **SERVE_KW)
    a0 = dataclasses.replace(a, wall_time_s=0.0)
    b0 = dataclasses.replace(b, wall_time_s=0.0)
    assert a0.to_json(include_series=True) == b0.to_json(include_series=True)


def test_serving_sweep_pointwise():
    grid = {"threshold": [0.4, 0.6], "max_transient": [4, 12]}
    sr = sweep("serve_yahoo", grid, engine="serving", **SERVE_KW)
    assert sr.shape == (2, 2) and sr.engine == "serving"
    pt = sr.at(threshold=0.4, max_transient=12)
    one = run("serve_yahoo", "serving",
              sim_overrides={"threshold": 0.4, "max_transient": 12},
              **SERVE_KW)
    assert pt["short_avg_wait_s"] == one.metrics["short_avg_wait_s"]
    # a bigger transient budget can only help the short delay
    lo = sr.at(threshold=0.4, max_transient=4)["short_avg_wait_s"]
    assert pt["short_avg_wait_s"] <= lo


def test_sweep_keeps_non_des_engines_out_of_the_pool(monkeypatch):
    """processes=N fans out DES only: any other engine runs in the calling
    process (a JAX child of a parent holding the chip fails or hangs)."""
    import concurrent.futures

    def no_pool(*a, **k):
        raise AssertionError("a non-DES engine went to a process pool")

    monkeypatch.setattr(concurrent.futures, "ProcessPoolExecutor", no_pool)
    sr = sweep("serve_yahoo", {"threshold": [0.4, 0.6]}, engine="serving",
               processes=2, **SERVE_KW)
    assert sr.shape == (2,) and sr.engine == "serving"


def test_serving_beats_static_at_equal_budget():
    """The acceptance comparison behind benchmarks/serving_delay.py: the
    transient-backed preset beats a static fleet of equal-or-higher paid
    budget on short_avg_wait_s."""
    kw = dict(quick=True, seed=42, sim_seed=0)
    elastic = run("serve_flash_crowd", "serving", **kw)
    r = get_scenario("serve_flash_crowd").sim_config(quick=True).cost_ratio
    paid = elastic.metrics["avg_active_transients"] / r
    budget = int(np.ceil(paid))
    static = run("serve_flash_crowd", "serving",
                 sim_overrides={"max_transient": 0, "n_reserve": budget},
                 **kw)
    assert paid <= budget
    assert elastic.metrics["short_avg_wait_s"] < \
        static.metrics["short_avg_wait_s"]


def test_unknown_engine_and_scenario_raise():
    with pytest.raises(ValueError, match="unknown engine"):
        run("coaster_r3", "no_such_engine", quick=True)
    with pytest.raises(ValueError, match="unknown scenario"):
        run("no_such_scenario", "des", quick=True)


# ---------------------------------------------------------------- overrides

def test_resolve_overrides_matches_legacy_chain():
    trace_over, sim_over = resolve_overrides(
        servers=300, short=16, horizon_h=2.0, p=0.25, r=2.0, threshold=0.9,
        provisioning=60.0, revocation_mttf_h=1.5, burst_mult=None)
    assert trace_over == {"n_servers": 300, "n_short": 16,
                          "horizon": 7200.0}
    assert sim_over == {"n_servers": 300, "n_short_reserved": 16,
                        "replace_fraction": 0.25, "cost_ratio": 2.0,
                        "threshold": 0.9, "provisioning_delay": 60.0,
                        "revocation_mttf": 5400.0}
    # names outside the spec are raw SimConfig fields
    _, sim_over = resolve_overrides(probe_d=3)
    assert sim_over == {"probe_d": 3}


# ------------------------------------------------------------------- sweeps

def test_sweep_fluid_matches_simjax_cube(tmp_path):
    from repro.core.simjax import sweep as jsweep

    sc = get_scenario("coaster_r3")
    tr = sc.trace(quick=True, seed=11,
                  trace_overrides=SMALL_KW["trace_overrides"])
    thr = np.array([0.9, 0.95])
    ks = np.array([0.0, 12.0])
    sr = sweep("coaster_r3", {"threshold": thr, "max_transient": ks},
               engine="fluid", quick=True, trace=tr,
               sim_overrides=SMALL_SIM)
    lw, sw, fcfg, _ = sc.fluid_setup(quick=True, trace=tr,
                                     sim_overrides=SMALL_SIM)
    raw = jsweep(lw, sw, fcfg, thr, ks, policy=sc.fluid_params(quick=True))
    np.testing.assert_allclose(sr.metrics["short_avg_wait_s"],
                               np.asarray(raw["avg_short_delay"]), rtol=1e-6)
    assert sr.shape == (2, 2)
    point = sr.at(threshold=0.95, max_transient=12.0)
    assert point["short_avg_wait_s"] == float(
        sr.metrics["short_avg_wait_s"][1, 1])
    best = sr.best("short_avg_wait_s")
    assert best["short_avg_wait_s"] == float(
        np.min(sr.metrics["short_avg_wait_s"]))
    back = SweepResult.load(sr.save(tmp_path / "grid.npz"))
    assert list(back.axes) == list(sr.axes)
    for k in sr.metrics:
        np.testing.assert_array_equal(back.metrics[k], sr.metrics[k])
    with pytest.raises(ValueError, match="fluid sweep axes"):
        sweep("coaster_r3", {"cost_ratio": [1.0]}, engine="fluid",
              quick=True, trace=tr)


def test_sweep_des_grid_points_match_individual_runs():
    sc = get_scenario("coaster_r1")
    tr = sc.trace(quick=True, seed=7,
                  trace_overrides=SMALL_KW["trace_overrides"])
    sr = sweep("coaster_r1", {"r": [1.0, 3.0], "threshold": [0.9, 0.95]},
               engine="des", quick=True, trace=tr, sim_overrides=SMALL_SIM)
    assert sr.shape == (2, 2) and sr.meta["n_points"] == 4
    single = run("coaster_r1", "des", quick=True, trace=tr,
                 sim_overrides={**SMALL_SIM, "cost_ratio": 3.0,
                                "threshold": 0.9})
    point = sr.at(r=3.0, threshold=0.9)
    assert point["short_avg_wait_s"] == single.metrics["short_avg_wait_s"]
    # a trace-shaped axis is rejected (the trace is shared across the grid)
    with pytest.raises(ValueError, match="changes the trace"):
        sweep("coaster_r1", {"servers": [100, 200]}, engine="des",
              quick=True, trace=tr)


def test_sweep_json_artifact_is_strict_and_roundtrips_nan(tmp_path):
    """p=0 points lack dynamic_partition_cost_saving (NaN in the grid); the
    JSON artifact must stay strictly parseable (null, not bare NaN) and load
    back as NaN."""
    sc = get_scenario("coaster_r1")
    tr = sc.trace(quick=True, seed=7,
                  trace_overrides=SMALL_KW["trace_overrides"])
    sr = sweep("coaster_r1", {"p": [0.0, 0.5]}, engine="des", quick=True,
               trace=tr, sim_overrides=SMALL_SIM)
    assert np.isnan(sr.metrics["dynamic_partition_cost_saving"][0])
    path = sr.save(tmp_path / "grid.json")
    assert "NaN" not in path.read_text()  # strict JSON: null, never NaN
    back = SweepResult.load(path)
    assert np.isnan(back.metrics["dynamic_partition_cost_saving"][0])
    np.testing.assert_array_equal(back.metrics["short_avg_wait_s"],
                                  sr.metrics["short_avg_wait_s"])


# ------------------------------------------------------------- calibration

def test_compare_engines_table_shape():
    table = compare_engines("coaster_r3", quick=True, seed=7)
    row = table["metrics"]["short_avg_wait_s"]
    assert set(row) == {"des", "fluid", "abs_err", "rel_err"}
    assert row["fluid"] - row["des"] == pytest.approx(row["abs_err"])


def test_fluid_vs_des_calibrated_tolerance():
    """The coarse FluidPolicyParams fit must land the fluid short_avg_wait
    within 30% of the DES on the calibrated coaster_r3 quick preset (the
    uncalibrated model is ~85% off), and can never do worse than the
    scenario's own params (the identity is in the fit grid)."""
    out = calibrate("coaster_r3", quick=True, seed=42)
    before = abs(out["before"]["metrics"]["short_avg_wait_s"]["rel_err"])
    after = abs(out["fitted"]["metrics"]["short_avg_wait_s"]["rel_err"])
    assert after <= before + 1e-12
    assert after < 0.30, (before, after, out["fitted"]["policy"])
    pol = FluidPolicyParams(**out["fitted"]["policy"])
    # the fitted params reproduce the fitted error through the public API
    table = compare_engines("coaster_r3", quick=True, seed=42, policy=pol)
    assert table["metrics"]["short_avg_wait_s"]["rel_err"] == pytest.approx(
        out["fitted"]["metrics"]["short_avg_wait_s"]["rel_err"])
